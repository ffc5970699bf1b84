# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""The closed loop and the arithmetic of the end-to-end metrics.

One client sends the cell's statements in the stream's order, whole passes
only: a new pass starts while fewer than ``seconds`` have elapsed, and the
window ends when the pass in flight ends, so every run holds the same mix.
The clock is the host's, around calls that end in fetched rows.

Both metrics are taken over all the work and all the time of the window:

``power_query_ms``    the window's wall (first call's start to last call's
                      end, stalls between calls included) over the number
                      of statements completed in it
``power_geomean_ms``  geometric mean, over the statement names, of each
                      name's mean wall over all its executions in the window
"""

from __future__ import annotations

import math
import time


def run_passes(names, execute, seconds: float, clock=time.monotonic,
               max_passes: int | None = None) -> dict:
    """Drive ``execute(name, pass_index) -> record`` in a closed loop.
    ``record`` is a dict the caller fills (``ok`` at least); this adds
    ``name``, ``pass``, ``start_s`` and ``end_s`` relative to the window's
    start. Returns {"records", "start", "end", "passes"}."""
    records = []
    start = clock()
    passes = 0
    while True:
        for name in names:
            t0 = clock()
            rec = execute(name, passes)
            t1 = clock()
            rec.update(name=name, **{"pass": passes},
                       start_s=t0 - start, end_s=t1 - start)
            records.append(rec)
        passes += 1
        if clock() - start >= seconds:
            break
        if max_passes is not None and passes >= max_passes:
            break
    return {"records": records, "start": start, "end": clock(),
            "passes": passes}


def attempted_failed(records) -> tuple:
    return len(records), sum(1 for r in records if not r.get("ok"))


def power_query_ms(records) -> float | None:
    """Window wall over completed statements, in ms."""
    done = [r for r in records if r.get("ok")]
    if not done:
        return None
    wall_s = max(r["end_s"] for r in records) - min(
        r["start_s"] for r in records)
    return wall_s * 1e3 / len(done)


def power_geomean_ms(records) -> float | None:
    """Geometric mean over names of the name's mean wall (ms) over all its
    completed executions."""
    by_name: dict = {}
    for r in records:
        if r.get("ok"):
            by_name.setdefault(r["name"], []).append(
                (r["end_s"] - r["start_s"]) * 1e3)
    if not by_name:
        return None
    means = [sum(v) / len(v) for v in by_name.values()]
    if min(means) <= 0:
        return None
    return math.exp(sum(math.log(m) for m in means) / len(means))

