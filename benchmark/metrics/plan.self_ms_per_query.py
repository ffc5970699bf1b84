# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Mean host time per statement the plan layer spends in itself (ms): the
program's ``plan`` and ``parse`` spans minus what their child spans
cover (``selfMs`` of the program's rollup). Nothing where the program
records no self time (a program without span parents)."""


def read(run):
    recs = run["records"]
    vals = [r["phases"][p]["selfMs"] for r in recs
            for p in ("plan", "parse")
            if "selfMs" in r["phases"].get(p, {})]
    return sum(vals) / len(recs) if vals else None
