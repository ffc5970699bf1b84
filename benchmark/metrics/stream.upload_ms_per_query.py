# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Mean time per statement flattening chunks and handing them to
``device_put`` (ms): the program's ``prefetch.prepare`` spans, mostly on
the prefetch ring's worker thread. Nothing where the program records no
such span."""


def read(run):
    recs = run["records"]
    vals = [r["phases"]["prefetch.prepare"]["ms"] for r in recs
            if "prefetch.prepare" in r["phases"]]
    return sum(vals) / len(recs) if vals else None
