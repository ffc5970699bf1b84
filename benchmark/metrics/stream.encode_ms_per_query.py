# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Mean time per statement the chunk source spends slicing and encoding
(ms): the program's ``prefetch.source`` spans, mostly on the prefetch
ring's worker thread, beside the driver. Nothing where the program records
no such span."""


def read(run):
    recs = run["records"]
    vals = [r["phases"]["prefetch.source"]["ms"] for r in recs
            if "prefetch.source" in r["phases"]]
    return sum(vals) / len(recs) if vals else None
