# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Mean rows per statement that the general join's two binary searches ran
over (the bucket each ``op.join`` searched at: the probe side's, or its
candidates' where the probe narrowed to them first): the program's
``phases["op.join"]["probeRows"]``. Nothing where no statement reports
it."""


def read(run):
    recs = run["records"]
    vals = [r["phases"]["op.join"]["probeRows"] for r in recs
            if "probeRows" in r["phases"].get("op.join", {})]
    return sum(vals) / len(recs) if vals else None
