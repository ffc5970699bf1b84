# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Mean columns per statement that its catalog scans kept after the
planner's projection pushdown, summed over the statement's scans (what
every later gather, compaction and upload carries): the program's
``phases["plan"]["scanColumns"]``. Nothing where no statement reports
it."""


def read(run):
    recs = run["records"]
    vals = [r["phases"]["plan"]["scanColumns"] for r in recs
            if "scanColumns" in r["phases"].get("plan", {})]
    return sum(vals) / len(recs) if vals else None
