# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Mean cells per statement touched by set operations and by appending
tables: the program's ``phases["op.setop"]["cells"]`` (the key arrays of
both sides at their buckets and the mask out) +
``phases["op.concat"]["cells"]`` (arrays concatenated x the output's
bucket: UNION ALL, the groupings of a ROLLUP, the null-extended misses of
an outer join). Nothing where no statement reports it."""

PHASES = ("op.setop", "op.concat")


def read(run):
    recs = run["records"]
    vals = [r["phases"][p]["cells"] for r in recs for p in PHASES
            if "cells" in r["phases"].get(p, {})]
    return sum(vals) / len(recs) if vals else None
