# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Mean cells per statement touched by the general join and the semi-join
(bucket width x arrays: both sides' keys and the indices or mask out, each
counted once): the program's ``phases["op.join"]["cells"]`` +
``phases["op.semi_join"]["cells"]``. Nothing where no statement reports
it."""

PHASES = ("op.join", "op.semi_join")


def read(run):
    recs = run["records"]
    vals = [r["phases"][p]["cells"] for r in recs for p in PHASES
            if "cells" in r["phases"].get(p, {})]
    return sum(vals) / len(recs) if vals else None
