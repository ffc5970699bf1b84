# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Mean cells per statement moved by whole-table row gathers (index width
x arrays gathered, data and validity): the program's
``phases["op.gather"]["cells"]``. Nothing where no statement reports
it."""


def read(run):
    recs = run["records"]
    vals = [r["phases"]["op.gather"]["cells"] for r in recs
            if "cells" in r["phases"].get("op.gather", {})]
    return sum(vals) / len(recs) if vals else None
