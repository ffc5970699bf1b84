# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Mean arrays per statement (data and validity) that a row gather read
through a composed index, so that a PK-gather join's dimension columns were
never gathered at the fact's bucket: the program's
``phases["op.gather"]["deferredArrays"]``. Nothing where no statement
reports it."""


def read(run):
    recs = run["records"]
    vals = [r["phases"]["op.gather"]["deferredArrays"] for r in recs
            if "deferredArrays" in r["phases"].get("op.gather", {})]
    return sum(vals) / len(recs) if vals else None
