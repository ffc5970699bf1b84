# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Mean time per statement from the statement's start to the first
dispatch of its first streamed scan's chunk program (ms): the program's
``phases["stream"]["leadInMs"]``. Nothing where no statement reports
it."""


def read(run):
    recs = run["records"]
    vals = [r["phases"]["stream"]["leadInMs"] for r in recs
            if "leadInMs" in r["phases"].get("stream", {})]
    return sum(vals) / len(recs) if vals else None
