# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Mean host time per statement inside UNION / INTERSECT / EXCEPT (ms):
the inclusive ``ms`` of the program's ``op.setop`` spans, which open around
a set operation's DISTINCT, its null-safe membership and its compaction
(the operands' own scans and joins are outside them).

It is WAIT-ATTRIBUTED host time, not the set operation's device time, as
``resident.join_ms_per_query`` says of itself: the span's counted reads
(the DISTINCT's group count, the membership's candidate total, the
compaction's count) wait for everything the statement issued before them,
so the operands' star joins queued ahead are in this number. The device
time is the trace's ``nds.group_ids`` / ``nds.join`` / ``nds.semi_join``
scopes (``tools/trace_report.py --profile``). Nothing where no statement
has such a span."""


def read(run):
    recs = run["records"]
    vals = [r["phases"]["op.setop"]["ms"] for r in recs
            if "ms" in r["phases"].get("op.setop", {})]
    return sum(vals) / len(recs) if vals else None
