# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Mean host time per statement inside the general join and the semi-join
(ms): self time of the program's ``op.join`` and ``op.semi_join`` spans.

It is WAIT-ATTRIBUTED host time, not the join's device time: a join's
span makes a counted read (the candidate total), and that read waits for
everything the statement issued before it, so gathers and key lookups
queued ahead of the join are in this number, while a semi-join that reads
nothing shows only its dispatch. The join's own device time is the trace's
``nds.join.probe`` / ``.key_hash`` / ``.span_pairs`` and ``nds.semi_join``
scopes (``tools/trace_report.py --profile``). Nothing where no statement
has such a span."""

PHASES = ("op.join", "op.semi_join")


def read(run):
    recs = run["records"]
    vals = [r["phases"][p]["selfMs"] for r in recs for p in PHASES
            if "selfMs" in r["phases"].get(p, {})]
    return sum(vals) / len(recs) if vals else None
