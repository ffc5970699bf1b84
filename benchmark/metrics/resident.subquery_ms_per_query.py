# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Mean host time per statement inside the subquery evaluators (ms): the
inclusive ``ms`` of the program's ``op.subquery`` spans, which
``Planner._eval_exists`` / ``_eval_in_subquery`` / ``_eval_scalar_subquery``
/ ``_eval_quantified`` open on entry. It holds the inner query's plan where
the evaluator is the first of its statement to ask for it (the span's
``planned``), and the decorrelation: the keys, the join or membership, the
residual on the pair table, the scatter back.

It is WAIT-ATTRIBUTED host time, not the decorrelation's device time, as
``resident.join_ms_per_query`` says of itself: the evaluator's counted
reads (the inner plan's counts, the join's candidate total, the "one row
per outer row" check) wait for everything the statement issued before
them, so the outer statement's star joins queued ahead are in this number.
A subquery inside a subquery is counted in both spans. The device time is
the trace's ``nds.join`` / ``nds.semi_join`` / ``nds.gather`` /
``nds.group_ids`` scopes (``tools/trace_report.py --profile``). Nothing
where no statement has such a span (a program from before the span)."""


def read(run):
    recs = run["records"]
    vals = [r["phases"]["op.subquery"]["ms"] for r in recs
            if "ms" in r["phases"].get("op.subquery", {})]
    return sum(vals) / len(recs) if vals else None
