# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""The subquery evaluators' spans (``op.subquery``) and the DISTINCT
aggregates' (``op.agg[count_distinct | sum_distinct | avg_distinct]``), PR 35:
each arm of ``Planner._eval_exists`` / ``_eval_in_subquery`` /
``_eval_scalar_subquery`` / ``_eval_quantified`` and of ``_count_distinct``
/ ``_sum_avg_distinct`` on toy tables opens its span with the stated ``fn``
/ ``correlated`` / ``residual`` / ``negated`` / ``cells``, makes exactly the
host reads it made before the spans were there (pinned, and equal with
tracing off), and answers as a plain-Python reading of the SQL does, the
NULL cases among them."""

import jax
import jax.numpy as jnp
import pyarrow as pa
import pytest

from nds_tpu.engine import ops as E
from nds_tpu.engine.session import Session
from nds_tpu.obs import export as obs_export
from nds_tpu.obs import trace as obs_trace
from nds_tpu.sql import ast as A
from nds_tpu.sql import planner as P
from nds_tpu.sql.parser import parse

# orders: (ord, wh, cust, amt); ord 7 stands twice, one order has a NULL
# key, one a NULL warehouse, one a NULL customer
ORDERS = [(1, 10, 100, 50), (2, 10, 101, 70), (3, 11, 100, 20),
          (4, None, 102, 90), (None, 12, 103, 10), (6, 12, None, 40),
          (7, 10, 104, 60), (7, 11, 104, 65), (8, 13, 105, 5)]
# lines: (ord, wh, amt); order 1 ships from two warehouses, order 2 from its
# own alone, order 3 has a line with a NULL warehouse, a line has no order
LINES = [(1, 10, 30), (1, 11, 40), (2, 10, 80), (3, None, 25), (3, 11, 15),
         (4, 12, 90), (7, 10, 10), (7, 10, 20), (None, 10, 99), (9, 14, 1)]
RETURNS = [1, 7, 9]                      # ord
RETURNS_WITH_NULL = [1, 7, None]


def session():
    s = Session()
    cols = list(zip(*ORDERS))
    s.create_temp_view("o", pa.table({
        n: pa.array(c, pa.int64())
        for n, c in zip(("ord", "wh", "cust", "amt"), cols)}))
    cols = list(zip(*LINES))
    s.create_temp_view("l", pa.table({
        n: pa.array(c, pa.int64())
        for n, c in zip(("ord", "wh", "amt"), cols)}))
    s.create_temp_view("r", pa.table({"ord": pa.array(RETURNS, pa.int64())}))
    s.create_temp_view("rn", pa.table({
        "ord": pa.array(RETURNS_WITH_NULL, pa.int64())}))
    return s


# -- the SQL read in plain Python: three-valued logic, None = unknown ---------

def eq(a, b):
    return None if a is None or b is None else a == b


def not3(v):
    return None if v is None else not v


def in3(x, values):
    """``x IN (values)``: true on a match, else unknown where ``x`` or a
    value is NULL, else false (an empty list: false)."""
    hits = [eq(x, v) for v in values]
    if any(h is True for h in hits):
        return True
    return None if any(h is None for h in hits) else False


def lines_of(ord_):
    return [ln for ln in LINES if eq(ln[0], ord_) is True]


def avg(values):
    values = [v for v in values if v is not None]
    return sum(values) / len(values) if values else None


def gt(a, b):
    return None if a is None or b is None else a > b


ALL_LINE_AMTS = [ln[2] for ln in LINES]

# case -> (the predicate in SQL, the same in Python, the spans it opens as
# (fn, correlated, residual, negated), host reads of the statement)
CASES = {
    "exists": (
        "exists (select * from l where l.ord = o.ord)",
        lambda ord_, wh, cust, amt: bool(lines_of(ord_)),
        [("exists", 1, 0, 0)], 2),
    "exists-residual-null-warehouse": (
        # <> over a NULL warehouse (order 4's own, a line of order 3) is
        # unknown: no pair
        "exists (select * from l where l.ord = o.ord and l.wh <> o.wh)",
        lambda ord_, wh, cust, amt: any(
            not3(eq(ln[1], wh)) is True for ln in lines_of(ord_)),
        [("exists", 1, 1, 0)], 2),
    "not-exists-null-key": (
        # the order with a NULL key matches nothing: NOT EXISTS keeps it
        "not exists (select * from r where r.ord = o.ord)",
        lambda ord_, wh, cust, amt: not any(
            eq(v, ord_) is True for v in RETURNS),
        [("exists", 1, 0, 0)], 2),
    "exists-uncorrelated": (
        "exists (select * from r where r.ord > 8)",
        lambda ord_, wh, cust, amt: any(v > 8 for v in RETURNS),
        [("exists", 0, 0, 0)], 2),
    "in": (
        "o.ord in (select ord from r)",
        lambda ord_, wh, cust, amt: in3(ord_, RETURNS),
        [("in", 0, 0, 0)], 1),
    "not-in-null-on-the-right": (
        # ANSI: one NULL in the list and NOT IN is never true
        "o.ord not in (select ord from rn)",
        lambda ord_, wh, cust, amt: not3(in3(ord_, RETURNS_WITH_NULL)),
        [("in", 0, 0, 1)], 2),
    "not-in-null-on-the-left": (
        "o.ord not in (select ord from r)",
        lambda ord_, wh, cust, amt: not3(in3(ord_, RETURNS)),
        [("in", 0, 0, 1)], 1),
    "in-correlated": (
        "o.wh in (select l.wh from l where l.ord = o.ord)",
        lambda ord_, wh, cust, amt: in3(wh, [ln[1] for ln in lines_of(ord_)]),
        [("in", 1, 0, 0)], 3),
    "not-in-correlated-null-in-the-group": (
        # order 3's group holds a NULL warehouse: unknown, not kept
        "o.wh not in (select l.wh from l where l.ord = o.ord)",
        lambda ord_, wh, cust, amt: not3(
            in3(wh, [ln[1] for ln in lines_of(ord_)])),
        [("in", 1, 0, 1)], 4),
    "scalar-correlated-no-match-is-null": (
        # orders 6 and 8 have no line: the scalar is NULL, the row goes
        "o.amt > (select 1.2 * avg(l.amt) from l where l.ord = o.ord)",
        lambda ord_, wh, cust, amt: gt(
            amt, None if avg([ln[2] for ln in lines_of(ord_)]) is None
            else 1.2 * avg([ln[2] for ln in lines_of(ord_)])),
        [("scalar", 1, 0, 0)], 4),
    "scalar-uncorrelated": (
        "o.amt > (select avg(amt) from l)",
        lambda ord_, wh, cust, amt: gt(amt, avg(ALL_LINE_AMTS)),
        [("scalar", 0, 0, 0)], 1),
    "quantified-all": (
        "o.amt >= all (select amt from l where amt < 50)",
        lambda ord_, wh, cust, amt: all(
            amt >= v for v in ALL_LINE_AMTS if v < 50),
        [("quantified", 0, 0, 0)], 2),
    "quantified-any-is-in": (
        "o.ord = any (select ord from r)",
        lambda ord_, wh, cust, amt: in3(ord_, RETURNS),
        [("quantified", 0, 0, 0)], 1),
    "quantified-not-all-is-not-in": (
        "o.ord <> all (select ord from rn)",
        lambda ord_, wh, cust, amt: not3(in3(ord_, RETURNS_WITH_NULL)),
        [("quantified", 0, 0, 1)], 2),
    "the-same-subquery-twice-is-planned-once": (
        "(o.ord in (select ord from r) or o.cust in (select ord from r))",
        lambda ord_, wh, cust, amt: in3(ord_, RETURNS) is True
        or in3(cust, RETURNS) is True,
        [("in", 0, 0, 0), ("in", 0, 0, 0)], 1),
}


def run_both(s, q):
    """(rows, host reads, span records) with tracing on, after checking that
    tracing off gives the same rows with the same count of host reads."""
    def run():
        E.resolve_counts()                # start from a drained thread
        obs_trace.drain_spans()
        before = E.sync_count()
        rows = s.sql(q).collect()
        return rows, E.sync_count() - before, obs_trace.drain_spans()

    rows_on, syncs_on, records = run()
    obs_trace.set_enabled(False)
    try:
        rows_off, syncs_off, nothing = run()
    finally:
        obs_trace.set_enabled(True)
    assert rows_on == rows_off
    assert syncs_on == syncs_off and not nothing
    return rows_on, syncs_on, records


def spans_named(records, name):
    return [r for r in records
            if isinstance(r, obs_trace.SpanRecord) and r.name == name]


@pytest.mark.parametrize("case", sorted(CASES))
def test_each_arm_opens_its_span_answers_right_and_adds_no_read(case):
    predicate, in_python, want_spans, want_reads = CASES[case]
    s = session()
    rows, reads, records = run_both(
        s, f"select ord, amt from o where {predicate} order by amt")
    by_amt = sorted(ORDERS, key=lambda o: o[3])
    assert [r[0] for r in rows] == [
        o[0] for o in by_amt if in_python(*o) is True]
    # the reads of the statement as the parent (no such span) made them
    assert reads == want_reads
    spans = spans_named(records, "op.subquery")
    assert [(r.attrs["fn"], r.attrs["correlated"], r.attrs["residual"],
             r.attrs["negated"]) for r in spans] == want_spans
    opened = {r.sid for r in records if isinstance(r, obs_trace.SpanRecord)}
    for r in spans:
        assert r.sid and r.qid and r.parent in opened
        assert r.attrs["planned"] in (0, 1) and r.attrs["cells"] >= 0
    phase = obs_export.rollup(records)["phases"]["op.subquery"]
    for key in ("cells", "planned", "correlated", "residual", "negated"):
        assert phase[key] == sum(r.attrs[key] for r in spans)
    assert phase["count"] == len(spans) and phase["ms"] > 0
    if case == "the-same-subquery-twice-is-planned-once":
        assert [r.attrs["planned"] for r in spans] == [1, 0]
    else:
        assert all(r.attrs["planned"] == 1 for r in spans)


def test_cells_are_the_key_arrays_at_their_buckets():
    """What each arm states, from the toy tables' shapes: ``o`` and ``l``
    come at one bucket (``plen``), every column nullable but ``r.ord``."""
    s = session()
    o, ln, r = s.catalog["o"], s.catalog["l"], s.catalog["r"]

    def cells_of(predicate):
        _rows, _reads, records = run_both(
            s, f"select count(*) from o where {predicate}")
        return [x.attrs["cells"] for x in spans_named(records,
                                                      "op.subquery")]

    # outer key (data + validity) and the inner DISTINCT's key
    (plain,) = cells_of("exists (select * from l where l.ord = o.ord)")
    assert plain >= 2 * o.plen + 2 * 16
    # the membership's two sides: o.ord with its validity, r.ord without
    assert cells_of("o.ord in (select ord from r)") == [2 * o.plen + r.plen]
    # residual arm: the keys, the two columns the residual names (wh of each
    # side, data and validity: the pairs' table is two deferred groups and
    # ord is read by the equality alone) at the pairs' bucket, and the two
    # pair-index arrays
    (residual,) = cells_of(
        "exists (select * from l where l.ord = o.ord and l.wh <> o.wh)")
    pairs = E.bucket_len(16)
    assert residual == 2 * o.plen + 2 * ln.plen + 4 * pairs + 2 * pairs
    # nothing is read where nothing correlates and the answer is one count
    assert cells_of("exists (select * from r where r.ord > 8)") == [0]
    assert cells_of("o.amt > (select avg(amt) from l)") == [0]


def test_exists_negated_on_the_node_is_stated_and_answers():
    """The parser writes NOT EXISTS as NOT over the node, so ``negated`` is
    0 on every statement's span; the evaluator's own negation (an AST built
    with it) is stated, and keeps the NULL-keyed order."""
    s = session()
    q = parse("select ord, amt from o where exists "
              "(select * from r where r.ord = o.ord) order by amt")
    exists = q.body.where
    assert isinstance(exists, A.Exists) and exists.negated is False
    exists.negated = True
    obs_trace.drain_spans()
    table = E.resolve_table(P.Planner(s.catalog).query(q))
    assert table.to_arrow().column("ord").to_pylist() == [
        o[0] for o in sorted(ORDERS, key=lambda o: o[3])
        if o[0] not in RETURNS]
    (span,) = spans_named(obs_trace.drain_spans(), "op.subquery")
    assert (span.attrs["fn"], span.attrs["negated"]) == ("exists", 1)


@pytest.mark.parametrize("text, message", [
    ("select ord from o where o.amt > (select max(l.amt) from l "
     "where l.ord = o.ord group by l.wh)", "more than one row per outer row"),
    ("select ord from o where o.amt > (select amt from l)",
     "more than one row"),
])
def test_a_scalar_subquery_of_more_than_one_row_raises_under_its_span(
        text, message):
    s = session()
    obs_trace.drain_spans()
    with pytest.raises(P.ExecError, match=message):
        s.sql(text).collect()
    (span,) = spans_named(obs_trace.drain_spans(), "op.subquery")
    assert span.attrs["fn"] == "scalar"


# -- DISTINCT aggregates -----------------------------------------------------------

def by_warehouse(fold):
    groups = {}
    for _ord, wh, cust, _amt in ORDERS:
        groups.setdefault(wh, set())
        if cust is not None:
            groups[wh].add(cust)
    return sorted(((wh, fold(custs)) for wh, custs in groups.items()),
                  key=lambda row: (row[0] is None, row[0]))


@pytest.mark.parametrize("fn, call, fold", [
    # count(distinct) skips the NULL customer: warehouse 12 counts one
    ("count_distinct", "count(distinct cust)", len),
    ("sum_distinct", "sum(distinct cust)", sum),
    ("avg_distinct", "avg(distinct cust)", lambda c: sum(c) / len(c)),
])
def test_distinct_aggregates_run_under_op_agg_and_skip_nulls(fn, call, fold):
    s = session()
    o = s.catalog["o"]
    rows, reads, records = run_both(
        s, f"select wh, {call} from o group by wh order by wh nulls last")
    assert [(wh, float(v) if fn == "avg_distinct" else v)
            for wh, v in rows] == by_warehouse(fold)
    assert reads == 2                     # as the parent makes them
    (span,) = [r for r in spans_named(records, "op.agg")
               if r.attrs["fn"] == fn]
    # the two arrays regrouped (group ids, argument) at the base width
    assert span.attrs["cells"] == 2 * o.plen
    inside = [r for r in spans_named(records, "op.group_ids")
              if r.parent == span.sid]
    assert len(inside) == 1               # the regrouping is in the span


def test_count_distinct_of_an_empty_input_opens_no_span_and_counts_zero():
    s = session()
    rows, _reads, records = run_both(
        s, "select count(distinct cust) from o where ord > 100")
    assert rows == [(0,)]
    assert not [r for r in spans_named(records, "op.agg")
                if r.attrs.get("fn") == "count_distinct"]


def test_under_a_replay_retrace_the_distinct_aggregates_span_is_a_scope():
    """Where the planner's code is re-traced into a replayed or chunk
    program the span is no span (``obs.op``) and the operations between the
    primitives carry ``nds.agg`` / ``nds.agg.<fn>`` instead."""
    def body(x):
        with E.replaying([]), P.Planner._distinct_agg_span("count_distinct",
                                                           x):
            return x + 1
    obs_trace.drain_spans()
    text = jax.jit(body).lower(jnp.zeros(16, jnp.int64)).as_text(
        debug_info=True)
    assert "nds.agg/nds.agg.count_distinct" in text
    assert not spans_named(obs_trace.drain_spans(), "op.agg")
