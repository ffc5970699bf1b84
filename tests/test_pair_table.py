# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""A join's pair table stands on deferred groups of both sides (PR 36).

Past ``NDS_TPU_PAIR_BUDGET`` candidates ``join_tables`` makes its pairs span
by span (``_chunked_inner_join``). A span's pair table is ``ops.pair_table``:
one deferred group a side and nothing gathered, so a residual reads the
columns it names, each alone at the candidates' bucket; the survivors are
kept as their two index arrays, and the result is again a pair table, a
column of it gathered when something first reads it. ``_exists_mask``'s
residual arm builds its pairs' table with the same constructor.

Each ops-level case holds the chunked arm to the monolithic arm, to a filter
after the join and to a plain-Python reading, row for row and in order; pins
the host reads to what the parent (``6d6715b``) made of the same case; and
counts what is gathered from host-known shapes. The planner-level cases run
query95's and query94's shapes on toy tables.
"""

import os

import numpy as np
import pyarrow as pa
import pytest

import jax.numpy as jnp

from nds_tpu.engine import ops as E
from nds_tpu.engine.column import from_arrow
from nds_tpu.engine.session import Session
from nds_tpu.obs import export as obs_export
from nds_tpu.obs import trace as obs_trace

BUDGET = 64


# -- the tables -------------------------------------------------------------------

def _ints(values):
    return pa.array([None if v is None else int(v) for v in values],
                    pa.int64())


def _sides(keys="int", n_l=60, n_r=50, seed=11, heavy=0):
    """Left ``(k[, ks], a, ls)`` and right ``(j[, js], b, rs)``: keys drawn
    from 12 values so that candidates pass the budget several times over;
    ``a`` / ``b`` payloads with NULLs; ``ls`` / ``rs`` strings of two
    dictionaries. ``heavy``: that many more right rows of ONE key, so its
    left rows each have more candidates than a span may hold."""
    rng = np.random.default_rng(seed)
    lk = list(rng.integers(0, 12, n_l))
    rk = list(rng.integers(0, 12, n_r)) + [5] * heavy
    n_r += heavy
    if keys == "nullable":
        lk = [None if i % 7 == 3 else v for i, v in enumerate(lk)]
        rk = [None if i % 5 == 2 else v for i, v in enumerate(rk)]
    left = {"k": _ints(lk),
            "a": _ints(None if i % 6 == 1 else v for i, v in
                       enumerate(rng.integers(0, 30, n_l))),
            "ls": pa.array([f"l{v % 4}" for v in range(n_l)])}
    right = {"j": _ints(rk),
             "b": _ints(None if i % 4 == 2 else v for i, v in
                        enumerate(rng.integers(0, 30, n_r))),
             "rs": pa.array([None if v % 9 == 0 else f"r{v % 5}"
                             for v in range(n_r)])}
    l_on, r_on = ["k"], ["j"]
    if keys == "int+str":
        left["ks"] = pa.array([f"s{v % 2}" for v in range(n_l)])
        right["js"] = pa.array([f"s{v % 3}" for v in range(n_r)])
        l_on, r_on = ["k", "ks"], ["j", "js"]
    return (from_arrow(pa.table(left)), from_arrow(pa.table(right)),
            l_on, r_on)


def _with_snowflake(left):
    """``left`` with a dimension joined on as a deferred group of its own
    (what a PK-gather join leaves past ``NDS_TPU_LAZY_SHRINK_ROWS``)."""
    dim = from_arrow(pa.table({
        "dk": _ints(range(12)),
        "dv": _ints(None if v == 4 else 100 + v for v in range(12))}))
    k = left["k"]
    return left.with_deferred(dim, jnp.clip(k.data, 0, 11))


# residuals, as a mask over the pair table and as SQL's reading of one pair
# (None = unknown, not kept)

def _lt(x, y):
    return lambda t: (t[x].data < t[y].data) & t[x].valid_mask() \
        & t[y].valid_mask()


def _ne(x, y):
    return lambda t: (t[x].data != t[y].data) & t[x].valid_mask() \
        & t[y].valid_mask()


def _py(op, x, y):
    return lambda row: row[x] is not None and row[y] is not None \
        and op(row[x], row[y])


# case -> (sides' keyword arguments, residual mask, the same in Python, the
# columns the residual reads, the left side's own deferred group)
CASES = {
    "int": (dict(), None, None, (), False),
    "nullable": (dict(keys="nullable"), None, None, (), False),
    "int+str": (dict(keys="int+str"), None, None, (), False),
    "int-residual": (dict(), _lt("a", "b"),
                     _py(lambda x, y: x < y, "a", "b"), ("a", "b"), False),
    "nullable-residual": (dict(keys="nullable"), _lt("a", "b"),
                          _py(lambda x, y: x < y, "a", "b"), ("a", "b"),
                          False),
    "int+str-residual": (dict(keys="int+str"), _lt("a", "b"),
                         _py(lambda x, y: x < y, "a", "b"), ("a", "b"),
                         False),
    # <> over a NULL is unknown: such a pair is not kept
    "residual-over-a-null": (dict(), _ne("a", "b"),
                             _py(lambda x, y: x != y, "a", "b"),
                             ("a", "b"), False),
    "empty-result": (dict(), lambda t: t["a"].data < -1,
                     lambda row: False, ("a",), False),
    # a = 16 holds on ONE left row (row 23 of 40, five candidates): the
    # spans before and after its own keep none
    "every-span-empty-but-one": (
        dict(n_l=40, seed=5),
        lambda t: (t["a"].data == 16) & t["a"].valid_mask(),
        lambda row: row["a"] == 16, ("a",), False),
    "oversized-row-has-its-own-span": (dict(heavy=70), None, None, (),
                                       False),
    "oversized-row-residual": (dict(heavy=70), _lt("a", "b"),
                               _py(lambda x, y: x < y, "a", "b"),
                               ("a", "b"), False),
    # the residual reads a column of the left side's own deferred group
    "left-side-carries-a-deferred-group": (
        dict(), _lt("dv", "b"), None, ("dv", "b"), True),
    "left-side-carries-a-deferred-group-no-residual": (
        dict(), None, None, (), True),
}

# host reads of E.join_tables on the chunked arm, measured on the parent
# (6d6715b, tests/test_pair_table.py::reads_on(case) with PYTHONPATH there):
# the probe's total, the spans' table, one count a span with candidates
PARENT_READS = {
    "int": 6, "nullable": 5, "int+str": 4, "int-residual": 6,
    "nullable-residual": 5, "int+str-residual": 4,
    "residual-over-a-null": 6, "empty-result": 6,
    "every-span-empty-but-one": 5, "oversized-row-has-its-own-span": 13,
    "oversized-row-residual": 13,
    "left-side-carries-a-deferred-group": 6,
    "left-side-carries-a-deferred-group-no-residual": 6,
}


def _case(case):
    kw, residual, in_python, reads_cols, snowflake = CASES[case]
    left, right, l_on, r_on = _sides(**kw)
    if snowflake:
        left = _with_snowflake(left)
        if in_python is None and residual is not None:
            in_python = _py(lambda x, y: x < y, "dv", "b")
    return left, right, l_on, r_on, residual, in_python, reads_cols


def _counted(fn):
    """``fn()``'s value, its counted host reads and its span records."""
    E.resolve_counts()                    # start from a drained thread
    obs_trace.drain_spans()
    before = E.sync_count()
    out = fn()
    return out, E.sync_count() - before, [
        r for r in obs_trace.drain_spans()
        if isinstance(r, obs_trace.SpanRecord)]


def reads_on(case):
    """Host reads of the chunked arm on ``case`` (also run on the parent's
    tree to pin ``PARENT_READS``)."""
    os.environ["NDS_TPU_PAIR_BUDGET"] = str(BUDGET)
    try:
        left, right, l_on, r_on, residual, _p, _c = _case(case)
        return _counted(lambda: E.join_tables(
            left, right, l_on, r_on, residual_fn=residual))[1]
    finally:
        del os.environ["NDS_TPU_PAIR_BUDGET"]


def _rows(table):
    arrow = E.resolve_table(table).to_arrow()
    return list(zip(*[arrow.column(n).to_pylist()
                      for n in arrow.column_names])), arrow.column_names


def _python_join(left, right, l_on, r_on, in_python):
    """The inner join read in plain Python, in the engine's order: left
    rows in theirs, a left row's matches in the right side's; a NULL key
    equals nothing. Also the pairs' left row numbers, for the spans."""
    la, ra = left.to_arrow(), right.to_arrow()
    names = la.column_names + ra.column_names
    lrows = list(zip(*[la.column(n).to_pylist() for n in la.column_names]))
    rrows = list(zip(*[ra.column(n).to_pylist() for n in ra.column_names]))
    lk = [la.column_names.index(n) for n in l_on]
    rk = [ra.column_names.index(n) for n in r_on]
    out, owners, candidates = [], [], [0] * len(lrows)
    for i, lr in enumerate(lrows):
        for rr in rrows:
            if all(lr[a] is not None and lr[a] == rr[b]
                   for a, b in zip(lk, rk)):
                candidates[i] += 1
                row = dict(zip(names, lr + rr))
                if in_python is None or in_python(row):
                    out.append(lr + rr)
                    owners.append(i)
    return out, names, owners, candidates


def _col_arrays(table, names):
    """Arrays (data and validity) of the columns ``names`` of a table."""
    return sum(1 + (table.columns[n].valid is not None) for n in names
               if n in table)


@pytest.mark.parametrize("case", sorted(CASES))
def test_chunked_arm_rows_reads_and_cells(case, monkeypatch):
    left, right, l_on, r_on, residual, in_python, reads_cols = _case(case)
    expect, names, owners, candidates = _python_join(
        left.select(left.column_names).materialize(), right, l_on, r_on,
        in_python)

    # the monolithic arm, the residual inside and as a filter afterwards
    mono, mono_names = _rows(E.join_tables(left, right, l_on, r_on,
                                           residual_fn=residual))
    assert mono_names == names and mono == expect
    if residual is not None:
        whole = E.join_tables(left, right, l_on, r_on)
        mask = residual(whole) & E.live_mask(whole.plen, whole.nrows)
        assert _rows(E.compact_table(whole, mask))[0] == expect

    monkeypatch.setenv("NDS_TPU_PAIR_BUDGET", str(BUDGET))
    assert sum(candidates) > BUDGET
    out, reads, records = _counted(lambda: E.join_tables(
        left, right, l_on, r_on, residual_fn=residual))
    assert reads == PARENT_READS[case]

    # the spans as the arm cuts them, and their survivors, from the Python
    # reading: left rows [s, e) whose candidates' sum stays in the budget
    spans = [(s, e) for s, e in E._chunk_spans(np.asarray(candidates),
                                               BUDGET)
             if sum(candidates[s:e])]
    cand = [E.bucket_len(sum(candidates[s:e])) for s, e in spans]
    live = [sum(s <= i < e for i in owners) for s, e in spans]
    kept = [E.bucket_len(n) for n in live if n]
    if case.startswith("oversized"):
        assert max(candidates) > BUDGET and any(e - s == 1
                                                for s, e in spans)
    if case == "every-span-empty-but-one":
        assert len(spans) > 2 and sum(1 for n in live if n) == 1

    (join,) = [r for r in records if r.name == "op.join"]
    key_cells = E._key_cells([left[n] for n in l_on]) \
        + E._key_cells([right[n] for n in r_on])
    assert join.attrs["spans"] == len(spans)
    assert join.attrs["cells"] == key_cells + 2 * sum(cand) + 2 * sum(kept)
    assert obs_export.rollup(records)["phases"]["op.join"]["spans"] \
        == len(spans)

    # op.gather: per span the residual's arrays at the candidates' bucket
    # and the two index arrays at the survivors'; the squeeze of the
    # concatenated index arrays; nothing else
    total = sum(live)
    squeeze = 0
    if len(kept) > 1 and not (total == sum(kept)
                              and total == E.bucket_len(total)):
        squeeze = 2 * E.bucket_len(total)
    # a copy of the two sides to read validity from
    res_arrays = sum(_col_arrays(t, reads_cols) for t in _case(case)[:2])
    # a column of the left side's own group comes through one more index
    own_group = int("dv" in reads_cols)
    gathers = [r for r in records if r.name == "op.gather"]
    assert sum(r.attrs["cells"] for r in gathers) \
        == (res_arrays + own_group) * sum(cand) + 2 * sum(kept) + squeeze
    # every array the residual read came through a pair table's index
    assert sum(r.attrs.get("deferredArrays", 0) for r in gathers) \
        == res_arrays * len(spans)
    concat = [r for r in records if r.name == "op.concat"]
    assert [r.attrs["cells"] for r in concat] == (
        [2 * E.bucket_len(total)] if len(kept) > 1 else [])

    # the result: two groups, nothing gathered, the schema whole
    gathered, groups = out.split()
    assert not gathered and len(groups) == 2
    assert out.column_names == names
    assert [g.source for g, _ in groups] == [left, right]
    assert all(g.pair and g.match is None for g, _ in groups)
    assert E.count_int(out.nrows) == total
    assert out.plen == E.bucket_len(total)

    # a column read twice is gathered once, alone, at the result's bucket
    name = "b"
    first, _r, recs = _counted(lambda: out[name])
    (g,) = [r for r in recs if r.name == "op.gather"]
    assert g.attrs["cells"] == out.plen * _col_arrays(right, [name])
    assert g.attrs["deferredArrays"] == _col_arrays(right, [name])
    again, _r, recs = _counted(lambda: out[name])
    assert again is first and not recs
    assert set(out.split()[0]) == {name}

    got, got_names = _rows(out)
    assert got_names == names and got == expect
    if case == "empty-result":
        assert not expect
        assert [out.kind(n) for n in names] == [
            (left if n in left else right).kind(n) for n in names]


def test_pair_table_gathers_nothing_and_right_wins_a_shared_name():
    """The constructor alone: no span, no gather; on a name both sides
    hold, the right side's column is the pair table's."""
    left = from_arrow(pa.table({"k": _ints([1, 2, 3]),
                                "v": _ints([10, 20, 30])}))
    right = from_arrow(pa.table({"j": _ints([7, 8]),
                                 "v": _ints([70, None])}))
    l_idx = jnp.array([0, 2, 2, 1], dtype=jnp.int64)
    r_idx = jnp.array([1, 0, 1, 0], dtype=jnp.int64)
    pairs, reads, records = _counted(
        lambda: E.pair_table(left, l_idx, right, r_idx, 4))
    assert reads == 0 and not records
    assert pairs.column_names == ["k", "v", "j"]
    assert pairs.plen == 4 and pairs.nrows == 4
    assert not pairs.split()[0] and len(pairs.split()[1]) == 2
    assert pairs.kind("v") == right.kind("v")
    assert pairs.to_arrow().to_pylist() == [
        {"k": 1, "v": None, "j": 8}, {"k": 3, "v": 70, "j": 7},
        {"k": 3, "v": None, "j": 8}, {"k": 2, "v": 70, "j": 7}]


# -- the planner: query95's and query94's shapes ------------------------------------

N_ORDERS = 24


def _toy_session():
    """``o(ord, wh, amt)``: one row an order; ``l(ord, wh, amt)``: one to
    five lines an order, from one to three warehouses, some NULL; ``r(ord)``:
    the returned orders."""
    rng = np.random.default_rng(3)
    orders = [(i, int(rng.integers(1, 4)), int(rng.integers(1, 100)))
              for i in range(N_ORDERS)]
    lines = []
    for i in range(N_ORDERS):
        for _ in range(int(rng.integers(1, 6))):
            wh = None if rng.integers(0, 9) == 0 else int(
                rng.integers(1, 1 + (1 if i % 3 == 0 else 3)))
            lines.append((i, wh, int(rng.integers(1, 50))))
    returns = [i for i in range(N_ORDERS) if i % 2 == 0]
    s = Session()
    for name, rows, cols in (("o", orders, ("ord", "wh", "amt")),
                             ("l", lines, ("ord", "wh", "amt"))):
        s.create_temp_view(name, pa.table({
            c: _ints(v) for c, v in zip(cols, zip(*rows))}))
    s.create_temp_view("r", pa.table({"ord": _ints(returns)}))
    return s, orders, lines, returns


def _statement(s, text):
    """Rows, host reads and the rollup's phases of one statement."""
    def run():
        E.resolve_counts()
        obs_trace.drain_spans()
        before = E.sync_count()
        rows = s.sql(text).collect()
        return rows, E.sync_count() - before, obs_trace.drain_spans()
    rows, reads, records = run()
    return rows, reads, records, obs_export.rollup(records)["phases"]


QUERY95 = """
with ww as (select l1.ord ord, l1.wh wh1, l2.wh wh2 from l l1, l l2
            where l1.ord = l2.ord and l1.wh <> l2.wh)
select o.ord, o.amt from o
where o.ord in (select ord from ww)
  and o.ord in (select r.ord from r, ww where r.ord = ww.ord)
order by o.ord
"""

QUERY94 = """
select o.ord, o.amt from o
where exists (select * from l where l.ord = o.ord and l.wh <> o.wh)
  and not exists (select * from r where r.ord = o.ord)
order by o.ord
"""

# the parent's figures (6d6715b, the same statements under the same budget):
# host reads, the cells of op.gather and op.concat, and of the residual arm's
# op.subquery
PARENT_QUERY95 = {"reads": 26, "gather_cells": 4640, "concat_cells": 576}
PARENT_QUERY94 = {"reads": 3, "gather_cells": 1072, "residual_cells": 1312}


def test_query95_shape_a_with_self_join_read_by_two_in(monkeypatch):
    monkeypatch.setenv("NDS_TPU_PAIR_BUDGET", "16")
    s, orders, lines, returns = _toy_session()
    rows, reads, records, phases = _statement(s, QUERY95)
    two_warehouses = {a[0] for a in lines for b in lines
                      if a[0] == b[0] and a[1] is not None
                      and b[1] is not None and a[1] != b[1]}
    assert rows == [(o, amt) for o, _wh, amt in orders
                    if o in two_warehouses and o in returns]
    assert rows
    assert reads == PARENT_QUERY95["reads"]
    # both joins of the statement take the chunked arm and say so
    joins = [r for r in records if isinstance(r, obs_trace.SpanRecord)
             and r.name == "op.join"]
    assert len(joins) == 2 and all(r.attrs["spans"] > 1 and
                                   r.attrs["cells"] > 0 for r in joins)
    assert phases["op.join"]["spans"] == sum(r.attrs["spans"]
                                             for r in joins)
    assert phases["op.gather"]["cells"] < PARENT_QUERY95["gather_cells"]
    assert phases["op.gather"]["deferredArrays"] > 0
    # the index arrays, not the WITH's columns, are what is appended
    assert phases["op.concat"]["cells"] < PARENT_QUERY95["concat_cells"]


def test_query94_shape_exists_with_a_residual_over_shared_names(monkeypatch):
    monkeypatch.setenv("NDS_TPU_PAIR_BUDGET", "16")
    s, orders, lines, returns = _toy_session()
    rows, reads, records, phases = _statement(s, QUERY94)
    assert rows == [(o, amt) for o, wh, amt in orders
                    if any(ln[0] == o and ln[1] is not None and ln[1] != wh
                           for ln in lines) and o not in returns]
    assert rows
    assert reads == PARENT_QUERY94["reads"]
    assert phases["op.gather"]["cells"] < PARENT_QUERY94["gather_cells"]
    spans = [r for r in records if isinstance(r, obs_trace.SpanRecord)
             and r.name == "op.subquery"]
    (residual,) = [r for r in spans if r.attrs["residual"]]
    # the keys (o.ord and l.ord, neither nullable), the residual's two
    # columns at the pairs' bucket (l.wh with its validity, o.wh without:
    # the other four columns of the two sides are never gathered), and the
    # two index arrays
    o, ln = s.catalog["o"], s.catalog["l"]
    pairs = E.bucket_len(len(lines))
    assert residual.attrs["cells"] == o.plen + ln.plen + 3 * pairs \
        + 2 * pairs < PARENT_QUERY94["residual_cells"]
    inside = [r for r in records if isinstance(r, obs_trace.SpanRecord)
              and r.name == "op.gather" and r.attrs.get("deferredArrays")]
    assert sorted(r.attrs["cells"] for r in inside) == [pairs, 2 * pairs]
