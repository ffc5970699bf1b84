# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Planner-feature tests for the TPC-DS corpus shapes that drove them:
expression equi-join keys, OR-common-conjunct hoisting (q13/q41/q48/q85),
correlated EXISTS with residual predicates (q16/q94), subquery-bearing
filter deferral (q32), windows over aggregates incl. empty inputs
(q49/q53/q63), ORDER BY on a select-list aggregate (q16)."""

import os
import sys

import pyarrow as pa
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from nds_tpu.engine.session import Session


def _session():
    s = Session()
    s.create_temp_view("sales", pa.table({
        "s_order": pa.array([1, 1, 2, 3, 4], type=pa.int64()),
        "s_wh": pa.array([10, 11, 10, 10, 12], type=pa.int64()),
        "s_item": pa.array([100, 101, 100, 102, 103], type=pa.int64()),
        "s_amt": pa.array([5.0, 6.0, 7.0, 8.0, 9.0], type=pa.float64()),
        "s_date": pa.array(["2000-01-01", "2000-01-02", "2000-01-03",
                            "2000-01-04", "2000-01-05"], type=pa.string()),
    }))
    s.create_temp_view("dim", pa.table({
        "d_sk": pa.array([100, 101, 102, 103], type=pa.int64()),
        "d_cat": pa.array(["a", "a", "b", "b"], type=pa.string()),
        "d_day": pa.array(["2000-01-01", "2000-01-02", "2000-01-03",
                           "2000-01-04"], type=pa.string()),
    }))
    return s


class TestExpressionEquiKeys:
    def test_cast_key_join(self):
        s = _session()
        # join on an expression of the left side = plain right column
        out = s.sql("""
            select count(*) from sales left outer join dim
            on (cast(s_item as bigint) = d_sk)""").collect()
        assert out[0][0] == 5

    def test_residual_in_outer_join(self):
        s = _session()
        # residual conjunct restricts which right rows may match; unmatched
        # left rows survive with nulls
        rows = s.sql("""
            select s_order, d_cat from sales left outer join dim
            on (s_item = d_sk and d_cat = 'a')
            order by s_order, d_cat""").collect()
        cats = [r[1] for r in rows]
        assert len(rows) == 5
        assert cats.count("a") == 3          # items 100,101,100
        assert cats.count(None) == 2         # items 102,103 blocked by residual


class TestOrHoisting:
    def test_join_key_inside_or(self):
        s = _session()
        # (k and X) or (k and Y) must not fall back to a cartesian; result
        # equals the hoisted form k and (X or Y)
        a = s.sql("""
            select count(*) from sales, dim
            where (s_item = d_sk and d_cat = 'a')
               or (s_item = d_sk and d_cat = 'b')""").collect()
        b = s.sql("""
            select count(*) from sales, dim
            where s_item = d_sk and (d_cat = 'a' or d_cat = 'b')""").collect()
        assert a == b
        assert a[0][0] == 5

    def test_degenerate_or(self):
        s = _session()
        # one disjunct exactly the common set -> OR collapses to it
        a = s.sql("""
            select count(*) from sales, dim
            where (s_item = d_sk and d_cat = 'a') or (s_item = d_sk)
        """).collect()
        assert a[0][0] == 5


class TestCorrelatedExistsResidual:
    def test_not_equal_residual(self):
        s = _session()
        # orders shipped from more than one warehouse (the q16 shape)
        rows = s.sql("""
            select distinct s_order from sales s1
            where exists (select * from sales s2
                          where s1.s_order = s2.s_order
                            and s1.s_wh <> s2.s_wh)
            order by s_order""").collect()
        assert [r[0] for r in rows] == [1]

    def test_not_exists_residual(self):
        s = _session()
        rows = s.sql("""
            select distinct s_order from sales s1
            where not exists (select * from sales s2
                              where s1.s_order = s2.s_order
                                and s1.s_wh <> s2.s_wh)
            order by s_order""").collect()
        assert [r[0] for r in rows] == [2, 3, 4]


class TestSubqueryFilterDeferral:
    def test_correlated_scalar_in_multijoin_where(self):
        s = _session()
        # q32 shape: the scalar subquery's correlation column (d_sk) belongs
        # to another joined table, so the predicate must not be pushed down
        # to the sales part alone
        rows = s.sql("""
            select count(*) from sales, dim
            where s_item = d_sk
              and s_amt > (select avg(s_amt) from sales where s_item = d_sk)
        """).collect()
        # per-item averages: 100 -> 6.0, 101 -> 6.0, 102 -> 8.0, 103 -> 9.0
        # rows above their item average: (2, 7.0 > 6.0) only
        assert rows[0][0] == 1


class TestWindowOverAggregate:
    def test_window_on_aggregate_result(self):
        s = _session()
        rows = s.sql("""
            select * from (
              select d_cat, sum(s_amt) sum_amt,
                     avg(sum(s_amt)) over (partition by d_cat) avg_cat
              from sales, dim where s_item = d_sk
              group by d_cat, s_order) t
            order by d_cat, sum_amt""").collect()
        assert len(rows) == 4
        # category 'a' groups: (1 -> 11.0), (2 -> 7.0) => avg 9.0
        a_rows = [r for r in rows if r[0] == "a"]
        assert all(abs(r[2] - 9.0) < 1e-9 for r in a_rows)

    def test_window_on_empty_aggregate(self):
        s = _session()
        rows = s.sql("""
            select * from (
              select d_cat, sum(s_amt) sum_amt,
                     rank() over (partition by d_cat
                                  order by sum(s_amt)) rk
              from sales, dim where s_item = d_sk and d_cat = 'zzz'
              group by d_cat, s_order) t""").collect()
        assert rows == []


class TestOrderByAggregateItem:
    def test_order_by_count_distinct(self):
        s = _session()
        rows = s.sql("""
            select count(distinct s_wh) from sales
            order by count(distinct s_wh)""").collect()
        assert rows == [(3,)]


def test_star_over_cte_with_colliding_names():
    """Projection pruning must keep the collision-suffixed duplicate
    column (``_project`` renames the second ``x`` to ``x_3``-style): a CTE
    projecting the same bare name from two tables, then ``SELECT *`` over
    it, silently lost the renamed column when the pruning side guessed
    output names without modeling the rename."""
    s = _session()
    rows = s.sql("""
        with j as (
            select sales.s_item, dim.d_sk, sales.s_amt amt, dim.d_cat amt
            from sales, dim where s_item = d_sk
        )
        select * from j order by s_item, d_sk""").collect()
    # every projected column survives: s_item, d_sk, amt, amt_3 (renamed)
    assert all(len(r) == 4 for r in rows)
    assert rows[0] == (100, 100, 5.0, "a")


def test_rollup_hierarchy_matches_generic_path(monkeypatch):
    """The hierarchical rollup re-aggregation must reproduce the per-set
    generic path exactly: nulls in keys and args, empty groups, string
    keys, avg/sum/min/max/count, grouping(), HAVING."""
    import numpy as np
    import pyarrow as pa

    from nds_tpu.engine.session import Session
    from nds_tpu.sql.planner import Planner

    rng = np.random.default_rng(3)
    n = 2000
    t = pa.table({
        "a": pa.array([None if x % 11 == 0 else f"a{x % 5}"
                       for x in rng.integers(0, 1000, n)]),
        "b": pa.array([None if x % 7 == 0 else int(x % 4)
                       for x in rng.integers(0, 1000, n)], pa.int64()),
        "c": pa.array(rng.integers(0, 3, n), pa.int64()),
        "v": pa.array([None if x % 5 == 0 else int(x)
                       for x in rng.integers(1, 500, n)], pa.int64()),
        "w": pa.array((rng.random(n) * 100).round(2)),
    })
    sql = """
        select a, b, c, sum(v) s, count(v) cv, count(*) cs, avg(w) aw,
               min(v) mn, max(w) mx, grouping(b) gb
        from t group by rollup(a, b, c)
        having count(*) > 1
        order by a, b, c, gb
    """
    fast = Session()
    fast.create_temp_view("t", t)
    got_fast = fast.sql(sql).collect()

    monkeypatch.setattr(Planner, "_rollup_fast",
                        lambda self, *a, **k: None)
    generic = Session()
    generic.create_temp_view("t", t)
    got_generic = generic.sql(sql).collect()

    def norm(rows):
        return sorted(
            (tuple((x is None,
                    round(x, 6) if isinstance(x, float) else x)
                   for x in r) for r in rows),
            key=repr)
    assert norm(got_fast) == norm(got_generic)
    assert len(got_fast) > 10


# ---------------------------------------------------------------------------
# projection pushdown: a star under EXISTS names no columns
# ---------------------------------------------------------------------------

def _schema_columns():
    from nds_tpu.schema import get_schemas
    return {t: [f.name.lower() for f in fields]
            for t, fields in get_schemas(True).items()}


def _planner_names(stmt):
    """The planner's walk over a catalog that holds the TPC-DS column
    names and no data (the walk reads ``column_names`` alone)."""
    from types import SimpleNamespace
    from nds_tpu.sql.planner import Planner
    catalog = {t: SimpleNamespace(column_names=cols)
               for t, cols in _schema_columns().items()}
    return Planner(catalog)._collect_needed_names(stmt)


def _mirror_names(stmt):
    from nds_tpu.analysis.mem_audit import statement_needed_names
    return statement_needed_names(stmt)


_SEMI = ("c.c_customer_sk = ss_customer_sk and ss_sold_date_sk = d_date_sk "
         "and d_year = 2002 and d_moy between 1 and 4")
_SEMI_NAMES = {"c_customer_sk", "ss_customer_sk", "ss_sold_date_sk",
               "d_date_sk", "d_year", "d_moy"}

# (id, statement, bare names it must name, tables named whole)
_EXISTS_STAR_CASES = [
    ("exists_star",
     f"select c_customer_sk from customer c where exists "
     f"(select * from store_sales, date_dim where {_SEMI})",
     _SEMI_NAMES, []),
    ("not_exists_star",
     f"select c_customer_sk from customer c where not exists "
     f"(select * from store_sales, date_dim where {_SEMI})",
     _SEMI_NAMES, []),
    ("qualified_star",
     f"select c_customer_sk from customer c where exists "
     f"(select ss.* from store_sales ss, date_dim where {_SEMI})",
     _SEMI_NAMES, []),
    ("explicit_item_is_named",
     f"select c_customer_sk from customer c where exists "
     f"(select ss_item_sk + 1, d.* from store_sales, date_dim d "
     f"where {_SEMI})",
     _SEMI_NAMES | {"ss_item_sk"}, []),
    ("from_subquery_star_keeps_its_scope",
     "select c_customer_sk from customer c where exists "
     "(select * from (select * from store_sales) x "
     "where x.ss_customer_sk = c.c_customer_sk)",
     {"c_customer_sk"}, ["store_sales"]),
    ("top_level_star_beside_exists",
     "select * from customer where exists "
     "(select * from store_sales where ss_customer_sk = c_customer_sk)",
     {"ss_customer_sk"}, ["customer"]),
    ("query16_residual",
     "select count(distinct cs_order_number) from catalog_sales cs1 "
     "where exists (select * from catalog_sales cs2 "
     "where cs1.cs_order_number = cs2.cs_order_number "
     "and cs1.cs_warehouse_sk <> cs2.cs_warehouse_sk)",
     {"cs_order_number", "cs_warehouse_sk"}, []),
    ("set_operation_under_exists_keeps_its_stars",
     "select c_customer_sk from customer where exists "
     "(select * from store_sales union all select * from store_sales)",
     {"c_customer_sk"}, ["store_sales"]),
]


@pytest.mark.parametrize("walker", [_planner_names, _mirror_names],
                         ids=["planner", "mirror"])
@pytest.mark.parametrize("sql,named,whole",
                         [c[1:] for c in _EXISTS_STAR_CASES],
                         ids=[c[0] for c in _EXISTS_STAR_CASES])
def test_star_under_exists_names_no_columns(walker, sql, named, whole):
    """The select list of an EXISTS is unobservable: a star directly
    under it adds nothing to the statement's needed names, in the
    planner's walk and in the auditors' mirror of it alike. Everything
    else the subquery names (its predicates, an explicit item, a star in
    a FROM-subquery's own scope, a set operation's operands) still is."""
    from nds_tpu.sql.parser import parse
    cols = _schema_columns()
    want = set(named).union(*(cols[t] for t in whole))
    assert walker(parse(sql)) == want


def test_needed_names_mirror_agrees_with_planner_on_corpus():
    """``statement_needed_names`` is a hand-kept mirror of
    ``Planner._collect_needed_names``: over every one of the 103 generated
    statements the two keep the SAME columns of every catalog table (and
    neither disables pruning). The planner's set may hold more: the
    output aliases of a CTE under a star (``tpcds_cmax``, ``psum``), which
    prune the CTE's own table and are no catalog column, so no scan the
    auditors size can see them."""
    import numpy as np
    from nds_tpu.analysis.mem_audit import _AUDIT_SEED
    from nds_tpu.queries import (TEMPLATE_DIR, instantiate_template,
                                 list_templates, load_template)
    from nds_tpu.sql.parser import parse
    catalog_names = set().union(*_schema_columns().values())
    n = 0
    for name in list_templates(TEMPLATE_DIR):
        sql = instantiate_template(load_template(name, TEMPLATE_DIR),
                                   np.random.default_rng(_AUDIT_SEED))
        for text in (s for s in sql.split(";") if s.strip()):
            stmt = parse(text)
            got, mirror = _planner_names(stmt), _mirror_names(stmt)
            n += 1
            assert got is not None and mirror is not None, name
            assert mirror <= got, (name, sorted(mirror - got))
            assert not (got - mirror) & catalog_names, \
                (name, sorted((got - mirror) & catalog_names))
    assert n == 103


# (id, statement, rows, columns the catalog scans kept: sales has 5, dim 3)
_EXISTS_ARM_CASES = [
    ("equality_arm",
     "select s_order from sales where exists "
     "(select * from dim where d_sk = s_item and d_cat = 'a') "
     "order by s_order", [(1,), (1,), (2,)], 2 + 2),
    ("equality_arm_negated",
     "select s_order from sales where not exists "
     "(select * from dim where d_sk = s_item and d_cat = 'a') "
     "order by s_order", [(3,), (4,)], 2 + 2),
    ("residual_arm",
     "select distinct s_order from sales s1 where exists "
     "(select * from sales s2 where s1.s_order = s2.s_order "
     "and s1.s_wh <> s2.s_wh) order by s_order", [(1,)], 2 + 2),
    ("uncorrelated_arm",
     "select count(*) from sales where s_amt > 5 and exists "
     "(select * from dim where d_cat = 'b')", [(4,)], 1 + 1),
    ("uncorrelated_arm_empty",
     "select count(*) from sales where s_amt > 5 and exists "
     "(select * from dim where d_cat = 'z')", [(0,)], 1 + 1),
    # a table nothing names stays whole (select() refuses an empty keep)
    ("uncorrelated_arm_nothing_named",
     "select count(*) from sales where s_amt > 5 and exists "
     "(select * from dim)", [(4,)], 1 + 3),
    ("uncorrelated_arm_explicit_item",
     "select count(*) from sales where s_amt > 5 and exists "
     "(select d_sk + 1 from dim where d_cat = 'b')", [(4,)], 1 + 2),
]


@pytest.mark.parametrize("sql,rows,scan_columns",
                         [c[1:] for c in _EXISTS_ARM_CASES],
                         ids=[c[0] for c in _EXISTS_ARM_CASES])
def test_exists_arms_on_pruned_tables(sql, rows, scan_columns):
    """Each arm of ``_eval_exists`` gives its rows on tables that carry
    only the columns the statement names, and the ``plan`` span states
    how many columns the scans kept."""
    from nds_tpu.obs import export as obs_export
    from nds_tpu.obs import trace as obs_trace
    s = _session()
    obs_trace.drain_spans()
    assert s.sql(sql).collect() == rows
    phases = obs_export.rollup(obs_trace.drain_spans())["phases"]
    assert phases["plan"]["scanColumns"] == scan_columns
