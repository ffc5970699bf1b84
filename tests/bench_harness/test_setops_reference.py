# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""The set-operation mix (``power_setop_outer``) at SF0.01 on the CPU: each
statement through the program against its configuration's reference
(``reference/sqlite_ref_setops.py``) with ``compare.py`` at limits 0, a
fault planted in the program under each statement and caught by that
comparison, the reference's three departures from the stream's text held to
the untouched form on toy tables, and the cell's span metrics on synthetic
records.

The seeds' data is made by child processes (``datagen.ensure``) into a
temporary directory; the program runs in this process, as the engine's own
tests run it."""

import contextlib
import io
import json
import os
import shutil
import sqlite3
from decimal import Decimal

import pytest

from benchmark import compare, datagen, manifest
from benchmark import run as bench_run

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MAN = manifest.Manifest(REPO)
CELL = "sf1_resident_setops.power_setop_outer"
CONFIG = MAN.config(MAN.cell(CELL)["config"])
TRAFFIC = MAN.traffic(MAN.cell(CELL)["traffic"])
STATEMENTS = [q["name"] for q in TRAFFIC["queries"]]
SEEDS = [2_500_000_033, 4242]          # one past 2**31, as the driver's are
SCALE = str(CONFIG["rehearsal"]["scale_factor"])


@pytest.fixture(scope="module")
def ref():
    return manifest.load_module(
        os.path.join(REPO, "benchmark", CONFIG["reference"]), "ref_setops")


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    path = tmp_path_factory.mktemp("setops_cache")
    yield str(path)
    shutil.rmtree(path, ignore_errors=True)


@pytest.fixture(scope="module")
def seeds(cache, ref):
    """{seed: the seed's data, the mix's statements in the stream's order,
    and the configuration's reference's rows}."""
    out = {}
    # the suite's workers run several harness modules at once, and each
    # seed's data takes eight generator and eight transcode children: two
    # of each are plenty at SF0.01 (any chunking gives the program and the
    # reference the same rows)
    children, datagen.TRANSCODE_CHILDREN = datagen.TRANSCODE_CHILDREN, 2
    chunks, datagen.GEN_PARALLEL = datagen.GEN_PARALLEL, 2
    try:
        for seed in SEEDS:
            data = datagen.ensure(REPO, cache, SCALE, seed)
            names, queries, wanted = bench_run.cell_queries(data["stream"],
                                                            TRAFFIC)
            out[seed] = {"data": data, "names": names, "queries": queries,
                         "wanted": wanted,
                         "reference": ref.answers(data["raw"], queries)}
    finally:
        datagen.TRANSCODE_CHILDREN = children
        datagen.GEN_PARALLEL = chunks
    return out


@pytest.fixture(scope="module")
def sessions(seeds):
    """{seed: a Session with the seed's 24 tables, loaded as the Power Run
    loads them}."""
    from nds_tpu import power
    from nds_tpu.engine.session import Session
    out = {}
    for seed, s in seeds.items():
        session = Session({})
        with contextlib.redirect_stdout(io.StringIO()):
            power.setup_tables(session, s["data"]["parquet"], "parquet",
                               bool(CONFIG["use_decimal"]), [])
        out[seed] = session
    yield out
    for session in out.values():
        session.catalog.clear()


def program_rows(session, text):
    from nds_tpu import power
    return session.sql(power.strip_stream_markers(text)).collect()


def verdict_of(seed_state, name, rows):
    return compare.compare_all([{"name": name, "rows": rows}],
                               seed_state["reference"], seed_state["wanted"])


# -- (a) the program against the configuration's reference, limits 0 ----------

def test_the_mix_is_the_traffic_files_statements_in_the_streams_order(seeds):
    in_stream_order = sorted(STATEMENTS, key=lambda n: int(n[len("query"):]))
    assert len(STATEMENTS) == 4 and "query97" in STATEMENTS
    assert {"query38", "query87"} & set(STATEMENTS)
    for s in seeds.values():
        assert s["names"] == in_stream_order
    kinds = {k for q in TRAFFIC["queries"] for k in q["result"]}
    assert kinds == {"int", "str", "cents"}     # the control has a decimal


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", STATEMENTS)
def test_statement_agrees_with_the_reference_at_limits_0(seeds, sessions,
                                                         name, seed):
    s = seeds[seed]
    rows = program_rows(sessions[seed], s["queries"][name]["sql"])
    verdict = verdict_of(s, name, rows)
    assert verdict["correct"] is True, verdict
    assert verdict["compared"] == {"answers_never_came": [0, 0],
                                   "rows_off": [0, 0],
                                   "decimal_gap_max": [0.0, 0]}
    assert verdict["rows"] == len(s["reference"][name]) > 0


# -- (b) a fault planted in the program is caught -------------------------------

def _a_count_off_by_one(monkeypatch):
    from nds_tpu.engine import session as session_mod
    collect = session_mod.Result.collect

    def broken(self):
        rows = list(collect(self))
        return [(rows[0][0] + 1,) + tuple(rows[0][1:])] + rows[1:]
    monkeypatch.setattr(session_mod.Result, "collect", broken)


def _a_cent_off(monkeypatch):
    from nds_tpu.engine import session as session_mod
    collect = session_mod.Result.collect

    def broken(self):
        rows = list(collect(self))
        return [(rows[0][0] + Decimal("0.01"),) + tuple(rows[0][1:])] \
            + rows[1:]
    monkeypatch.setattr(session_mod.Result, "collect", broken)


def _a_duplicate_let_through_the_distinct(monkeypatch):
    from nds_tpu.sql import planner
    monkeypatch.setattr(planner.Planner, "_distinct", lambda self, t: t)


def _a_null_of_the_outer_join_turned_to_0(monkeypatch):
    import jax.numpy as jnp
    from nds_tpu.engine import ops
    from nds_tpu.engine.column import Column

    def zeros_not_nulls(col, n):
        data = jnp.zeros((n,) + col.data.shape[1:], dtype=col.data.dtype)
        return Column(col.kind, data, None, col.dict_values, enc=col.enc)
    monkeypatch.setattr(ops, "_null_column_like", zeros_not_nulls)


FAULTS = [("query38", _a_count_off_by_one, "rows_off"),
          ("query86", _a_cent_off, "decimal_gap_max"),
          ("query87", _a_duplicate_let_through_the_distinct, "rows_off"),
          ("query97", _a_null_of_the_outer_join_turned_to_0, "rows_off")]


@pytest.mark.parametrize("name,fault,caught_by", FAULTS,
                         ids=[f"{n}-{f.__name__.strip('_')}"
                              for n, f, _ in FAULTS])
def test_a_planted_fault_is_caught(seeds, sessions, monkeypatch, name, fault,
                                   caught_by):
    """query38 / query86: the answer altered where it is produced
    (``Result.collect``); query87: the planner's DISTINCT made to keep every
    row; query97: the null extension of an outer join's unmatched rows made
    to write zeros."""
    fault(monkeypatch)
    s = seeds[SEEDS[0]]
    rows = program_rows(sessions[SEEDS[0]], s["queries"][name]["sql"])
    verdict = verdict_of(s, name, rows)
    assert verdict["correct"] is False
    value, limit = verdict["compared"][caught_by]
    assert value > limit == 0, verdict


# -- (c) the reference's departures from the text, on toy tables ----------------

@pytest.fixture()
def toy():
    con = sqlite3.connect(":memory:")
    con.execute("CREATE TABLE t (a INTEGER, b TEXT)")
    con.execute("CREATE TABLE u (a INTEGER, b TEXT)")
    con.execute("CREATE TABLE w (a INTEGER, b TEXT)")
    con.executemany("INSERT INTO t VALUES (?, ?)", [
        (1, "x"), (1, "x"), (2, "y"), (3, None), (None, "z"), (4, "(q)")])
    con.executemany("INSERT INTO u VALUES (?, ?)", [
        (2, "y"), (3, None), (5, "v")])
    con.executemany("INSERT INTO w VALUES (?, ?)", [(1, "x"), (None, "z")])
    yield con
    con.close()


def test_departure_a_only_the_operands_parentheses_go(ref, toy):
    text = ("select count(*) from ((select distinct a, b from t where b <> "
            "'(q)')\n except (select a, b from u)\n except\n (select a, b "
            "from w)) cool")
    with pytest.raises(sqlite3.OperationalError):
        toy.execute(text)              # why the departure exists
    stripped = ref.strip_operand_parentheses(text)
    bare = ("select count(*) from (select distinct a, b from t where b <> "
            "'(q)' except select a, b from u except select a, b from w) cool")
    assert "".join(stripped.split()) == "".join(bare.split())
    # {(1,x), (2,y)} (b <> '(q)' drops the NULL b) - u - w = {}
    assert toy.execute(stripped).fetchall() == [(0,)]
    assert toy.execute(ref.strip_operand_parentheses(
        "select count(*) from ((select a, b from t) intersect "
        "(select a, b from u)) hot")).fetchall() == [(2,)]   # (2,y), (3,NULL)
    # a statement without such operands comes back as it is
    for same in (bare, "select (a + 1) * 2 from t where a in (select a from "
                 "u)", "select '(select 1) union (select 2)' from t",
                 "select a from t where a in (select a from u) union "
                 "select a from w"):
        assert ref.strip_operand_parentheses(same) == same
    with pytest.raises(ValueError):
        ref.strip_operand_parentheses(
            "(select a from t order by a limit 1) union (select a from u)")


WITH_STATEMENT = """
with tt as (select a, count(*) n from t group by a),
     uu as (select a, count(*) n from u group by a)
select sum(case when tt.a is not null and uu.a is null then 1 else 0 end),
       sum(case when tt.a is null and uu.a is not null then 1 else 0 end),
       sum(case when tt.a is not null and uu.a is not null then 1 else 0 end)
from tt full outer join uu on (tt.a = uu.a)"""
OPERAND_STATEMENTS = [
    "select count(*) from (select distinct a, b from t intersect select "
    "distinct a, b from u) x",
    "select a, b from t except select a, b from u except select a, b from w "
    "order by a",
    "select a from (select a, b from t where a in (select a from u intersect "
    "select a from t) union all select a, b from w) y order by a"]


def test_departure_b_materialised_bodies_and_operands_equal_the_inline_form(
        ref, toy):
    inline = toy.execute(WITH_STATEMENT).fetchall()
    # t groups: 1, 2, 3, 4, NULL; u groups: 2, 3, 5; the NULL group joins
    # nothing and counts under no CASE
    assert inline == [(2, 1, 2)]
    rest, made = ref.materialise_with(toy, WITH_STATEMENT)
    assert made == ["tt", "uu"] and rest.lstrip().startswith("select")
    assert toy.execute(rest).fetchall() == inline
    indexes = {r[0]: r[1] for r in toy.execute(
        "SELECT tbl_name, sql FROM sqlite_temp_master WHERE type = 'index'")}
    assert set(indexes) == {"tt", "uu"}          # on the equated column
    assert all('("a")' in sql for sql in indexes.values())
    for name in made:
        toy.execute(f'DROP TABLE "{name}"')
    for text in OPERAND_STATEMENTS:
        want = toy.execute(text).fetchall()
        assert want
        got_text, made = ref.materialise_operands(toy, text)
        assert len(made) in (2, 3) and "_operand" in got_text
        assert toy.execute(got_text).fetchall() == want
        for name in made:
            toy.execute(f'DROP TABLE "{name}"')
    # no INTERSECT or EXCEPT: nothing is made, nothing changes
    plain = "select a from t union all select a from u"
    assert ref.materialise_operands(toy, plain) == (plain, [])
    assert ref.materialise_with(toy, plain) == (plain, [])


ROLLUP_STATEMENT = """
select sum(v) as total, cat, cls,
       grouping(cat) + grouping(cls) as level,
       rank() over (partition by grouping(cat) + grouping(cls),
                    case when grouping(cls) = 0 then cat end
                    order by sum(v) desc) as r
from sales, dim
where sales.k = dim.k
group by rollup(cat, cls)
order by level desc, case when level = 0 then cat end, r
limit 100"""


def test_departure_c_rollup_expands_to_the_union_of_its_groupings(ref):
    con = sqlite3.connect(":memory:")
    con.execute("CREATE TABLE dim (k INTEGER, cat TEXT, cls TEXT)")
    con.execute("CREATE TABLE sales (k INTEGER, v INTEGER)")
    con.executemany("INSERT INTO dim VALUES (?, ?, ?)", [
        (1, "A", "a1"), (2, "A", "a2"), (3, "B", "b1"), (4, None, "n1")])
    con.executemany("INSERT INTO sales VALUES (?, ?)", [
        (1, 10), (1, 5), (2, 40), (3, 7), (3, None), (4, 2), (9, 1000)])
    with pytest.raises(sqlite3.OperationalError):
        con.execute(ROLLUP_STATEMENT)        # why the departure exists
    rows = con.execute(ref.expand_rollup(ROLLUP_STATEMENT)).fetchall()
    # by hand: the grand total; the categories ranked among themselves (a
    # real NULL category is one of them); the classes ranked within their
    # category, categories in order, the NULL one first
    assert rows == [
        (64, None, None, 2, 1),
        (55, "A", None, 1, 1), (7, "B", None, 1, 2), (2, None, None, 1, 3),
        (2, None, "n1", 0, 1),
        (40, "A", "a2", 0, 1), (15, "A", "a1", 0, 2),
        (7, "B", "b1", 0, 1)]
    plain = "select cat, sum(v) from sales, dim where sales.k = dim.k group by cat"
    assert ref.expand_rollup(plain) == plain
    con.close()


def test_the_reference_imports_nothing_of_the_program(ref):
    with open(ref.__file__) as f:
        source = f.read()
    assert "nds_tpu" not in source and "import jax" not in source
    for departure in ("(a)", "(b)", "(c)"):
        assert departure in ref.__doc__


# -- (d) the cell's span metrics on synthetic records ----------------------------

def phase(ms, cells=None):
    p = {"ms": ms, "count": 2, "syncs": 1, "selfMs": ms / 2,
         "syncWaitMs": 0.5, "compileMs": 0.0, "rootMs": 0.0}
    return p if cells is None else dict(p, cells=cells)


RECORDS = [
    {"phases": {"op.setop": phase(40.0, 1000), "op.join": phase(3.0, 9),
                "op.semi_join": phase(1.0, 5)}},
    {"phases": {"op.concat": phase(2.0, 300), "op.window": phase(6.0, 70)}},
    {"phases": {"op.setop": phase(20.0, 500), "op.concat": phase(1.0, 100)}},
    {"phases": {"op.filter": phase(1.0)}},
]
# the parent's spans: no op.setop, an op.concat and an op.window without cells
PARENT = [{"phases": {"op.concat": phase(2.0), "op.window": phase(6.0)}},
          {"phases": {}}]
METRICS = ["resident.setop_ms_per_query", "resident.setop_cells_per_query",
           "resident.window_ms_per_query"]
METRIC_CASES = [
    ("resident.setop_ms_per_query", RECORDS, (40.0 + 20.0) / 4),
    ("resident.setop_cells_per_query", RECORDS, (1000 + 300 + 500 + 100) / 4),
    ("resident.window_ms_per_query", RECORDS, 6.0 / 4),
    ("resident.setop_ms_per_query", PARENT, None),
    ("resident.setop_cells_per_query", PARENT, None),
    ("resident.window_ms_per_query", PARENT, 6.0 / 2),
    ("resident.window_ms_per_query", [{"phases": {"op.sort": phase(1.0)}}],
     None),
    ("resident.setop_ms_per_query", [], None),
    ("resident.setop_cells_per_query", [], None),
    ("resident.window_ms_per_query", [], None),
]


@pytest.mark.parametrize("metric,records,want", METRIC_CASES,
                         ids=[f"{m}-{i}" for i, (m, _, _)
                              in enumerate(METRIC_CASES)])
def test_span_metric_reads_the_rollup_and_nothing_without_it(metric, records,
                                                             want):
    got = MAN.reader(metric)({"records": records})
    assert got is None if want is None else got == pytest.approx(want)


@pytest.mark.parametrize("metric", METRICS)
def test_new_metric_is_listed_with_the_new_cell(metric):
    entry = next(m for m in MAN.doc["per_layer"] if m["name"] == metric)
    assert CELL in entry["workloads"]
    assert entry["layer"] == "resident execution"
    assert entry["moves"] == "power_query_ms"
    assert entry["source"] == "program_span"
    assert entry["better"] == "lower"


def test_the_cell_reports_its_metrics_and_states_its_one_setting():
    """The cell is in the lists of its own metrics and of those every
    resident cell reports, a share of a roofline and the idle share among
    them; its configuration sets one variable and says why."""
    listed = {m["name"] for m in MAN.per_layer(CELL)}
    assert set(METRICS) < listed
    assert {"kernels.scan_roofline", "device.idle_share",
            "device.peak_hbm_bytes", "resident.host_syncs_per_query",
            "resident.join_cells_per_query", "load.tables_s"} < listed
    assert not [m for m in listed if m.startswith("stream.")]
    assert json.dumps(CONFIG["env"]) == '{"NDS_TPU_REPLAY": "off"}'
    assert CONFIG["rehearsal"]["env"] == CONFIG["env"]
    assert "Power Run" in CONFIG["env_why"]["NDS_TPU_REPLAY"]
    assert CONFIG["stream_scans"] == "none" and CONFIG["use_decimal"] is True
    assert MAN.cell(CELL)["chips"] == 1
