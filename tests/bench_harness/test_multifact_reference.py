# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""The multi-fact mix (``power_multifact``) at SF0.01 on the CPU: each
statement through the program against its configuration's reference
(``reference/sqlite_ref_joins.py``) with ``compare.py`` at limits 0, each
planted fault caught by that comparison, the two references row for row on
both mixes, and the two span metrics the cell adds on synthetic records.

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
CELL = "sf1_resident_channels.power_multifact"
CONFIG = MAN.config(MAN.cell(CELL)["config"])
TRAFFIC = MAN.traffic(MAN.cell(CELL)["traffic"])
STATEMENTS = [q["name"] for q in TRAFFIC["queries"]]
SEEDS = [2_500_000_028, 4242]          # one past 2**31, as the driver's are
SCALE = str(CONFIG["rehearsal"]["scale_factor"])


def reference_module(relative: str):
    return manifest.load_module(
        os.path.join(REPO, "benchmark", relative),
        "ref_" + os.path.basename(relative)[:-3])


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    path = tmp_path_factory.mktemp("multifact_cache")
    yield str(path)
    shutil.rmtree(path, ignore_errors=True)


@pytest.fixture(scope="module")
def seeds(cache):
    """{seed: the seed's data, the mix's statements in the stream's order,
    and the configuration's reference's rows}."""
    ref = reference_module(CONFIG["reference"])
    out = {}
    for seed in SEEDS:
        data = datagen.ensure(REPO, cache, SCALE, seed)
        names, queries, wanted = bench_run.cell_queries(data["stream"],
                                                        TRAFFIC)
        out[seed] = {"data": data, "names": names, "queries": queries,
                     "wanted": wanted,
                     "reference": ref.answers(data["raw"], queries)}
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
    for s in seeds.values():
        assert s["names"] == in_stream_order


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


# -- (b) a planted fault in each statement's answer is caught -----------------

def _drop_a_row(rows, kinds):
    return rows[:-1]


def _a_cent_off(rows, kinds):
    col = kinds.index("cents")
    i = next(i for i, r in enumerate(rows) if r[col] is not None)
    row = list(rows[i])
    row[col] = row[col] + Decimal("0.01")
    return rows[:i] + [tuple(row)] + rows[i + 1:]


def _a_group_key_altered(rows, kinds):
    col = kinds.index("str")
    row = list(rows[0])
    row[col] = (row[col] or "") + "x"
    return [tuple(row)] + rows[1:]


def _a_count_off_by_one(rows, kinds):
    col = kinds.index("int")
    row = list(rows[0])
    row[col] = row[col] + 1
    return [tuple(row)] + rows[1:]


FAULTS = [("query10", _drop_a_row, "rows_off"),
          ("query25", _a_cent_off, "decimal_gap_max"),
          ("query29", _a_count_off_by_one, "rows_off"),
          ("query50", _a_group_key_altered, "rows_off")]


@pytest.mark.parametrize("name,fault,caught_by", FAULTS,
                         ids=[f"{n}-{f.__name__.strip('_')}"
                              for n, f, _ in FAULTS])
def test_a_planted_fault_is_caught(seeds, sessions, monkeypatch, name, fault,
                                   caught_by):
    """The answer is altered where it is produced (``Result.collect``)."""
    from nds_tpu.engine import session as session_mod
    kinds = seeds[SEEDS[0]]["wanted"][name]["result"]
    collect = session_mod.Result.collect
    monkeypatch.setattr(session_mod.Result, "collect",
                        lambda self: fault(list(collect(self)), kinds))
    s = seeds[SEEDS[0]]
    rows = program_rows(sessions[SEEDS[0]], s["queries"][name]["sql"])
    verdict = verdict_of(s, name, rows)
    assert verdict["correct"] is False
    value, limit = verdict["compared"][caught_by]
    assert value > limit == 0, verdict


# -- the statements the cost rule took out of the mix (PERF.md section 4) -------
# query51 (cumulative windows over a full outer join) and query97 (a full
# outer join of two grouped facts) cost over 300 s of cold compilation each
# on a v5e, and query97 is recorded for replay at SF1, so no cell times them
# yet. At this scale the reference answers both as it stands (at SF1 their
# WITH bodies would first have to be materialised and indexed: the work of
# the PR that brings such a statement into a cell), and the program agrees
# with it here.

TAKEN_OUT = {
    "query51": {"name": "query51",
                "scans": {"web_sales": ["ws_item_sk", "ws_sold_date_sk",
                                        "ws_sales_price"],
                          "store_sales": ["ss_item_sk", "ss_sold_date_sk",
                                          "ss_sales_price"],
                          "date_dim": ["d_date_sk", "d_date", "d_month_seq"]},
                "result": ["int", "str", "cents", "cents", "cents", "cents"],
                "ordered": True},
    "query97": {"name": "query97",
                "scans": {"store_sales": ["ss_customer_sk", "ss_item_sk",
                                          "ss_sold_date_sk"],
                          "catalog_sales": ["cs_bill_customer_sk",
                                            "cs_item_sk", "cs_sold_date_sk"],
                          "date_dim": ["d_date_sk", "d_month_seq"]},
                "result": ["int", "int", "int"], "ordered": True},
}


def _a_null_turned_to_0(rows, kinds):
    for i, r in enumerate(rows):
        for col, (v, k) in enumerate(zip(r, kinds)):
            if v is None and k == "cents":
                row = list(r)
                row[col] = Decimal("0.00")
                return rows[:i] + [tuple(row)] + rows[i + 1:]
    raise AssertionError("the full outer join left no NULL in the answer")


@pytest.mark.parametrize("name,fault", [
    ("query51", None), ("query51", _a_null_turned_to_0),
    ("query97", None), ("query97", _a_count_off_by_one)],
    ids=["query51-agrees", "query51-a_null_turned_to_0", "query97-agrees",
         "query97-a_count_off_by_one"])
def test_a_statement_taken_out_still_agrees_and_its_fault_is_caught(
        seeds, sessions, name, fault):
    seed = SEEDS[0]
    data = seeds[seed]["data"]
    entry = TAKEN_OUT[name]
    _, queries, wanted = bench_run.cell_queries(data["stream"],
                                                {"queries": [entry]})
    reference = reference_module(CONFIG["reference"]).answers(data["raw"],
                                                              queries)
    assert reference[name]
    rows = program_rows(sessions[seed], queries[name]["sql"])
    if fault is not None:
        rows = fault(list(rows), entry["result"])
    verdict = compare.compare_all([{"name": name, "rows": rows}], reference,
                                  wanted)
    assert verdict["correct"] is (fault is None), verdict
    assert (verdict["compared"]["rows_off"][0] > 0) is (fault is not None)


# -- (c) the two references, row for row ----------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("traffic", ["power_scan_join", "power_multifact"])
def test_the_two_references_agree_row_for_row(seeds, traffic, seed):
    standing = reference_module("reference/sqlite_ref.py")
    joins = reference_module(CONFIG["reference"])
    data = seeds[seed]["data"]
    names, queries, _ = bench_run.cell_queries(data["stream"],
                                               MAN.traffic(traffic))
    want = standing.answers(data["raw"], queries)
    got = (seeds[seed]["reference"] if traffic == "power_multifact"
           else joins.answers(data["raw"], queries))
    assert list(got) == list(want) == names
    for name in names:
        assert got[name] == want[name], name
        assert want[name], f"{name}: an empty answer proves nothing"


def test_the_reference_imports_nothing_of_the_program_and_indexes_the_equated():
    joins = reference_module(CONFIG["reference"])
    with open(joins.__file__) as f:
        source = f.read()
    assert "nds_tpu" not in source and "import jax" not in source
    assert joins.equated_columns(
        "select * from a full outer join b on (a.k = b.K)") == {"k"}
    assert joins.equated_columns("ss_item_sk = sr_item_sk and x=y") == {
        "ss_item_sk", "sr_item_sk", "x", "y"}
    assert joins.equated_columns("d_year = 2001 and a.x <= b.y") == set()
    con = sqlite3.connect(":memory:")
    con.execute("CREATE TABLE t (a INTEGER, b INTEGER)")
    con.execute("CREATE TABLE u (a INTEGER, c INTEGER)")
    joins.index_equated(con, ["select b from t, u where t.a = u.a and c = 3"])
    assert {r[0] for r in con.execute(
        "SELECT name FROM sqlite_master WHERE type = 'index'")} == {
            "ix_t_a", "ix_u_a"}
    con.close()


# -- (d) the two span metrics on synthetic records --------------------------------

def phase(self_ms, cells=None):
    p = {"ms": self_ms + 1.0, "count": 2, "syncs": 0, "selfMs": self_ms,
         "syncWaitMs": 0.5, "compileMs": 0.0, "rootMs": 0.0}
    return p if cells is None else dict(p, cells=cells)


RECORDS = [
    {"phases": {"op.join": phase(40.0, 1000), "op.sort": phase(3.0),
                "op.gather": phase(9.0, 77)}},
    {"phases": {"op.semi_join": phase(20.0, 500), "op.join": phase(4.0, 100)}},
    {"phases": {"op.filter": phase(1.0)}},
    {"phases": {}},
]
METRICS = ["resident.join_ms_per_query", "resident.join_cells_per_query"]
METRIC_CASES = [
    ("resident.join_ms_per_query", RECORDS, (40.0 + 20.0 + 4.0) / 4),
    ("resident.join_cells_per_query", RECORDS, (1000 + 500 + 100) / 4),
    # the parent's spans state no cells: nothing is read, nothing raised
    ("resident.join_cells_per_query",
     [{"phases": {"op.join": phase(40.0)}}, {"phases": {}}], None),
    ("resident.join_ms_per_query", [{"phases": {"op.sort": phase(1.0)}}],
     None),
    ("resident.join_ms_per_query", [], None),
    ("resident.join_cells_per_query", [], None),
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
    """The cell is IN the metric's list; the list is not pinned, so a later
    cell that joins facts is named there by an entry alone."""
    entry = next(m for m in MAN.doc["per_layer"] if m["name"] == metric)
    assert CELL in entry["workloads"]
    assert entry["layer"] == "resident execution"
    assert entry["moves"] == "power_query_ms"
    assert entry["source"] == "program_span"
    assert entry["better"] == "lower"


def test_the_cell_reports_its_metrics_and_runs_engine_defaults():
    """A per-layer metric is reported for a cell only where its list names
    it: the cell's own two are among those it reports, with at least one
    the benchmark already had."""
    listed = {m["name"] for m in MAN.per_layer(CELL)}
    assert set(METRICS) < listed
    assert json.dumps(CONFIG["env"]) == "{}"
