# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""The subquery mix (``power_subquery``) at SF0.01 on the CPU: each statement
through the program against its configuration's reference
(``reference/sqlite_ref_subqueries.py``) with ``compare.py`` at limits 0, as
the stream writes it and with its parameters WIDENED (at SF0.01 a seed's 60
days of one state and one company match no web order, and a NULL sum agrees
with anything); a fault planted in the program and caught; and the
reference's three departures from the stream's text held to the untouched
form on toy tables.

The seeds' data is made by child processes (``datagen.ensure``) into a
temporary directory; the program runs in this process, as the engine's own
tests run it."""

import contextlib
import io
import os
import re
import shutil
import sqlite3

import pytest

from benchmark import compare, datagen, manifest
from benchmark import run as bench_run

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MAN = manifest.Manifest(REPO)
CELL = "sf1_resident_subqueries.power_subquery"
CONFIG = MAN.config(MAN.cell(CELL)["config"])
TRAFFIC = MAN.traffic(MAN.cell(CELL)["traffic"])
STATEMENTS = [q["name"] for q in TRAFFIC["queries"]]
SEEDS = [2_500_000_035, 4242]          # one past 2**31, as the driver's are
SCALE = str(CONFIG["rehearsal"]["scale_factor"])


def widened(text: str) -> str:
    """The statement with its parameters opened up: every date window starts
    in 1998 and lasts 2,500 days, any state, any company, any manufacturer.
    The program and the reference get the same text."""
    text = re.sub(r"interval \d+ days", "interval 2500 days", text)
    text = re.sub(r"cast\('\d{4}-\d{2}-\d{2}' as date\)",
                  "cast('1998-01-01' as date)", text)
    text = re.sub(r"\n\s*and ca_state = '[A-Z]+'", "", text)
    text = re.sub(r"\n\s*and web_company_name = 'pri'", "", text)
    return re.sub(r"i_manufact_id = \d+", "i_manufact_id > 0", text)


@pytest.fixture(scope="module")
def ref():
    return manifest.load_module(
        os.path.join(REPO, "benchmark", CONFIG["reference"]), "ref_subqueries")


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    path = tmp_path_factory.mktemp("subquery_cache")
    yield str(path)
    shutil.rmtree(path, ignore_errors=True)


@pytest.fixture(scope="module")
def seeds(cache, ref):
    """{seed: the seed's data, the mix's statements in the stream's order
    as written and widened, and the reference's rows for both}."""
    out = {}
    # two generator and two transcode children a seed are plenty at SF0.01
    # (the suite's workers run several harness modules at once)
    children, datagen.TRANSCODE_CHILDREN = datagen.TRANSCODE_CHILDREN, 2
    chunks, datagen.GEN_PARALLEL = datagen.GEN_PARALLEL, 2
    try:
        for seed in SEEDS:
            data = datagen.ensure(REPO, cache, SCALE, seed)
            names, queries, wanted = bench_run.cell_queries(data["stream"],
                                                            TRAFFIC)
            wide = {n: dict(q, sql=widened(q["sql"]))
                    for n, q in queries.items()}
            out[seed] = {"data": data, "names": names, "wanted": wanted,
                         "written": (queries,
                                     ref.answers(data["raw"], queries)),
                         "widened": (wide, ref.answers(data["raw"], wide))}
    finally:
        datagen.TRANSCODE_CHILDREN = children
        datagen.GEN_PARALLEL = chunks
    return out


@pytest.fixture(scope="module")
def sessions(seeds):
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


def verdict_of(seed_state, form, name, rows):
    _queries, reference = seed_state[form]
    return compare.compare_all([{"name": name, "rows": rows}], reference,
                               seed_state["wanted"])


# -- (1) the program against the configuration's reference, limits 0 ----------

def test_the_mix_is_the_traffic_files_statements_in_the_streams_order(seeds):
    in_stream_order = sorted(STATEMENTS, key=lambda n: int(n[len("query"):]))
    assert len(STATEMENTS) == 4
    for s in seeds.values():
        assert s["names"] == in_stream_order


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", STATEMENTS)
@pytest.mark.parametrize("form", ["written", "widened"])
def test_statement_agrees_with_the_reference_at_limits_0(seeds, sessions,
                                                         form, name, seed):
    s = seeds[seed]
    queries, reference = s[form]
    rows = program_rows(sessions[seed], queries[name]["sql"])
    verdict = verdict_of(s, form, name, rows)
    assert verdict["correct"] is True, verdict
    assert verdict["compared"] == {"answers_never_came": [0, 0],
                                   "rows_off": [0, 0],
                                   "decimal_gap_max": [0.0, 0]}
    assert verdict["rows"] == len(reference[name])
    if form == "widened":
        # the mechanism saw rows: no statement answers with NULLs alone
        assert reference[name] and all(
            v is not None for row in reference[name] for v in row)


# -- (2) a fault planted in the program is caught -------------------------------

def _a_distinct_count_off_by_one(monkeypatch):
    from nds_tpu.sql import planner
    count = planner.Planner._count_distinct

    def broken(self, arg, gids, ng, n_base):
        col = count(self, arg, gids, ng, n_base)
        return type(col)(col.kind, col.data + 1, col.valid)
    monkeypatch.setattr(planner.Planner, "_count_distinct", broken)


def _the_residual_of_an_exists_dropped(monkeypatch):
    """``ws1.ws_warehouse_sk <> ws2.ws_warehouse_sk`` never evaluated: an
    order matches itself and every order of two lines qualifies."""
    import jax.numpy as jnp
    from nds_tpu.sql import planner
    mask = planner.Planner._conjunct_mask

    def broken(self, table, conjuncts):
        if any("warehouse" in repr(c) for c in conjuncts):
            return jnp.ones(table.plen, dtype=bool)
        return mask(self, table, conjuncts)
    monkeypatch.setattr(planner.Planner, "_conjunct_mask", broken)


def _a_cent_off(monkeypatch):
    from decimal import Decimal
    from nds_tpu.engine import session as session_mod
    collect = session_mod.Result.collect

    def broken(self):
        rows = list(collect(self))
        return [tuple(rows[0][:-1]) + (rows[0][-1] + Decimal("0.01"),)] \
            + rows[1:]
    monkeypatch.setattr(session_mod.Result, "collect", broken)


FAULTS = [("query94", _a_distinct_count_off_by_one, "rows_off"),
          ("query94", _the_residual_of_an_exists_dropped, "rows_off"),
          ("query32", _a_cent_off, "decimal_gap_max")]


@pytest.mark.parametrize("name,fault,caught_by", FAULTS,
                         ids=[f"{n}-{f.__name__.strip('_')}"
                              for n, f, _ in FAULTS])
def test_a_planted_fault_is_caught(seeds, sessions, monkeypatch, name, fault,
                                   caught_by):
    if name not in STATEMENTS:
        pytest.skip(f"{name} is not in the mix")
    fault(monkeypatch)
    s = seeds[SEEDS[0]]
    queries, _reference = s["widened"]
    rows = program_rows(sessions[SEEDS[0]], queries[name]["sql"])
    verdict = verdict_of(s, "widened", name, rows)
    assert verdict["correct"] is False
    value, limit = verdict["compared"][caught_by]
    assert value > limit == 0, verdict


# -- (3) the reference's departures from the text, on toy tables ----------------

@pytest.fixture()
def toy():
    con = sqlite3.connect(":memory:")
    con.execute("CREATE TABLE d (sk INTEGER, day TEXT)")
    con.execute("CREATE TABLE f (ord INTEGER, wh INTEGER, sk INTEGER, "
                "amt INTEGER)")
    con.execute("CREATE TABLE r (ord INTEGER)")
    days = ["1999-01-31", "1999-02-01", "1999-03-15", "1999-04-02",
            "1999-04-03", "2000-02-29", None]
    con.executemany("INSERT INTO d VALUES (?, ?)", list(enumerate(days)))
    con.executemany("INSERT INTO f VALUES (?, ?, ?, ?)", [
        (1, 10, 1, 500), (1, 11, 2, 700), (2, 10, 2, 100), (3, 12, 3, 900),
        (3, None, 3, 50), (4, 13, 4, 10), (None, 10, 2, 1), (5, 14, 6, 77),
        (6, 10, 5, 60), (6, 11, 0, 65)])
    con.executemany("INSERT INTO r VALUES (?)", [(1,), (4,), (None,)])
    yield con
    con.close()


DATE_STATEMENT = """
select count(*), sum(amt) from f, d
where f.sk = d.sk
  and d.day between cast('1999-02-01' as date)
                and (cast('1999-02-01' as date) + interval 60 days)"""


def test_departure_a_dates_become_the_iso_text_they_stand_for(ref, toy):
    with pytest.raises(sqlite3.OperationalError):
        toy.execute(DATE_STATEMENT)          # why the departure exists
    rewritten = ref.iso_dates(DATE_STATEMENT)
    assert "'1999-02-01'" in rewritten and "('1999-04-02')" in rewritten
    assert "cast" not in rewritten and "interval" not in rewritten
    # the untouched form, in SQLite's own date arithmetic
    own = DATE_STATEMENT.replace(
        "(cast('1999-02-01' as date) + interval 60 days)",
        "date('1999-02-01', '+60 days')").replace(
        "cast('1999-02-01' as date)", "date('1999-02-01')")
    want = toy.execute(own).fetchall()
    # days 1999-02-01, 03-15 and 04-02 (the last day counts): six facts
    assert want == [(6, 500 + 700 + 100 + 1 + 900 + 50)]
    assert toy.execute(rewritten).fetchall() == want
    assert toy.execute("select date('1999-12-15', '+90 days'), "
                       "date('2000-03-01', '-1 days')").fetchall() == [
        (ref.iso_dates("cast('1999-12-15' as date) + interval 90 days")
         .strip("'"),
         ref.iso_dates("cast('2000-03-01' as date) - interval 1 day")
         .strip("'"))] == [("2000-03-14", "2000-02-29")]
    # SQLite's own cast of a date reads the year as a number
    assert toy.execute("select cast('1999-02-01' as date)").fetchall() == [
        (1999,)]
    same = "select ord from f where amt > 10 and wh in (select wh from f)"
    assert ref.iso_dates(same) == same
    with pytest.raises(ValueError):
        ref.iso_dates("select d.day + interval 1 month from d")


WITH_STATEMENT = """
with per_order as (select ord, wh, sum(amt) total from f group by ord, wh),
     pairs as (select f1.ord, f1.wh wh1, f2.wh wh2 from f f1, f f2
               where f1.ord = f2.ord and f1.wh <> f2.wh)
select p1.ord, p1.wh, p1.total from per_order p1
where p1.total > (select avg(total) * 1.2 from per_order p2
                  where p1.ord = p2.ord)
   or p1.ord in (select ord from pairs)
   or p1.ord in (select r.ord from r, pairs where r.ord = pairs.ord)
order by p1.ord, p1.wh"""


def test_departures_b_and_c_bodies_and_indexes_keep_the_rows(ref, toy):
    inline = toy.execute(WITH_STATEMENT).fetchall()
    # orders 1 and 6 ship from two warehouses (order 3's other warehouse is
    # NULL: <> is unknown); order 3's 900 passes 1.2 x the 475 its two
    # totals average
    assert inline == [(1, 10, 500), (1, 11, 700), (3, 12, 900), (6, 10, 60),
                      (6, 11, 65)]
    rest, made = ref.sqlite_ref_setops.materialise_with(toy, WITH_STATEMENT)
    assert made == ["per_order", "pairs"]
    assert rest.lstrip().startswith("select")
    assert toy.execute(rest).fetchall() == inline
    ref.index_bodies(toy, made, rest)
    assert toy.execute(rest).fetchall() == inline
    indexes = {r[0] for r in toy.execute(
        "SELECT name FROM sqlite_temp_master WHERE type = 'index'")}
    # one a column the rest equates: a lookup by ord alone is served
    assert {"ix_per_order_ord", "ix_pairs_ord"} <= indexes
    plan = " ".join(r[-1] for r in toy.execute("EXPLAIN QUERY PLAN " + rest))
    assert "ix_per_order_ord" in plan or "ix_per_order" in plan
    for name in made:
        toy.execute(f'DROP TABLE "{name}"')
    # the whole of answer(): same rows, nothing left behind
    assert [tuple(r) for r in ref.answer(toy, WITH_STATEMENT + "\n;")] \
        == inline
    assert not toy.execute("SELECT name FROM sqlite_temp_master").fetchall()


EXISTS_STATEMENT = """
select count(distinct f1.ord), sum(f1.amt) from f f1
where exists (select * from f f2 where f1.ord = f2.ord and f1.wh <> f2.wh)
  and not exists (select * from r where f1.ord = r.ord)"""


def test_departure_c_an_index_on_the_facts_keeps_the_rows(ref, toy):
    want = toy.execute(EXISTS_STATEMENT).fetchall()
    assert want == [(1, 125)]              # order 6 alone: order 1 came back
    ref.sqlite_ref_joins.index_equated(toy, [EXISTS_STATEMENT])
    indexed = {r[0] for r in toy.execute(
        "SELECT name FROM sqlite_master WHERE type = 'index'")}
    assert {"ix_f_ord", "ix_r_ord"} <= indexed
    assert toy.execute(EXISTS_STATEMENT).fetchall() == want


def test_the_reference_imports_nothing_of_the_program(ref):
    with open(ref.__file__) as f:
        source = f.read()
    assert "nds_tpu" not in source and "import jax" not in source
    for departure in ("(a)", "(b)", "(c)"):
        assert departure in ref.__doc__
    assert "1.2 * avg" in ref.__doc__      # what stays inexact is said
