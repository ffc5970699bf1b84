# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""The inventory mix (``power_inventory``) at SF0.01 on the CPU: each statement
through the program against its configuration's reference
(``reference/sqlite_ref_inventory.py``) with ``compare.py`` at limits 0, as
the stream writes it and with its parameters WIDENED (at SF0.01 query21's
price band of 50 cents holds no item); a run of the cell's rehearsal with
one of query72's counts altered, caught; and the reference's departures
from the stream's text and its faster asking held to the untouched form on
toy tables.

The seeds' data is made by child processes (``datagen.ensure``) into a
temporary directory; the program runs in this process, as the engine's own
tests run it."""

import contextlib
import io
import json
import os
import random
import re
import shutil
import signal
import sqlite3
import subprocess
import sys

import pytest

from benchmark import compare, manifest
from benchmark import run as bench_run

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MAN = manifest.Manifest(REPO)
CELL = "sf1_resident_inventory.power_inventory"
CONFIG = MAN.config(MAN.cell(CELL)["config"])
TRAFFIC = MAN.traffic(MAN.cell(CELL)["traffic"])
STATEMENTS = [q["name"] for q in TRAFFIC["queries"]]
# query72 returns rows at SF0.01 on both (3 and 48); one is past 2**31
SEEDS = [3_900_000_001, 4242]
SCALE = str(CONFIG["rehearsal"]["scale_factor"])
REFERENCE = os.path.join(REPO, "benchmark", CONFIG["reference"])


def widened(text: str) -> str:
    """The statement with its parameters opened up: date windows ten times
    as long, a price band of 0.50 to 50.00 in query21 and of any price in
    query37 and query82, any marital status and buying potential in query72
    (query37 and query82 keep their four manufacturers: with any, the LIMIT
    cuts a non-unique order). The program and the reference get the same
    text."""
    text = re.sub(r"(as date\)\s*[+-]\s*interval )(\d+) days",
                  lambda m: f"{m.group(1)}{int(m.group(2)) * 10} days", text)
    text = re.sub(r"between 0\.99 and 1\.49", "between 0.50 and 50.00", text)
    text = re.sub(r"i_current_price between (\d+) and \1 \+ 30",
                  "i_current_price between 0 and 1000", text)
    text = re.sub(r"\n\s*and hd_buy_potential = '[^']*'", "", text)
    return re.sub(r"\n\s*and cd_marital_status = '[^']*'", "", text)


@pytest.fixture(scope="module")
def ref():
    return manifest.load_module(REFERENCE, "ref_inventory")


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    path = tmp_path_factory.mktemp("inventory_cache")
    yield str(path)
    shutil.rmtree(path, ignore_errors=True)


MAKE_DATA = """
import json, sys
from benchmark import datagen
datagen.TRANSCODE_CHILDREN = datagen.GEN_PARALLEL = 2
print(json.dumps(datagen.ensure(sys.argv[1], sys.argv[2], sys.argv[3],
                                int(sys.argv[4]))))
"""


def make_data(cache, seed):
    """``datagen.ensure`` in a child process of its own, with a time limit:
    a transcode child has been seen to deadlock under the loaded suite, and
    a second attempt starts the seed's entry again (it has no done.json)."""
    for attempt in range(2):
        proc = subprocess.Popen(
            [sys.executable, "-c", MAKE_DATA, REPO, cache, SCALE, str(seed)],
            cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, start_new_session=True)
        try:
            out, err = proc.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.communicate()
            continue
        assert proc.returncode == 0, err[-2000:]
        return json.loads(out.strip().splitlines()[-1])
    pytest.fail(f"the data of seed {seed} was not made in two attempts")


@pytest.fixture(scope="module")
def seeds(cache, ref):
    """{seed: the seed's data, the mix's statements in the stream's order
    as written and widened, and the reference's rows for both}."""
    out = {}
    for seed in SEEDS:
        data = make_data(cache, seed)
        names, queries, wanted = bench_run.cell_queries(data["stream"],
                                                        TRAFFIC)
        wide = {n: dict(q, sql=widened(q["sql"])) for n, q in queries.items()}
        out[seed] = {"data": data, "names": names, "wanted": wanted,
                     "written": (queries, ref.answers(data["raw"], queries)),
                     "widened": (wide, ref.answers(data["raw"], wide))}
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
    from nds_tpu import power
    s = seeds[seed]
    queries, reference = s[form]
    rows = sessions[seed].sql(
        power.strip_stream_markers(queries[name]["sql"])).collect()
    verdict = compare.compare_all([{"name": name, "rows": rows}], reference,
                                  s["wanted"])
    assert verdict["correct"] is True, verdict
    assert verdict["compared"] == {"answers_never_came": [0, 0],
                                   "rows_off": [0, 0],
                                   "decimal_gap_max": [0.0, 0]}
    assert verdict["rows"] == len(reference[name])
    if form == "widened":
        assert reference[name], "the widened statement found no row"
    if name == "query72":
        assert reference[name], "query72 has rows on both seeds as written"
    if name in ("query37", "query82"):
        # ordered by i_item_id alone, which is not unique in item: under the
        # LIMIT the rows are a multiset only while the LIMIT cuts none
        assert len(reference[name]) < 100


# -- (2) a run of the cell's rehearsal with one of query72's counts altered ---

ALTER_A_COUNT = """
from nds_tpu.engine import session as _s
_collect = _s.Result.collect
def _broken(self):
    rows = _collect(self)
    if rows and len(rows[0]) == 6:      # query72: ..., promo, total_cnt
        rows[0] = tuple(rows[0][:5]) + (rows[0][5] + 1,)
    return rows
_s.Result.collect = _broken
"""
WRAPPER = """
import sys
cache = sys.argv[1]
{patch}
from benchmark import run
run.CACHE_DIR = cache
sys.exit(run.main(sys.argv[2:]))
"""


def test_a_count_of_query72_altered_makes_the_rehearsal_not_correct(cache,
                                                                    seeds):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", WRAPPER.format(patch=ALTER_A_COUNT), cache,
         "--workload", CELL, "--seed", str(SEEDS[0]), "--seconds", "1",
         "--trace", "0", "--rehearse"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is False and result["failed"] == 0
    off, limit = result["compared"]["rows_off"]
    assert off > limit == 0
    assert "worst: query72" in proc.stderr


# -- (3) the departures and the faster asking, on toy tables ------------------

@pytest.fixture()
def toy():
    con = sqlite3.connect(":memory:")
    con.execute("CREATE TABLE d (sk INTEGER, day TEXT, week INTEGER)")
    days = ["1999-02-25", "1999-02-28", "1999-03-01", "1999-03-02",
            "1999-03-06", "1999-03-07", "2000-02-29", "2000-03-05", None]
    con.executemany("INSERT INTO d VALUES (?, ?, ?)",
                    [(i, day, i // 3) for i, day in enumerate(days)])
    con.execute("CREATE TABLE p (id INTEGER, cents INTEGER, units REAL, "
                "qty INTEGER)")
    prices = [98, 99, 100, 149, 150, 6799, 6800, 9800, 9801, None]
    con.executemany("INSERT INTO p VALUES (?, ?, ?, ?)", [
        (i, c, None if c is None else c / 100, q)
        for i, (c, q) in enumerate(zip(prices, [3, 7, 0, 12, 5, 9, 4, 2,
                                                 8, 1]))])
    yield con
    con.close()


def test_departure_a_a_column_cast_to_date_is_the_column(ref, toy):
    text = ("select sk from d where cast(day as date) < "
            "cast('1999-03-02' as date) order by sk")
    rewritten = ref.sqlite_ref_subqueries.iso_dates(ref.column_dates(text))
    assert rewritten == ("select sk from d where day < '1999-03-02' "
                         "order by sk")
    own = ("select sk from d where date(day) < date('1999-03-02') "
           "order by sk")
    want = toy.execute(own).fetchall()
    assert want == [(0,), (1,), (2,)]
    assert toy.execute(rewritten).fetchall() == want
    # why: SQLite's own cast of a date is the number of its year
    assert toy.execute("select cast(day as date) from d where sk = 0"
                       ).fetchall() == [(1999,)]
    assert ref.column_dates("cast('1999-03-02' as date)") \
        == "cast('1999-03-02' as date)"


def test_departure_b_a_column_plus_days_is_sqlites_date(ref, toy):
    text = ("select d3.sk, d1.sk from d d1, d d3 where d3.day > d1.day + "
            "interval 5 days and d1.day < d3.day - interval 2 day "
            "order by 1, 2")
    rewritten = ref.column_days(text)
    assert "date(d1.day, '+5 days')" in rewritten
    assert "date(d3.day, '-2 days')" in rewritten
    assert "interval" not in rewritten
    own = ("select d3.sk, d1.sk from d d1, d d3 where julianday(d3.day) > "
           "julianday(d1.day) + 5 and julianday(d1.day) < julianday(d3.day)"
           " - 2 order by 1, 2")
    want = toy.execute(own).fetchall()
    assert len(want) > 5          # leap days and month ends among them
    assert toy.execute(rewritten).fetchall() == want
    # a literal's interval is left to iso_dates
    lit = "cast('1999-12-15' as date) + interval 90 days"
    assert ref.column_days(lit) == lit


@pytest.mark.parametrize("written,units", [
    ("cents between 0.99 and 1.49", "units between 0.99 and 1.49"),
    ("cents between 68 and 68 + 30", "units between 68 and 68 + 30"),
    ("p.cents between 1.00 and 99.99", "p.units between 1.00 and 99.99")])
def test_departure_c_literals_against_a_decimal_in_hundredths(ref, toy,
                                                              written, units):
    decimals = {"cents"}
    rewritten = ref.cents_literals(f"select id from p where {written} "
                                   "order by id", decimals)
    want = toy.execute(f"select id from p where {units} order by id"
                       ).fetchall()
    assert want
    assert toy.execute(rewritten).fetchall() == want
    # untouched, SQLite compares hundredths with units
    assert toy.execute(f"select id from p where {written} order by id"
                       ).fetchall() != want


def test_departure_c_refuses_what_it_cannot_rewrite(ref):
    decimals = {"cents"}
    assert ref.cents_literals("where cents between 99 and 149", decimals) \
        == "where cents between 9900 and 14900"
    same = "where qty between 100 and 500 and cents_x > 3"
    assert ref.cents_literals(same, decimals) == same
    for bad in ("where cents between 0.995 and 1",
                "where cents > 1.5", "where 1.5 < cents",
                "where cents between 1 and 2 * 3"):
        with pytest.raises(ValueError):
            ref.cents_literals(bad, decimals)
    assert "i_current_price" in ref.decimal_columns()
    assert "inv_quantity_on_hand" not in ref.decimal_columns()


def test_departure_d_a_ratio_of_two_names_divides_as_reals(ref, toy):
    text = ("select id from (select id, qty a, id + 1 b from p) x where "
            "(case when b > 0 then a / b else null end) "
            "between 2.0 / 3.0 and 3.0 / 2.0 order by id")
    rewritten = ref.real_division(text, {"cents"})
    assert "then 1.0 * a / b else" in rewritten
    assert "2.0 / 3.0" in rewritten
    own = text.replace("a / b", "cast(a as real) / b")
    want = toy.execute(own).fetchall()
    assert want == [(4,), (5,), (8,)]      # 5/5, 9/6 and 8/9
    assert toy.execute(rewritten).fetchall() == want
    assert toy.execute(text).fetchall() != want   # 8/9 -> 0
    with pytest.raises(ValueError):
        ref.real_division("select cents / qty from p", {"cents"})


def _raw_table(raw, table, rows, files=2):
    """``rows`` as the generator writes them: ``|``-separated, a trailing
    ``|``, NULL as an empty field, split over ``files`` files."""
    os.makedirs(os.path.join(raw, table), exist_ok=True)
    for f in range(files):
        with open(os.path.join(raw, table, f"{table}_{f + 1}_{files}.dat"),
                  "w") as out:
            for row in rows[f::files]:
                out.write("|".join("" if v is None else str(v) for v in row)
                          + "|\n")


@pytest.fixture()
def raw(tmp_path):
    """A raw directory with inventory, item, date_dim and warehouse rows
    (prices in units with two decimals, some NULLs)."""
    rnd = random.Random(39)
    path = str(tmp_path / "raw")
    schema = manifest.load_module(
        os.path.join(REPO, "benchmark", "reference", "sqlite_ref.py"),
        "standing").load_schema()

    def rows(table, n, values):
        return [[values.get(name, lambda i: None)(i) for name, _ in
                 schema[table]] for i in range(n)]
    _raw_table(path, "inventory", rows("inventory", 1001, {
        "inv_date_sk": lambda i: 2450815 + 7 * (i % 9),
        "inv_item_sk": lambda i: 1 + i // 45,
        "inv_warehouse_sk": lambda i: 1 + i % 5,
        "inv_quantity_on_hand": lambda i: None if i % 13 == 0
        else rnd.randrange(1000)}))
    _raw_table(path, "item", rows("item", 30, {
        "i_item_sk": lambda i: i + 1,
        "i_item_id": lambda i: f"AAAA{i % 12:04d}",
        "i_current_price": lambda i: None if i == 4 else f"{i * 0.37:.2f}"}))
    _raw_table(path, "warehouse", rows("warehouse", 5, {
        "w_warehouse_sk": lambda i: i + 1,
        "w_warehouse_name": lambda i: f"wh {i}"}), files=1)
    _raw_table(path, "date_dim", rows("date_dim", 80, {
        "d_date_sk": lambda i: 2450815 + i,
        "d_date": lambda i: f"1998-{1 + i // 28:02d}-{1 + i % 28:02d}"}))
    return path


SCANS = {"inventory": ["inv_date_sk", "inv_item_sk", "inv_warehouse_sk",
                       "inv_quantity_on_hand"],
         "item": ["i_item_sk", "i_item_id", "i_current_price"],
         "warehouse": ["w_warehouse_sk", "w_warehouse_name"],
         "date_dim": ["d_date_sk", "d_date"]}


def _tables(con):
    return {t: sorted(con.execute(f'SELECT * FROM "{t}"').fetchall(),
                      key=repr) for t in SCANS}


@pytest.mark.parametrize("per_insert", [7, 256])
def test_e_and_f_the_loader_holds_what_the_standing_one_holds(
        ref, raw, monkeypatch, per_insert):
    """Many rows a statement (e), a remainder included, load the values the
    standing loader does, in the same order; the indexes (f) are the listed
    ones over the tables loaded, and query21's text gives the same rows."""
    monkeypatch.setattr(ref, "ROWS_PER_INSERT", per_insert)
    mine = ref.connect(raw, SCANS)
    standing = ref.sqlite_ref.connect(raw, SCANS)
    assert _tables(mine) == _tables(standing)
    for t in SCANS:
        assert mine.execute(f'SELECT * FROM "{t}"').fetchall() \
            == standing.execute(f'SELECT * FROM "{t}"').fetchall()
    assert mine.execute("SELECT count(*), count(inv_quantity_on_hand) "
                        "FROM inventory").fetchall() == [(1001, 924)]
    made = {r[0] for r in mine.execute(
        "SELECT name FROM sqlite_master WHERE type = 'index'")}
    assert made == {f"ix_{t}_{'_'.join(c)}" for t, c in ref.INDEXES
                    if set(c) <= set(SCANS.get(t, ()))}
    assert "ix_inventory_inv_item_sk_inv_date_sk" in made
    text = """
select * from (select w_warehouse_name, i_item_id,
       sum(case when (cast(d_date as date) < cast('1998-02-20' as date))
                then inv_quantity_on_hand else 0 end) as inv_before,
       sum(case when (cast(d_date as date) >= cast('1998-02-20' as date))
                then inv_quantity_on_hand else 0 end) as inv_after
  from inventory, warehouse, item, date_dim
  where i_current_price between 0.99 and 9.49
    and i_item_sk = inv_item_sk and inv_warehouse_sk = w_warehouse_sk
    and inv_date_sk = d_date_sk
    and d_date between (cast('1998-02-20' as date) - interval 30 days)
                   and (cast('1998-02-20' as date) + interval 30 days)
  group by w_warehouse_name, i_item_id) x
where (case when inv_before > 0 then inv_after / inv_before else null end)
      between 2.0 / 3.0 and 3.0 / 2.0
order by w_warehouse_name, i_item_id"""
    sql = ref.statement(text, ref.decimal_columns())
    got = mine.execute(sql).fetchall()
    assert got and got == standing.execute(sql).fetchall()


Q72 = """
select i_item_desc, w_warehouse_name, d1.d_week_seq,
       sum(case when p_promo_sk is null then 1 else 0 end) no_promo,
       sum(case when p_promo_sk is not null then 1 else 0 end) promo,
       count(*) total_cnt
from catalog_sales
join inventory on (cs_item_sk = inv_item_sk)
join warehouse on (w_warehouse_sk = inv_warehouse_sk)
join item on (i_item_sk = cs_item_sk)
join customer_demographics on (cs_bill_cdemo_sk = cd_demo_sk)
join household_demographics on (cs_bill_hdemo_sk = hd_demo_sk)
join date_dim d1 on (cs_sold_date_sk = d1.d_date_sk)
join date_dim d2 on (inv_date_sk = d2.d_date_sk)
join date_dim d3 on (cs_ship_date_sk = d3.d_date_sk)
left outer join promotion on (cs_promo_sk = p_promo_sk)
left outer join catalog_returns on (cr_item_sk = cs_item_sk
                                    and cr_order_number = cs_order_number)
where d1.d_week_seq = d2.d_week_seq
  and inv_quantity_on_hand < cs_quantity
  and d3.d_date > d1.d_date + interval 5 days
  and hd_buy_potential = '1001-5000'
  and d1.d_year = 1998
  and cd_marital_status = 'M'
group by i_item_desc, w_warehouse_name, d1.d_week_seq
order by total_cnt desc, i_item_desc, w_warehouse_name, d1.d_week_seq
limit 100
"""


@pytest.fixture()
def q72_toy():
    """query72's tables, toy sized, with matches, NULLs, returns and
    promotions."""
    rnd = random.Random(72)
    con = sqlite3.connect(":memory:")
    con.execute("CREATE TABLE catalog_sales (cs_item_sk, cs_order_number, "
                "cs_quantity, cs_bill_cdemo_sk, cs_bill_hdemo_sk, "
                "cs_sold_date_sk, cs_ship_date_sk, cs_promo_sk)")
    con.executemany("INSERT INTO catalog_sales VALUES (?,?,?,?,?,?,?,?)", [
        (rnd.randrange(1, 9), o, rnd.randrange(1, 100), rnd.randrange(1, 5),
         rnd.randrange(1, 4), rnd.randrange(1, 40), rnd.randrange(1, 60),
         rnd.choice([None, 1, 2, 3, 9])) for o in range(300)])
    con.execute("CREATE TABLE inventory (inv_date_sk, inv_item_sk, "
                "inv_warehouse_sk, inv_quantity_on_hand)")
    con.executemany("INSERT INTO inventory VALUES (?,?,?,?)", [
        (d, i, w, rnd.choice([None, rnd.randrange(100)]))
        for d in range(1, 60, 7) for i in range(1, 9) for w in (1, 2)])
    con.execute("CREATE TABLE warehouse (w_warehouse_sk, w_warehouse_name)")
    con.executemany("INSERT INTO warehouse VALUES (?,?)",
                    [(1, "north"), (2, "south")])
    con.execute("CREATE TABLE item (i_item_sk, i_item_desc)")
    con.executemany("INSERT INTO item VALUES (?,?)",
                    [(i, None if i == 3 else f"item {i % 5}")
                     for i in range(1, 9)])
    con.execute("CREATE TABLE customer_demographics (cd_demo_sk, "
                "cd_marital_status)")
    con.executemany("INSERT INTO customer_demographics VALUES (?,?)",
                    [(1, "M"), (2, "S"), (3, "M"), (4, None)])
    con.execute("CREATE TABLE household_demographics (hd_demo_sk, "
                "hd_buy_potential)")
    con.executemany("INSERT INTO household_demographics VALUES (?,?)",
                    [(1, "1001-5000"), (2, "0-500"), (3, "1001-5000")])
    con.execute("CREATE TABLE date_dim (d_date_sk, d_date, d_week_seq, "
                "d_year)")
    con.executemany("INSERT INTO date_dim VALUES (?,?,?,?)", [
        (k, f"1998-{1 + (k - 1) // 28:02d}-{1 + (k - 1) % 28:02d}",
         (k - 1) // 7, 1998) for k in range(1, 61)])
    con.execute("CREATE TABLE promotion (p_promo_sk)")
    con.executemany("INSERT INTO promotion VALUES (?)", [(1,), (2,), (3,)])
    con.execute("CREATE TABLE catalog_returns (cr_item_sk, cr_order_number)")
    con.executemany("INSERT INTO catalog_returns VALUES (?,?)",
                    [(rnd.randrange(1, 9), rnd.randrange(300))
                     for _ in range(60)])
    yield con
    con.close()


def test_g_query72_in_the_forced_order_gives_the_texts_rows(ref, q72_toy):
    """query72's text with departures (a)-(d) alone, which SQLite plans as it
    likes, against the same with its inner joins in ``QUERY72_ORDER``;
    the outer joins' ``ON`` stay, each inner join's moves to the WHERE."""
    decimals = ref.decimal_columns()
    plain = ref.column_days(ref.sqlite_ref.bare_statement(Q72))
    forced = ref.statement(Q72, decimals)
    assert forced == ref.force_join_order(plain)
    head = forced[forced.index("from"):forced.index("left outer join")]
    assert [t.split()[-1] for t in head[len("from "):].split(" cross join ")] \
        == list(ref.QUERY72_ORDER)
    assert " join inventory on" not in forced
    assert "left outer join catalog_returns on (cr_item_sk = cs_item_sk" \
        in forced
    want = q72_toy.execute(plain).fetchall()
    assert len(want) > 3
    assert any(r[4] for r in want) and any(r[3] for r in want)
    assert q72_toy.execute(forced).fetchall() == want


def test_g_leaves_other_texts_as_they_are(ref, monkeypatch):
    comma = "select a from t1, t2 where t1.k = t2.k"
    assert ref.force_join_order(comma) == comma
    other = ("select * from f join g on (f.k = g.k) join d on (g.d = d.sk) "
             "where d.y = 1")
    assert ref.force_join_order(other) == other
    monkeypatch.setattr(ref, "QUERY72_ORDER", ("d", "g", "f"))
    assert ref.force_join_order(other) == (
        "select * from d cross join g cross join f\n"
        "where (f.k = g.k) and (g.d = d.sk) and (d.y = 1)\n")


def test_the_reference_imports_nothing_of_the_program(ref):
    with open(ref.__file__) as f:
        source = f.read()
    assert "nds_tpu" not in source and "import jax" not in source
    for departure in ("(a)", "(b)", "(c)", "(d)", "(e)", "(f)", "(g)"):
        assert departure in ref.__doc__
    probe = (f"import sys\nsys.path.insert(0, {REPO!r})\n"
             "from benchmark import manifest\n"
             f"ref = manifest.load_module({REFERENCE!r}, 'r')\n"
             "ref.statement('select 1 from item where i_current_price "
             "between 1 and 2', ref.decimal_columns())\n"
             "print(sorted(m for m in sys.modules if m.split('.')[0] in "
             "('nds_tpu', 'jax')))\n")
    out = subprocess.run([sys.executable, "-c", probe], capture_output=True,
                         text=True, timeout=120, cwd=REPO)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "[]"
