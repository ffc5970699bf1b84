# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""The year-over-year customer mix (``power_customer_yoy``) at SF0.01 on the
CPU: each statement through the program against its configuration's
reference (``reference/sqlite_ref_customer_yoy.py``) with ``compare.py`` at
limits 0 on two seeds, query74 under each of its four aggregates; the
reference's real division held to SQLite's own on a toy table where
integer division flips the comparison; the two arms of ``group_ids`` on
many string keys against NumPy; and the cell's rehearsal.

The seeds' data is made by child processes (``datagen.ensure``) into a
temporary directory; the program runs in this process, as the engine's own
tests run it."""

import contextlib
import io
import json
import os
import re
import shutil
import signal
import sqlite3
import subprocess
import sys

import numpy as np
import pyarrow as pa
import pytest

from benchmark import compare, manifest
from benchmark import run as bench_run

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MAN = manifest.Manifest(REPO)
CELL = "sf1_resident_customer_yoy.power_customer_yoy"
CONFIG = MAN.config(MAN.cell(CELL)["config"])
TRAFFIC = MAN.traffic(MAN.cell(CELL)["traffic"])
STATEMENTS = [q["name"] for q in TRAFFIC["queries"]]
SEEDS = [3_420_000_001, 4242]
SCALE = str(CONFIG["rehearsal"]["scale_factor"])
REFERENCE = os.path.join(REPO, "benchmark", CONFIG["reference"])
AGGS = ["max", "sum", "min", "avg"]
_AGG = re.compile(r"\b(max|sum|min|avg)\(((?:ss|ws)_net_paid)\)")


def with_agg(text: str, agg: str) -> str:
    """query74's text with ``agg`` in place of the seed's aggregate, in
    both channels."""
    out, n = _AGG.subn(lambda m: f"{agg}({m.group(2)})", text)
    assert n == 2, "query74 draws its aggregate once for both channels"
    return out


@pytest.fixture(scope="module")
def ref():
    return manifest.load_module(REFERENCE, "ref_customer_yoy")


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    path = tmp_path_factory.mktemp("customer_yoy_cache")
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
    """``datagen.ensure`` in a child process of its own, with a time limit
    (a second attempt starts the seed's entry again)."""
    for _attempt in range(2):
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
    """{seed: the seed's data, the mix in the stream's order, and each case
    (the statement as written, query74 under each aggregate) with the
    reference's rows}."""
    out = {}
    for seed in SEEDS:
        data = make_data(cache, seed)
        names, queries, wanted = bench_run.cell_queries(data["stream"],
                                                        TRAFFIC)
        cases = {("query11", "written"): queries["query11"]}
        for agg in AGGS:
            q = queries["query74"]
            cases[("query74", agg)] = dict(q, sql=with_agg(q["sql"], agg))
        answers = {key: ref.answers(data["raw"], {key[0]: q})[key[0]]
                   for key, q in cases.items()}
        out[seed] = {"data": data, "names": names, "wanted": wanted,
                     "cases": cases, "answers": answers}
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

def test_the_mix_is_query11_then_query74_in_the_streams_order(seeds):
    assert STATEMENTS == ["query11", "query74"]
    for s in seeds.values():
        assert s["names"] == STATEMENTS


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("case", [("query11", "written")]
                         + [("query74", agg) for agg in AGGS],
                         ids=lambda c: "-".join(c))
def test_statement_agrees_with_the_reference_at_limits_0(seeds, sessions,
                                                         case, seed):
    from nds_tpu import power
    s = seeds[seed]
    name = case[0]
    rows = sessions[seed].sql(
        power.strip_stream_markers(s["cases"][case]["sql"])).collect()
    reference = {name: s["answers"][case]}
    verdict = compare.compare_all([{"name": name, "rows": rows}], reference,
                                  s["wanted"])
    assert verdict["correct"] is True, verdict
    assert verdict["compared"] == {"answers_never_came": [0, 0],
                                   "rows_off": [0, 0],
                                   "decimal_gap_max": [0.0, 0]}
    assert verdict["rows"] == len(reference[name])


def test_the_cases_find_customers(seeds):
    """At SF0.01 a year holds few customers of both channels: over both
    seeds each statement keeps some, so the comparisons above compare
    rows and not only empty answers."""
    for name in STATEMENTS:
        assert sum(len(rows) for s in seeds.values()
                   for key, rows in s["answers"].items()
                   if key[0] == name) > 0, name


# -- (2) the reference's real division, on a toy table -------------------------

@pytest.fixture()
def toy():
    con = sqlite3.connect(":memory:")
    con.execute("CREATE TABLE yt (id TEXT, ch TEXT, yr INTEGER, total "
                "INTEGER)")
    # hundredths: web grows 250 -> 625 (2.5x), store 100 -> 240 (2.4x)
    con.executemany("INSERT INTO yt VALUES (?, ?, ?, ?)", [
        ("A", "w", 1, 250), ("A", "w", 2, 625),
        ("A", "s", 1, 100), ("A", "s", 2, 240),
        ("B", "w", 1, 300), ("B", "w", 2, 600),
        ("B", "s", 1, 100), ("B", "s", 2, 300)])
    yield con
    con.close()


TOY = ("select w2.id from yt w1, yt w2, yt s1, yt s2 "
       "where w1.id = w2.id and s1.id = w1.id and s2.id = w1.id "
       "and w1.ch = 'w' and w2.ch = 'w' and s1.ch = 's' and s2.ch = 's' "
       "and w1.yr = 1 and w2.yr = 2 and s1.yr = 1 and s2.yr = 2 "
       "and case when w1.total > 0 then w2.total / w1.total else null end "
       "> case when s1.total > 0 then s2.total / s1.total else null end "
       "order by 1")


def test_departure_a_a_decimal_quotient_is_a_real_quotient(ref, toy):
    rewritten = ref.real_quotients(TOY)
    assert "CAST(w2.total AS REAL) / w1.total" in rewritten
    assert "CAST(s2.total AS REAL) / s1.total" in rewritten
    own = TOY.replace("w2.total / w1.total", "(w2.total * 1.0) / w1.total"
                      ).replace("s2.total / s1.total",
                                "(s2.total * 1.0) / s1.total")
    want = toy.execute(own).fetchall()
    assert want == [("A",)]          # 2.5 > 2.4; B: 2.0 < 3.0
    assert toy.execute(rewritten).fetchall() == want
    # why: SQLite divides integer hundredths as integers, 2 > 2 is false
    assert toy.execute(TOY).fetchall() == []
    # the mean of integer hundredths is a real already
    assert toy.execute("select avg(total) from yt where id = 'A' and "
                       "ch = 'w'").fetchall() == [(437.5,)]


@pytest.mark.parametrize("text", ["select 5 / 2 from yt",
                                  "select total / 2 from yt",
                                  "select sum(total) / count(*) from yt"])
def test_departure_a_refuses_a_division_of_another_form(ref, text):
    with pytest.raises(ValueError):
        ref.real_quotients(text)


def test_departure_b_the_with_body_is_read_once_indexed(ref, seeds):
    """The stream's query74 as the reference asks it: its ``WITH`` body
    is made once as a table, with an index on the customer id it is joined
    on, and the statement reads it by name four times."""
    text = ref.real_quotients(ref.sqlite_ref.bare_statement(
        seeds[SEEDS[0]]["cases"][("query74", "sum")]["sql"]))
    con = sqlite3.connect(":memory:")
    con.execute("CREATE TABLE customer (c_customer_sk INTEGER, "
                "c_customer_id TEXT, c_first_name TEXT, c_last_name TEXT)")
    for fact, key in (("store_sales", "ss"), ("web_sales", "ws")):
        customer = "ss_customer_sk" if key == "ss" else "ws_bill_customer_sk"
        con.execute(f"CREATE TABLE {fact} ({customer} INTEGER, "
                    f"{key}_sold_date_sk INTEGER, {key}_net_paid INTEGER)")
    con.execute("CREATE TABLE date_dim (d_date_sk INTEGER, d_year INTEGER)")
    subqueries = ref.sqlite_ref_subqueries
    rest, bodies = subqueries.sqlite_ref_setops.materialise_with(con, text)
    subqueries.index_bodies(con, bodies, rest)
    assert bodies == ["year_total"]
    assert len(re.findall(r"\byear_total\s+t_[sw]_", rest)) == 4
    indexed = {r[0] for r in con.execute(
        "SELECT name FROM sqlite_temp_master WHERE type = 'index'")}
    assert {"ix_year_total", "ix_year_total_customer_id"} <= indexed
    con.close()


# -- (3) group_ids' two arms on many string keys, against NumPy ----------------

def _string_table(n_keys, values, rows, seed):
    rng = np.random.default_rng(seed)
    cols = {}
    for k in range(n_keys):
        codes = rng.integers(0, values, rows)
        cols[f"k{k}"] = pa.array([None if (i + k) % 11 == 0 else
                                  f"v{k}_{int(c):05d}"
                                  for i, c in enumerate(codes)])
    return pa.table(cols)


@pytest.mark.parametrize("n_keys,values,arm", [(8, 600, "fold"),
                                               (4, 90, "packed")])
def test_group_ids_on_many_string_keys_against_numpy(n_keys, values, arm,
                                                     monkeypatch):
    """Eight string keys of 600 values (11 bits each with the null flag:
    88 bits, past the 62 that pack) take the fold arm; four of 90 (8 bits
    each) take the packed arm once the rows pass the threshold. Either
    way the groups are NumPy's ``unique`` over the stacked keys."""
    from nds_tpu.engine import ops as E
    from nds_tpu.engine.session import Session
    from nds_tpu.obs import trace as obs_trace
    monkeypatch.setenv("NDS_TPU_GROUP_PACK_MIN", "1024")
    rows = 3000
    table = _string_table(n_keys, values, rows, seed=n_keys)
    s = Session()
    s.create_temp_view("t", table)
    t = s.catalog["t"]
    assert t.plen >= 1024
    obs_trace.drain_spans()
    gids, ngroups, _rep, _cap = E.group_ids(
        [t[f"k{k}"] for k in range(n_keys)], n_valid=t.nrows)
    (span,) = [r for r in obs_trace.drain_spans()
               if isinstance(r, obs_trace.SpanRecord)
               and r.name == "op.group_ids"]
    assert span.attrs["arm"] == arm and span.attrs["keys"] == n_keys
    stacked = np.array([[("\0" if v is None else v) for v in
                         table.column(f"k{k}").to_pylist()]
                        for k in range(n_keys)]).T
    _u, inverse = np.unique(stacked, axis=0, return_inverse=True)
    inverse = inverse.reshape(-1)
    got = np.asarray(gids)[:rows]
    assert ngroups == len(_u) == span.attrs["groups"]
    # the same partition: one id of ours for each of NumPy's, and back
    pairs = set(zip(got.tolist(), inverse.tolist()))
    assert len(pairs) == len(set(got.tolist())) == len(_u)
    assert max(got) < ngroups


# -- (4) the cell's rehearsal -----------------------------------------------------

WRAPPER = """
import sys
cache = sys.argv[1]
from benchmark import run
run.CACHE_DIR = cache
sys.exit(run.main(sys.argv[2:]))
"""


def test_the_cells_rehearsal_is_correct(cache, seeds):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", WRAPPER, cache, "--workload", CELL, "--seed",
         str(SEEDS[0]), "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.strip()]
    result = lines[-1]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 2 and result["attempted"] % 2 == 0
    assert result["device"]["platform"] == "cpu"
    setup = [ln for ln in lines if ln.get("event") == "setup"][0]
    assert setup["order"] == STATEMENTS and setup["scale"] == SCALE
    assert setup["env"] == {"NDS_TPU_REPLAY": "off"}
    for _number, (value, limit) in result["compared"].items():
        assert value <= limit
