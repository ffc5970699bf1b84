# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Independent-oracle CI gate: the engine vs SQLite on SF0.01 data.

Breaks the round-1 validation circularity (engine-vs-itself): every query
here is checked row-for-row against stdlib SQLite, an engine that shares no
code with ours (VERDICT r1 #8; the reference's analogous gate is CPU-Spark
vs accelerated output, ref: nds/nds_validate.py:48-114). The full curated
list (tools/oracle_validate.py CURATED — 101 of 103 queries; the AST
emitter in tools/sqlite_emit.py expands rollup/grouping sets and stddev
for SQLite, and only the two queries whose SQLite plans exceed the oracle
time budget stay out) runs via ``python tools/oracle_validate.py``; CI
keeps to a subset of the faster ones so the suite stays responsive.
"""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# the CI subset: fast movers from the curated list (tools/oracle_validate.py
# CURATED is the superset), including rollup (q27/q36), stddev-family and
# true-division (q78) queries the AST emitter unlocked
CI_QUERIES = [
    "query3", "query7", "query13", "query15", "query19", "query26",
    "query27", "query36", "query37", "query41", "query42", "query43",
    "query45", "query48", "query50", "query52", "query55", "query62",
    "query68", "query73", "query78", "query84", "query91", "query92",
    "query96",
]


def _load_sqlite_cached(load_sqlite, data_dir):
    """The oracle DB, persisted next to the generated data: the pure-
    Python ``|``-CSV parse + insert + index build over SF0.01 costs ~2
    minutes of the suite on one core, and its input is the immutable
    cached dataset — so build once, ``backup()`` to a file keyed by the
    data marker's mtime, and reopen on later runs. The tests only ever
    SELECT, so a plain file connection is safe."""
    import sqlite3

    db_path = os.path.join(data_dir, "oracle_sqlite.db")
    marker = os.path.join(data_dir, ".complete")
    if os.path.exists(db_path) and os.path.exists(marker) and \
            os.path.getmtime(db_path) >= os.path.getmtime(marker):
        return sqlite3.connect(db_path)
    con = load_sqlite(data_dir)
    tmp = db_path + ".tmp"
    if os.path.exists(tmp):
        os.remove(tmp)
    disk = sqlite3.connect(tmp)
    with disk:
        con.backup(disk)
    disk.close()
    os.replace(tmp, db_path)
    return con


@pytest.fixture(scope="module")
def oracle_setup():
    os.environ.setdefault("NDS_TPU_COMP_CACHE", "force")
    from tools.oracle_validate import load_sqlite
    from tools.coverage_sweep import ensure_data
    from nds_tpu.queries import generate_query_streams
    from nds_tpu.power import gen_sql_from_stream
    from nds_tpu.engine.session import Session
    from nds_tpu.schema import get_schemas

    data_dir = ensure_data()
    stream_dir = os.path.join(REPO, ".bench_cache", "oracle_stream")
    os.makedirs(stream_dir, exist_ok=True)
    stream_file = os.path.join(stream_dir, "query_0.sql")
    if not os.path.exists(stream_file):
        generate_query_streams(stream_dir, streams=1, rngseed=19620718,
                               scale=0.01)
    queries = gen_sql_from_stream(stream_file)
    con = _load_sqlite_cached(load_sqlite, data_dir)
    session = Session()
    for tname, fields in get_schemas(use_decimal=True).items():
        path = os.path.join(data_dir, f"{tname}.dat")
        if os.path.exists(path):
            session.read_raw_view(tname, path, fields)
    return con, session, queries


@pytest.mark.parametrize("qname", CI_QUERIES)
def test_engine_matches_sqlite(oracle_setup, qname):
    from tools.oracle_validate import (engine_date_to_text, execute_oracle,
                                       rows_match)
    con, session, queries = oracle_setup
    sql = queries[qname]
    oracle_rows = execute_oracle(con, sql)
    engine_rows = engine_date_to_text(session.sql(sql).collect(), None)
    ok, why = rows_match(engine_rows, oracle_rows)
    assert ok, f"{qname}: {why}"


# the five Power templates that write ``exists (select * from <fact> ...``:
# (statement, columns its catalog scans keep, host reads at SF0.01). The
# reads are the parent's (PR 33's tree, same data, same session): pruning
# the star's columns adds and removes none.
EXISTS_STAR_QUERIES = [
    ("query10", 29, 4), ("query16", 21, 3), ("query35", 26, 4),
    ("query69", 26, 4), ("query94", 21, 3),
]


@pytest.mark.parametrize("qname,scan_columns,reads", EXISTS_STAR_QUERIES,
                         ids=[q[0] for q in EXISTS_STAR_QUERIES])
def test_exists_star_statements_match_sqlite_on_pruned_scans(
        oracle_setup, qname, scan_columns, reads):
    """A star under EXISTS names no columns (query10 / 35 / 69: the
    equality arm over three facts x date_dim; query16 / 94: the residual
    arm over the outer scan's own fact): the rows are SQLite's, the
    ``plan`` span states the columns the scans kept (query10: 29, of 189
    before the rule), and the host reads are unchanged."""
    from nds_tpu.engine import ops as E
    from nds_tpu.obs import export as obs_export
    from nds_tpu.obs import trace as obs_trace
    from tools.oracle_validate import (engine_date_to_text, execute_oracle,
                                       rows_match)
    con, session, queries = oracle_setup
    sql = queries[qname]
    E.resolve_counts()
    obs_trace.drain_spans()
    before = E.sync_count()
    engine_rows = engine_date_to_text(session.sql(sql).collect(), None)
    got_reads = E.sync_count() - before
    phases = obs_export.rollup(obs_trace.drain_spans())["phases"]
    ok, why = rows_match(engine_rows, execute_oracle(con, sql))
    assert ok, f"{qname}: {why}"
    assert phases["plan"]["scanColumns"] == scan_columns
    assert got_reads == reads
