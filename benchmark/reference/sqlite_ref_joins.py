# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""The plain reference for statements that join facts: ``sqlite_ref.py``'s
loader, schema and exactness, with what keeps SQLite's plan from going
quadratic where a statement joins two or three facts or probes facts from a
correlated ``EXISTS``.

The semantics are the standing reference's: the stream's own text, run by
stdlib SQLite over the raw generated files, decimals as integer hundredths,
nothing of the program imported. One thing is added, which changes no
predicate, literal or expression: an index on every column of a loaded
table that some statement's text equates with another column (``a = b``),
facts included. The standing file indexes no table past a million rows,
which is right for one scan of one fact and leaves a fact-to-fact join or a
correlated ``EXISTS`` a nested loop over unindexed millions.

``tests/bench_harness/test_multifact_reference.py`` holds it to the standing
file row for row at SF0.01.
"""

from __future__ import annotations

import importlib.util
import os
import re
import sqlite3

HERE = os.path.dirname(os.path.abspath(__file__))


def _standing():
    spec = importlib.util.spec_from_file_location(
        "benchmark_reference_sqlite_ref", os.path.join(HERE, "sqlite_ref.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


sqlite_ref = _standing()

_NAME = r"[A-Za-z_][A-Za-z0-9_]*"
_EQUATED = re.compile(rf"(?:{_NAME}\.)?({_NAME})\s*=\s*(?:{_NAME}\.)?({_NAME})\b")


def equated_columns(sql: str) -> set:
    """Bare names on either side of a ``column = column`` in ``sql``."""
    names: set = set()
    for a, b in _EQUATED.findall(sql):
        names.update((a.lower(), b.lower()))
    return names


def index_equated(con: sqlite3.Connection, statements) -> None:
    """An index on each column of a loaded table that a statement equates
    with another column, where ``sqlite_ref.connect`` made none."""
    equated: set = set()
    for sql in statements:
        equated |= equated_columns(sql)
    tables = [r[0] for r in con.execute(
        "SELECT name FROM sqlite_master WHERE type = 'table'")]
    for table in tables:
        for _cid, column, *_rest in con.execute(
                f'PRAGMA table_info("{table}")').fetchall():
            if column.lower() in equated:
                con.execute(f'CREATE INDEX IF NOT EXISTS "ix_{table}_{column}" '
                            f'ON "{table}" ("{column}")')
    con.execute("ANALYZE")


def answers(raw_dir: str, queries: dict) -> dict:
    """``queries`` = {name: {"sql": stream text, "scans": {table: [cols]}}}
    -> {name: [row, ...]}, as ``sqlite_ref.answers`` gives them."""
    scans: dict = {}
    for q in queries.values():
        for table, cols in q["scans"].items():
            scans.setdefault(table, [])
            scans[table] += [c for c in cols if c not in scans[table]
                             and re.search(rf"\b{re.escape(c)}\b", q["sql"])]
    con = sqlite_ref.connect(raw_dir, scans)
    try:
        index_equated(con, [q["sql"] for q in queries.values()])
        return {name: [list(r) for r in con.execute(
                    sqlite_ref.bare_statement(q["sql"])).fetchall()]
                for name, q in queries.items()}
    finally:
        con.close()
