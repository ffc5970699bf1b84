# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""The plain reference for statements that put a subquery inside a predicate
(a correlated ``EXISTS`` with a non-equality residual, ``NOT EXISTS``, ``IN``
over a self-joined ``WITH``, a correlated scalar aggregate):
``sqlite_ref.py``'s loader, schema and exactness (the stream's own text, run
by stdlib SQLite over the raw generated files, decimals as integer
hundredths, nothing of the program imported), with three departures from the
text. Each changes how SQLite is asked, never what is asked: no predicate,
key or expression moves, and
``tests/bench_harness/test_subquery_reference.py`` holds each to the
untouched form on toy tables.

(a) **A date literal and its day arithmetic are written as the ISO text they
    stand for.** ``cast('1999-02-01' as date)`` becomes ``'1999-02-01'`` and
    ``cast('1999-02-01' as date) + interval 60 days`` (parenthesised or not)
    becomes ``'1999-04-02'``. Dates are ISO text in the raw files and ISO
    text sorts as a date; SQLite has no ``interval``, and its ``cast(... as
    date)`` gives the NUMBER 1999.

(b) **``WITH`` bodies are materialised as indexed temporary tables before the
    statement runs** (``sqlite_ref_setops.materialise_with``, PR 33's rule,
    by import): the rows are the same rows, and the statement then reads
    them by name. Inline, SQLite evaluates a body once for each of its
    readers and probes it unindexed.

(c) **An index on each column that the text equates with another column**,
    facts and the materialised bodies included (``sqlite_ref_joins``' rule
    for loaded tables, by import; one index a column on a body, since a
    correlated subquery looks a body up by ONE of the columns the statement
    joins it on). Without it a correlated ``EXISTS`` over a fact of 719 k
    rows is a nested loop.

What stays inexact, and why it does not show. ``1.2 * avg(<decimal>)`` and
``1.3 * avg(<decimal>)`` stand only INSIDE a comparison against a decimal of
the same scale: SQLite compares integer hundredths with a double here, the
program compares in its own arithmetic, and the two can disagree only where
the scaled average lies within about 1e-6 of a hundredth that some row holds
exactly (a chance of about 2e-4 a group, a dozen groups a statement). No
statement of the mix RETURNS an average or a ratio: the results are counts,
identifiers and sums of hundredths, which ``compare.py`` holds to limits 0.
"""

from __future__ import annotations

import datetime
import importlib.util
import os
import re
import sqlite3

HERE = os.path.dirname(os.path.abspath(__file__))


def _beside(name: str):
    spec = importlib.util.spec_from_file_location(
        f"benchmark_reference_{name}", os.path.join(HERE, f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


sqlite_ref_joins = _beside("sqlite_ref_joins")
sqlite_ref_setops = _beside("sqlite_ref_setops")
sqlite_ref = sqlite_ref_setops.sqlite_ref

_DATE = r"cast\s*\(\s*'(\d{4}-\d{2}-\d{2})'\s+as\s+date\s*\)"
_DATE_PLUS_DAYS = re.compile(
    rf"{_DATE}\s*([+-])\s*interval\s+(\d+)\s+days?\b", re.I)
_DATE_ALONE = re.compile(_DATE, re.I)


# -- (a) date literals and day arithmetic as ISO text ------------------------------

def iso_dates(sql: str) -> str:
    """``cast('D' as date) +/- interval N days`` -> the ISO text of that
    day; then every ``cast('D' as date)`` left -> ``'D'``. An ``interval``
    that survives is refused: it would reach SQLite as a column name."""
    def shifted(m):
        day = datetime.date.fromisoformat(m.group(1))
        days = int(m.group(3)) * (1 if m.group(2) == "+" else -1)
        return f"'{(day + datetime.timedelta(days=days)).isoformat()}'"
    sql = _DATE_ALONE.sub(lambda m: f"'{m.group(1)}'",
                          _DATE_PLUS_DAYS.sub(shifted, sql))
    if re.search(r"\binterval\b", sql, re.I):
        raise ValueError("an interval this reference cannot read")
    return sql


# -- (c) one index a column that the text equates ----------------------------------

def index_bodies(con: sqlite3.Connection, bodies, sql: str) -> None:
    """On each materialised ``WITH`` body, one index a column that ``sql``
    equates with another column (``materialise_with`` made ONE over all of
    them, which serves a lookup by its first column alone)."""
    equated = sqlite_ref_joins.equated_columns(sql)
    for name in bodies:
        for _cid, column, *_rest in con.execute(
                f'PRAGMA table_info("{name}")').fetchall():
            if column.lower() in equated:
                con.execute(f'CREATE INDEX "ix_{name}_{column}" '
                            f'ON "{name}" ("{column}")')


# -- the reference -------------------------------------------------------------------

def answer(con: sqlite3.Connection, text: str) -> list:
    """One statement of the stream -> its rows, as lists."""
    sql, bodies = sqlite_ref_setops.materialise_with(
        con, iso_dates(sqlite_ref.bare_statement(text)))
    try:
        index_bodies(con, bodies, sql)
        return [list(r) for r in con.execute(sql).fetchall()]
    finally:
        for name in bodies:
            con.execute(f'DROP TABLE "{name}"')


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
        sqlite_ref_joins.index_equated(
            con, [q["sql"] for q in queries.values()])
        return {name: answer(con, q["sql"]) for name, q in queries.items()}
    finally:
        con.close()
