# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""The plain reference for the year-over-year customer reports (query11,
query74): a ``WITH`` over two sales channels grouped by customer and year,
read four times and self-joined on the customer id, kept where one
channel's growth ratio beats the other's. ``sqlite_ref.py``'s loader, schema
and exactness (the stream's own text, run by stdlib SQLite over the raw
generated files, decimals as integer hundredths, nothing of the program
imported), with two departures from the text. Each changes how SQLite is
asked, never what is asked, and
``tests/bench_harness/test_customer_yoy_reference.py`` holds each to the
untouched form on toy tables.

(a) **A decimal quotient is a real quotient.** ``a.year_total /
    b.year_total`` becomes ``CAST(a.year_total AS REAL) / b.year_total``.
    Decimals are integer hundredths here, and SQLite divides two integers
    as integers: 250 / 100 would read 2, and a growth of 2.5 against one of
    2.4 would compare as equal. The hundredths cancel in a quotient of two
    sums, two minima or two maxima; an average of hundredths is already a
    real (SQLite's ``avg``), the mean of the hundredths, whose scale cancels
    as well. Any other ``/`` is refused.

(b) **The ``WITH`` body is materialised as an indexed temporary table
    before the statement runs, with one index a column that the statement
    equates** (``sqlite_ref_subqueries.answer``, by import). Inline, SQLite
    evaluates the body once for each of its four readers and probes it
    unindexed.

What stays inexact, and why it does not show. The program divides in
``f64`` on a chip that emulates ``f64``; this reference divides IEEE
doubles. The results are three or four strings a row, and a quotient
decides only which customers are kept: the two can disagree only for a
customer whose two growth ratios lie within the emulation's rounding of
each other (a relative 1e-9 is far wider than it). ``query74``'s ``avg``
is the program's decimal average against the reference's exact mean of
hundredths, which moves a ratio by less than a relative 1e-6: the same
holds there at that width. ``compare.py`` holds the strings to limits 0.
"""

from __future__ import annotations

import importlib.util
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))


def _beside(name: str):
    spec = importlib.util.spec_from_file_location(
        f"benchmark_reference_{name}", os.path.join(HERE, f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


sqlite_ref_subqueries = _beside("sqlite_ref_subqueries")
sqlite_ref_joins = sqlite_ref_subqueries.sqlite_ref_joins
sqlite_ref = sqlite_ref_subqueries.sqlite_ref

_NAME = r"[A-Za-z_][A-Za-z0-9_]*"
_QUOTIENT = re.compile(rf"({_NAME}\.{_NAME})\s*/\s*(?={_NAME}\.{_NAME})")


# -- (a) a decimal quotient as a real quotient -------------------------------------

def real_quotients(sql: str) -> str:
    """``a.x / b.y`` -> ``CAST(a.x AS REAL) / b.y``; a ``/`` of any other
    form is refused, since SQLite would divide it as integers."""
    sql = _QUOTIENT.sub(lambda m: f"CAST({m.group(1)} AS REAL) / ", sql)
    if re.search(r"(?<!AS REAL\) )/", sql):
        raise ValueError("a division this reference cannot read")
    return sql


# -- the reference -------------------------------------------------------------------

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
        return {name: sqlite_ref_subqueries.answer(
                    con, real_quotients(sqlite_ref.bare_statement(q["sql"])))
                for name, q in queries.items()}
    finally:
        con.close()
