# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""The plain reference for statements that read inventory:
``sqlite_ref.py``'s schema, reader and exactness (the stream's own text, run
by stdlib SQLite over the raw generated files, decimals as integer
hundredths, nothing of the program imported), with four departures from the
text and three ways of asking faster. Each changes how SQLite is asked,
never what is asked: no predicate, key or expression moves, and
``tests/bench_harness/test_inventory_reference.py`` holds each to the
untouched form on toy tables.

(a) **``cast(<column> as date)`` becomes the column.** Dates are ISO text in
    the raw files and ISO text sorts as a date; SQLite's own ``cast(... as
    date)`` gives the NUMBER of the year. A date LITERAL and its day
    arithmetic become the ISO text they stand for by
    ``sqlite_ref_subqueries.iso_dates`` (by import).

(b) **``<column> +/- interval N days`` becomes ``date(<column>, '+N
    days')``**: SQLite has no ``interval``; ``date()`` returns the ISO text
    of that day.

(c) **A numeric literal compared with a decimal column is written in
    hundredths**, as the column is loaded: ``i_current_price between 0.99 and
    1.49`` becomes ``between 99 and 149``, ``between 68 and 68 + 30`` becomes
    ``between 6800 and 9800``. A bound that is no whole number of
    hundredths, and a decimal column compared with a number in any other
    form, is refused (``ValueError``): the answer would be wrong, not slow.

(d) **``/`` between two names divides as reals** (``1.0 * a / b``), as Spark
    and the program divide; SQLite divides two integers as integers. A
    decimal column over a name that is not one is refused (hundredths over
    units).

Asked faster (the mix loads all 11,745,000 inventory rows at SF1):

(e) **Rows are inserted ``ROWS_PER_INSERT`` to a statement** (``INSERT ...
    VALUES (...), (...), ...``) where ``sqlite_ref.connect`` makes one
    ``executemany`` step a row: the same values in the same order, about 2.4
    times faster.

(f) **The indexes are the ones the statements' plans search** (``INDEXES``)
    and no other: ONE over (``inv_item_sk``, ``inv_date_sk``), which query72
    searches by both at once and query21, 37 and 82 by the item, where
    ``sqlite_ref_joins.index_equated`` would build three over inventory's
    11.7 M rows, one a column, and ``sqlite_ref.connect`` one on every ``_sk``
    column of each table under a million rows.

(g) **A chain of ``JOIN ... ON`` whose inner tables are query72's is read
    in ``QUERY72_ORDER``** (the year's dates, the catalog_sales of those
    dates, its two demographics, the week's dates, then inventory looked up
    by item and date, then the rest), by ``CROSS
    JOIN``, which SQLite never reorders. The inner joins' ``ON`` conditions
    join the ``WHERE`` (for an inner join they are the same filter: an ``ON``
    may name a table that the new order reads later); the outer joins stay
    last, in the text's order, on their own ``ON``. Left to itself SQLite
    starts query72 from catalog_sales whole and did not end in 900 s.
"""

from __future__ import annotations

import decimal
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


sqlite_ref_subqueries = _beside("sqlite_ref_subqueries")
sqlite_ref = sqlite_ref_subqueries.sqlite_ref
_closing = sqlite_ref_subqueries.sqlite_ref_setops._closing

ROWS_PER_INSERT = 256
# (f): (table, columns) of each index, a composite one in its search order
INDEXES = [
    ("inventory", ("inv_item_sk", "inv_date_sk")),
    ("catalog_sales", ("cs_item_sk",)),
    ("catalog_sales", ("cs_sold_date_sk",)),
    ("store_sales", ("ss_item_sk",)),
    ("catalog_returns", ("cr_item_sk", "cr_order_number")),
    ("date_dim", ("d_date_sk",)),
    ("date_dim", ("d_week_seq",)),
    ("item", ("i_item_sk",)),
    ("warehouse", ("w_warehouse_sk",)),
    ("customer_demographics", ("cd_demo_sk",)),
    ("household_demographics", ("hd_demo_sk",)),
    ("promotion", ("p_promo_sk",)),
]
# (g): the order in which a chain of inner joins over exactly these tables
# (aliases where the text gives one) is read: query72's
QUERY72_ORDER = ("d1", "catalog_sales", "household_demographics",
                 "customer_demographics", "d2", "inventory", "warehouse",
                 "item", "d3")

_NAME = r"[A-Za-z_][A-Za-z0-9_]*"
_COLUMN = rf"{_NAME}(?:\.{_NAME})?"
_NUMBER = r"\d+(?:\.\d+)?"
_LITERAL = rf"{_NUMBER}(?:\s*\+\s*{_NUMBER})*"
_COLUMN_AS_DATE = re.compile(rf"\bcast\s*\(\s*({_COLUMN})\s+as\s+date\s*\)",
                             re.I)
_COLUMN_PLUS_DAYS = re.compile(
    rf"(?<![\w.'])({_COLUMN})\s*([+-])\s*interval\s+(\d+)\s+days?\b", re.I)
_DIVISION = re.compile(rf"(?<![\w.])({_COLUMN})\s*/\s*({_COLUMN})(?![\w.(])")
_COMPARED = r"between|<=|>=|<>|!=|=|<|>"


def decimal_columns() -> set:
    """Every column the schema declares a decimal."""
    return {n for fields in sqlite_ref.load_schema().values()
            for n, t in fields if t.startswith("decimal")}


# -- (a), (b): dates of a column ----------------------------------------------

def column_dates(sql: str) -> str:
    """``cast(<column> as date)`` -> ``<column>``."""
    return _COLUMN_AS_DATE.sub(r"\1", sql)


def column_days(sql: str) -> str:
    """``<column> +/- interval N days`` -> ``date(<column>, '+/-N days')``."""
    return _COLUMN_PLUS_DAYS.sub(
        lambda m: f"date({m.group(1)}, '{m.group(2)}{m.group(3)} days')", sql)


# -- (c): literals against a decimal column in hundredths ---------------------

def _hundredths(literal: str) -> str:
    value = sum(decimal.Decimal(p) for p in re.split(r"\s*\+\s*", literal))
    cents = value * 100
    if cents != cents.to_integral_value():
        raise ValueError(f"{literal} is no whole number of hundredths")
    return str(int(cents))


def cents_literals(sql: str, decimals: set) -> str:
    """``<decimal column> between <literal> and <literal>`` with each bound
    (a number, or a sum of numbers) in hundredths. Any other comparison of
    a decimal column with a number is refused."""
    names = "|".join(sorted(map(re.escape, decimals), key=len, reverse=True))
    column = rf"(?<![\w.])(?:{_NAME}\.)?(?:{names})\b"
    between = re.compile(
        rf"({column})(\s+between\s+)({_LITERAL})(\s+and\s+)({_LITERAL})"
        r"(?![\w.])(?!\s*[-+*/])", re.I)
    sql = between.sub(lambda m: m.group(1) + m.group(2) + _hundredths(
        m.group(3)) + m.group(4) + _hundredths(m.group(5)), sql)
    done = {m.start() for m in between.finditer(sql)}
    for m in re.finditer(rf"({column})\s*({_COMPARED})\s*\(?\s*\d", sql, re.I):
        if m.start() not in done:
            raise ValueError(
                f"a decimal column against a number: {m.group(0)}")
    if re.search(rf"\d\s*({_COMPARED})\s*{column}", sql, re.I):
        raise ValueError("a number against a decimal column")
    return sql


# -- (d): a ratio of two names as reals ---------------------------------------

def real_division(sql: str, decimals: set) -> str:
    """``a / b`` (two names) -> ``1.0 * a / b``."""
    def real(m):
        kinds = {n.split(".")[-1].lower() in decimals for n in m.groups()}
        if len(kinds) > 1:
            raise ValueError(f"hundredths over units: {m.group(0)}")
        return f"1.0 * {m.group(1)} / {m.group(2)}"
    return _DIVISION.sub(real, sql)


# -- (g): a chain of inner joins in a forced order ----------------------------

_FIRST = re.compile(rf"\bfrom\s+({_NAME})(?:\s+(?!join\b|left\b)({_NAME}))?"
                    r"\s+(?=join\b)", re.I)
_JOIN = re.compile(rf"\s*(left\s+outer\s+join|join)\s+({_NAME})"
                   rf"(?:\s+(?!on\b)({_NAME}))?\s+on\s*(?=\()", re.I)
_WHERE = re.compile(r"\s*where\b", re.I)
_WHERE_END = re.compile(r"\b(group\s+by|order\s+by|limit)\b", re.I)


def force_join_order(sql: str) -> str:
    """A ``FROM t1 JOIN t2 ON (...) ... [LEFT OUTER JOIN ...] WHERE w`` whose
    inner tables are those of ``QUERY72_ORDER``
    -> ``FROM`` them in that order by ``CROSS JOIN``, the outer joins after,
    ``WHERE (on2) AND ... AND (w)``. Any other text is returned as it is."""
    first = _FIRST.search(sql)
    if first is None:
        return sql
    inner = [(first.group(1), first.group(2) or first.group(1), None)]
    outer = []
    pos = first.end()
    while True:
        m = _JOIN.match(sql, pos)
        if m is None:
            break
        close = _closing(sql, m.end())
        on = sql[m.end() + 1:close]
        item = (m.group(2), m.group(3) or m.group(2), on)
        if m.group(1).lower().startswith("left"):
            outer.append(item)
        elif outer:
            return sql              # an inner join after an outer one
        else:
            inner.append(item)
        pos = close + 1
    where = _WHERE.match(sql, pos)
    names = {name.lower(): (table, name, on) for table, name, on in inner}
    if (where is None or sorted(QUERY72_ORDER) != sorted(names)
            or len(names) != len(inner)):
        return sql
    end = _WHERE_END.search(sql, where.end())
    end = len(sql) if end is None else end.start()
    body = sql[where.end():end]
    if re.search(r"\bselect\b", body, re.I):
        return sql
    tables = " cross join ".join(
        t if t == n else f"{t} {n}"
        for t, n, _ in (names[o] for o in QUERY72_ORDER))
    joins = "".join(f" left outer join {t if t == n else f'{t} {n}'} on ({on})"
                    for t, n, on in outer)
    conditions = [f"({on.strip()})" for _t, _n, on in inner if on is not None]
    return (sql[:first.start()] + f"from {tables}{joins}\nwhere "
            + " and ".join(conditions + [f"({body.strip()})"]) + "\n"
            + sql[end:])


# -- (e), (f): the loader -----------------------------------------------------

def insert_rows(con: sqlite3.Connection, table: str, kinds, cols) -> None:
    """``cols`` (as ``sqlite_ref._read_columns`` gives them) into ``table``,
    ``ROWS_PER_INSERT`` rows a statement; NULL travels as the sentinel."""
    one = "(" + ", ".join("?" if k == "str"
                          else f"NULLIF(?, {sqlite_ref._NULL_INT})"
                          for k in kinds) + ")"
    width, rows = len(kinds), len(cols[0])
    flat = [v for row in zip(*cols) for v in row]
    step = ROWS_PER_INSERT * width
    whole = rows // ROWS_PER_INSERT * step
    insert = f'INSERT INTO "{table}" VALUES '
    if whole:
        con.executemany(insert + ", ".join([one] * ROWS_PER_INSERT),
                        (flat[i:i + step] for i in range(0, whole, step)))
    if whole < len(flat):
        con.execute(insert + ", ".join([one] * (rows % ROWS_PER_INSERT)),
                    flat[whole:])


def connect(raw_dir: str, scans: dict) -> sqlite3.Connection:
    """An in-memory database holding ``scans`` = {table: [columns]}, with
    the ``INDEXES`` whose columns it holds."""
    schema = sqlite_ref.load_schema()
    con = sqlite3.connect(":memory:")
    con.execute("PRAGMA temp_store=MEMORY")
    for table, wanted in sorted(scans.items()):
        fields = schema[table]
        types = dict(fields)
        unknown = [c for c in wanted if c not in types]
        if unknown:
            raise KeyError(f"{table} has no column(s) {unknown}")
        wanted = [n for n, _ in fields if n in set(wanted)]
        kinds = [sqlite_ref._kind(types[n]) for n in wanted]
        decl = ", ".join(f'"{n}" {"TEXT" if k == "str" else "INTEGER"}'
                         for n, k in zip(wanted, kinds))
        con.execute(f'CREATE TABLE "{table}" ({decl})')
        insert_rows(con, table, kinds, sqlite_ref._read_columns(
            os.path.join(raw_dir, table, "*.dat"), fields, wanted))
    for table, columns in INDEXES:
        if set(columns) <= set(scans.get(table, ())):
            con.execute(f'CREATE INDEX "ix_{table}_{"_".join(columns)}" ON '
                        f'"{table}" ({", ".join(columns)})')
    con.execute("ANALYZE")
    return con


# -- the reference ------------------------------------------------------------

def statement(text: str, decimals: set) -> str:
    """One statement of the stream as SQLite is asked it."""
    sql = column_days(column_dates(sqlite_ref.bare_statement(text)))
    sql = sqlite_ref_subqueries.iso_dates(sql)
    sql = real_division(cents_literals(sql, decimals), decimals)
    return force_join_order(sql)


def answers(raw_dir: str, queries: dict) -> dict:
    """``queries`` = {name: {"sql": stream text, "scans": {table: [cols]}}}
    -> {name: [row, ...]}, as ``sqlite_ref.answers`` gives them."""
    scans: dict = {}
    for q in queries.values():
        for table, cols in q["scans"].items():
            scans.setdefault(table, [])
            scans[table] += [c for c in cols if c not in scans[table]
                             and re.search(rf"\b{re.escape(c)}\b", q["sql"])]
    decimals = decimal_columns()
    con = connect(raw_dir, scans)
    try:
        return {name: [list(r) for r in con.execute(
                    statement(q["sql"], decimals)).fetchall()]
                for name, q in queries.items()}
    finally:
        con.close()
