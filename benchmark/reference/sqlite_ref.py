# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""The plain reference: the stream's own query text run by stdlib SQLite
over the raw generated files.

It imports nothing of the program and reads nothing the program made: the
``.dat`` files come from the data generator, the column names and types from
``tpcds_schema.json`` (the TPC-DS specification's table definitions, kept
beside this file as data), the statement from the generated stream.

Exactness. Every decimal column of TPC-DS has scale 2, so decimals are loaded
as INTEGER hundredths ("cents") and SQLite's integer arithmetic is exact.
That is sound for statements whose decimal results are built from +, -, sum,
min, max and multiplication by an integer -- the traffic file says, per
result column, which kind it is (``int`` | ``str`` | ``cents``), and a
statement that divides or averages decimals needs a reference of its own
(a new file beside this one, named in the traffic entry's ``reference``).

Only the tables and columns a traffic entry lists under ``scans`` are loaded,
and of those the ones named in the statement's text; they are the same lists
the scan-bytes function reads.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sqlite3

HERE = os.path.dirname(os.path.abspath(__file__))
_NULL_INT = -(2 ** 63)
# tables past this many rows are scanned, not indexed: building an index
# on a fact table costs as much as the one scan each statement makes of it
_INDEX_ROWS_MAX = 1_000_000


def load_schema() -> dict:
    with open(os.path.join(HERE, "tpcds_schema.json")) as f:
        return {t: [tuple(c) for c in cols] for t, cols in json.load(f).items()}


def _kind(sql_type: str) -> str:
    if sql_type.startswith("int"):
        return "int"
    if sql_type.startswith("decimal"):
        return "cents"
    return "str"          # char / varchar / date (ISO text sorts as a date)


def _read_columns(path_glob: str, fields, wanted):
    """One table's wanted columns from its ``|``-separated files, as Python
    lists: ints as int, decimals as int hundredths, the rest as str; empty
    fields are NULL."""
    import pyarrow as pa
    import pyarrow.compute as pc
    import pyarrow.csv as pacsv
    names = [n for n, _ in fields] + ["_trailing"]
    types = dict(fields)
    column_types = {}
    for n in wanted:
        k = _kind(types[n])
        column_types[n] = (pa.int64() if k == "int"
                           else pa.float64() if k == "cents" else pa.string())
    parts = []
    for path in sorted(glob.glob(path_glob)):
        if os.path.getsize(path) == 0:
            continue
        parts.append(pacsv.read_csv(
            path,
            read_options=pacsv.ReadOptions(column_names=names,
                                           encoding="iso-8859-1"),
            parse_options=pacsv.ParseOptions(delimiter="|",
                                             quote_char=False),
            convert_options=pacsv.ConvertOptions(
                include_columns=list(wanted), column_types=column_types,
                null_values=[""], strings_can_be_null=True)))
    if not parts:
        raise FileNotFoundError(f"no raw data under {path_glob}")
    table = pa.concat_tables(parts)
    out = []
    for n in wanted:
        col = table.column(n)
        kind = _kind(types[n])
        if kind == "str":
            out.append(col.to_pylist())
            continue
        if kind == "cents":
            # |value| < 1e13 and two decimals: value*100 is within 1e-3 of
            # the integer it stands for, so round() recovers it exactly
            col = pc.cast(pc.round(pc.multiply(col, 100.0)), pa.int64())
        # NULL travels as a sentinel that the INSERT turns back (NULLIF):
        # numpy's tolist() is a hundred times faster than to_pylist()
        out.append(pc.fill_null(col, _NULL_INT).to_numpy().tolist())
    return out


def connect(raw_dir: str, scans: dict) -> sqlite3.Connection:
    """An in-memory database holding ``scans`` = {table: [columns]}."""
    schema = load_schema()
    con = sqlite3.connect(":memory:")
    con.execute("PRAGMA temp_store=MEMORY")
    for table, wanted in sorted(scans.items()):
        fields = schema[table]
        unknown = [c for c in wanted if c not in dict(fields)]
        if unknown:
            raise KeyError(f"{table} has no column(s) {unknown}")
        wanted = [n for n, _ in fields if n in set(wanted)]
        decl = ", ".join(
            f'"{n}" {"TEXT" if _kind(dict(fields)[n]) == "str" else "INTEGER"}'
            for n in wanted)
        con.execute(f'CREATE TABLE "{table}" ({decl})')
        cols = _read_columns(os.path.join(raw_dir, table, "*.dat"),
                             fields, wanted)
        types = dict(fields)
        slots = ", ".join(
            "?" if _kind(types[n]) == "str" else f"NULLIF(?, {_NULL_INT})"
            for n in wanted)
        con.executemany(f'INSERT INTO "{table}" VALUES ({slots})', zip(*cols))
        # surrogate keys are what every TPC-DS join goes through; an index
        # on each keeps SQLite's nested loops out of quadratic territory
        if len(cols[0]) <= _INDEX_ROWS_MAX:
            for n in wanted:
                if n.endswith("_sk"):
                    con.execute(f'CREATE INDEX "ix_{table}_{n}" '
                                f'ON "{table}" ("{n}")')
    con.execute("ANALYZE")
    return con


def bare_statement(text: str) -> str:
    """The statement between the stream's ``-- start`` / ``-- end`` marker
    lines, without its trailing ``;``."""
    lines = [ln for ln in text.splitlines()
             if not ln.strip().startswith("--")]
    sql = "\n".join(lines).strip()
    return sql[:-1] if sql.endswith(";") else sql


def answers(raw_dir: str, queries: dict) -> dict:
    """``queries`` = {name: {"sql": stream text, "scans": {table: [cols]}}}
    -> {name: [row, ...]} with rows as lists in the statement's order."""
    scans: dict = {}
    for q in queries.values():
        for table, cols in q["scans"].items():
            scans.setdefault(table, [])
            scans[table] += [c for c in cols if c not in scans[table]
                             and re.search(rf"\b{re.escape(c)}\b", q["sql"])]
    con = connect(raw_dir, scans)
    try:
        return {name: [list(r) for r in
                       con.execute(bare_statement(q["sql"])).fetchall()]
                for name, q in queries.items()}
    finally:
        con.close()
