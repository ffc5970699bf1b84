# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""The plain reference for statements that intersect, subtract, full-outer-
join and rank over a ROLLUP: ``sqlite_ref.py``'s loader, schema and
exactness (the stream's own text, run by stdlib SQLite over the raw
generated files, decimals as integer hundredths, nothing of the program
imported), with three departures from the text. Each changes how SQLite is
asked, never what is asked: no predicate, literal, key or expression moves,
and ``tests/bench_harness/test_setops_reference.py`` holds each to the
untouched form on toy tables.

(a) **Parentheses around the operands of a compound select are stripped.**
    SQLite refuses ``(select ...) except (select ...)`` ("near except:
    syntax error"), which is how TPC-DS template 87 is written; template 38
    writes the same three operands bare. Only the pair of parentheses goes;
    an operand that carries an ORDER BY or LIMIT of its own is refused, for
    there the parentheses mean something.

(b) **``WITH`` bodies and the operands of INTERSECT / EXCEPT are materialised
    as indexed temporary tables before the statement runs.** A ``WITH`` body
    becomes ``CREATE TEMP TABLE <its name> AS <its body>`` with one index
    over the columns that the rest of the statement equates with another
    column; an operand becomes a temporary table with one index over all of
    its columns. Inline, SQLite evaluates the full outer join of two grouped
    facts as a nested loop over unindexed subqueries. The rows are the same
    rows; the statement then reads them by name.

(c) **``GROUP BY ROLLUP(a, b, ...)`` with ``grouping()`` is expanded** into
    what the SQL standard defines it as: the UNION ALL of the groupings
    ``(a, b)``, ``(a)``, ``()``, a rolled-up column read as NULL and its
    ``grouping()`` as 1. Each aggregate call of the select list and ORDER BY
    is computed once per grouping in an inner select, and the outer select
    evaluates the text's own select list (window functions included) over
    the union. SQLite has neither ROLLUP nor ``grouping()``.
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
_SET_OPERATOR = re.compile(r"\b(union\s+all|union|intersect|except)\b", re.I)
_AGGREGATE = re.compile(r"\b(sum|count|min|max|avg)\s*\(", re.I)
_ROLLUP = re.compile(r"\bgroup\s+by\s+rollup\s*\(", re.I)
_GROUPING = re.compile(r"\bgrouping\s*\(\s*([^()]+?)\s*\)", re.I)


# -- reading the text by its parentheses ----------------------------------------

def _closing(sql: str, opening: int) -> int:
    """Index of the ``)`` that closes the ``(`` at ``opening``; quoted
    strings are skipped."""
    depth, i = 0, opening
    while i < len(sql):
        ch = sql[i]
        if ch == "'":
            i = sql.index("'", i + 1)
        elif ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return i
        i += 1
    raise ValueError("unbalanced parentheses")


def _top_level(sql: str) -> str:
    """``sql`` with everything inside parentheses or quotes blanked out,
    character for character: positions found in it hold in ``sql``."""
    out, i = list(sql), 0
    while i < len(sql):
        if sql[i] == "'":
            j = sql.index("'", i + 1)
        elif sql[i] == "(":
            j = _closing(sql, i)
        else:
            i += 1
            continue
        out[i + 1:j] = " " * (j - i - 1)
        i = j + 1
    return "".join(out)


def _split_top_level(sql: str) -> list:
    """``sql`` cut at its top-level commas."""
    flat = _top_level(sql)
    cuts = [i for i, ch in enumerate(flat) if ch == ","]
    return [sql[a + 1:b].strip() for a, b in zip([-1] + cuts,
                                                 cuts + [len(sql)])]


def compound_operands(sql: str):
    """(operands, operators) of a compound select at the top level of
    ``sql``; one operand and no operator where it is a simple select."""
    flat = _top_level(sql)
    cuts = [(m.start(), m.end(), " ".join(m.group(1).lower().split()))
            for m in _SET_OPERATOR.finditer(flat)]
    starts = [0] + [end for _s, end, _op in cuts]
    ends = [s for s, _end, _op in cuts] + [len(sql)]
    return ([sql[a:b].strip() for a, b in zip(starts, ends)],
            [op for _s, _end, op in cuts])


# -- (a) parentheses around a compound select's operands ------------------------

def strip_operand_parentheses(sql: str) -> str:
    """``(select ...) except (select ...)`` -> ``select ... except select
    ...``, at every depth. Nothing else changes."""
    i = 0
    while i < len(sql):
        if sql[i] == "'":
            i = sql.index("'", i + 1) + 1
            continue
        if sql[i] != "(" or not re.match(r"\(\s*select\b", sql[i:], re.I):
            i += 1
            continue
        j = _closing(sql, i)
        lead = sql[:i].rstrip()
        before = re.search(r"\b(union\s+all|union|intersect|except)$", lead,
                           re.I)
        after = re.match(r"\s*(union|intersect|except)\b", sql[j + 1:], re.I)
        # an operand stands first in its group or right after an operator;
        # `x in (select ...) union ...` keeps its parentheses
        if not (before or (after and (not lead or lead.endswith("(")))):
            i += 1
            continue
        operand = sql[i + 1:j]
        if re.search(r"\b(order\s+by|limit)\b", _top_level(operand), re.I):
            raise ValueError("a parenthesised operand with an ORDER BY or "
                             "LIMIT of its own keeps its parentheses")
        sql = sql[:i] + " " + operand + " " + sql[j + 1:]
    return sql


# -- (c) GROUP BY ROLLUP and grouping() ------------------------------------------

def expand_rollup(sql: str) -> str:
    """A simple select with ``group by rollup(a, b, ...)`` -> the outer
    select of its own select list over the UNION ALL of its groupings.
    ``sql`` comes back as it is where it has no ROLLUP."""
    m = _ROLLUP.search(_top_level(sql))
    if not m:
        if _ROLLUP.search(sql):
            raise ValueError("ROLLUP below the statement's top level")
        return sql
    close = _closing(sql, m.end() - 1)
    columns = _split_top_level(sql[m.end():close])
    head, tail = sql[:m.start()], sql[close + 1:]
    flat = _top_level(head)
    select = re.match(r"\s*select\b", flat, re.I)
    source = re.search(r"\bfrom\b", flat, re.I)
    if not select or not source:
        raise ValueError("ROLLUP outside a simple select")
    items = head[select.end():source.start()]
    aggregates: list = []

    def outer(text: str) -> str:
        """``text`` with each aggregate call (not a window's own function)
        read from the inner select's column and each grouping() from its
        flag."""
        out, i = "", 0
        for call in _AGGREGATE.finditer(text):
            if call.start() < i:
                continue                  # inside a call already replaced
            end = _closing(text, call.end() - 1) + 1
            if re.match(r"\s*over\b", text[end:], re.I):
                continue
            body = " ".join(text[call.start():end].split())
            if body not in aggregates:
                aggregates.append(body)
            out += text[i:call.start()] + f"_agg{aggregates.index(body)}"
            i = end
        out += text[i:]
        return _GROUPING.sub(
            lambda g: f"_grouping{columns.index(g.group(1))}", out)

    items_out, tail_out = outer(items), outer(tail)
    bare = [c.split(".")[-1] for c in columns]
    levels = []
    for kept in range(len(columns), -1, -1):
        cols = [f"{c} as {b}" if i < kept else f"NULL as {b}"
                for i, (c, b) in enumerate(zip(columns, bare))]
        flags = [f"{int(i >= kept)} as _grouping{i}"
                 for i in range(len(columns))]
        aggs = [f"{a} as _agg{i}" for i, a in enumerate(aggregates)]
        group_by = f" group by {', '.join(columns[:kept])}" if kept else ""
        levels.append(f"select {', '.join(aggs + cols + flags)} "
                      f"{head[source.start():]}{group_by}")
    return (f"select {items_out} from ({' union all '.join(levels)}) "
            f"{tail_out}")


# -- (b) WITH bodies and set operands as indexed temporary tables ----------------

def equated_columns(sql: str) -> set:
    """Bare names on either side of a ``column = column`` in ``sql``."""
    names: set = set()
    for a, b in _EQUATED.findall(sql):
        names.update((a.lower(), b.lower()))
    return names


def _temp_table(con: sqlite3.Connection, name: str, body: str,
                indexed=None) -> None:
    """``body``'s rows as the temporary table ``name``, with one index over
    its columns (those of ``indexed`` where given, in the table's order)."""
    con.execute(f'CREATE TEMP TABLE "{name}" AS {body}')
    columns = [r[1] for r in con.execute(f'PRAGMA table_info("{name}")')]
    if indexed is not None:
        columns = [c for c in columns if c.lower() in indexed]
    if columns:
        con.execute(f'CREATE INDEX "ix_{name}" ON "{name}" ('
                    + ", ".join(f'"{c}"' for c in columns) + ")")


def materialise_with(con: sqlite3.Connection, sql: str) -> tuple:
    """``with a as (...), b as (...) <statement>`` -> (``<statement>``,
    [a, b]) with a and b made as temporary tables, in the text's order (a
    later body may read an earlier one)."""
    head = re.match(r"\s*with\b", sql, re.I)
    if not head:
        return sql, []
    made, i = [], head.end()
    while True:
        m = re.match(rf"\s*({_NAME})\s+as\s*\(", sql[i:], re.I)
        if not m:
            raise ValueError("a WITH body this reference cannot read")
        opening = i + m.end() - 1
        close = _closing(sql, opening)
        made.append((m.group(1), sql[opening + 1:close]))
        i = close + 1
        comma = re.match(r"\s*,", sql[i:])
        if not comma:
            break
        i += comma.end()
    rest = sql[i:]
    for name, body in made:
        _temp_table(con, name, body, indexed=equated_columns(rest))
    return rest, [name for name, _ in made]


def materialise_operands(con: sqlite3.Connection, sql: str) -> tuple:
    """Every INTERSECT / EXCEPT of ``sql``, at any depth, with each operand
    read from a temporary table made of it first -> (sql, [tables])."""
    made: list = []

    def rewrite(text: str) -> str:
        out, i = "", 0
        while i < len(text):
            if text[i] == "'":
                j = text.index("'", i + 1)
                out, i = out + text[i:j + 1], j + 1
            elif text[i] == "(":
                j = _closing(text, i)
                out, i = out + "(" + rewrite(text[i + 1:j]) + ")", j + 1
            else:
                out, i = out + text[i], i + 1
        operands, operators = compound_operands(out)
        if not set(operators) & {"intersect", "except"}:
            return out
        parts = []
        for operand in operands:
            name = f"_operand{len(made)}"
            _temp_table(con, name, operand)
            made.append(name)
            parts.append(f'select * from "{name}"')
        joined = parts[0]
        for op, part in zip(operators, parts[1:]):
            joined += f" {op} {part}"
        return joined

    return rewrite(sql), made


# -- the reference ----------------------------------------------------------------

def answer(con: sqlite3.Connection, text: str) -> list:
    """One statement of the stream -> its rows, as lists."""
    sql = expand_rollup(strip_operand_parentheses(
        sqlite_ref.bare_statement(text)))
    sql, bodies = materialise_with(con, sql)
    sql, operands = materialise_operands(con, sql)
    try:
        return [list(r) for r in con.execute(sql).fetchall()]
    finally:
        for name in bodies + operands:
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
        return {name: answer(con, q["sql"]) for name, q in queries.items()}
    finally:
        con.close()
