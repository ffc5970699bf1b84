# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""The comparison that decides ``correct``: the rows the timed path fetched
against the plain reference's rows, answer by answer.

Every execution of the window is compared (a statement's rows are at most
its LIMIT). The configurations state exact decimals, so the comparison is
exact and every limit is 0:

``answers_never_came``  executions that raised or returned no rows object
``rows_off``            rows missing, extra, or differing in a column that
                        is not a decimal (position by position where the
                        statement's ORDER BY is total, else as a multiset)
``decimal_gap_max``     the widest |program - reference| in a decimal
                        column, in the column's own unit; the reference
                        holds hundredths as integers, the program's value is
                        taken digit for digit (a float by its repr), so a
                        sum made in floating point reads above 0

A traffic entry gives each statement's ``result`` (one kind per column:
``int`` | ``str`` | ``cents``) and whether its order is total (``ordered``).
"""

from __future__ import annotations

import datetime
from decimal import Decimal

LIMITS = {"answers_never_came": 0, "rows_off": 0, "decimal_gap_max": 0}


def _plain(v):
    """A program value as int / str / None (non-decimal columns)."""
    if v is None or isinstance(v, (str, bool)):
        return v
    if isinstance(v, (datetime.date, datetime.datetime)):
        return v.isoformat()
    if isinstance(v, Decimal):
        return int(v) if v == v.to_integral_value() else str(v)
    if isinstance(v, float):
        return int(v) if v.is_integer() else repr(v)
    try:
        return int(v)
    except (TypeError, ValueError):
        return str(v)


def _hundredths(v) -> Decimal | None:
    """A program value of a decimal column, in hundredths, digit for digit."""
    if v is None:
        return None
    if isinstance(v, float):
        return Decimal(repr(v)) * 100
    return Decimal(str(v)) * 100


def _sort_key(row):
    return tuple((v is not None, str(type(v).__name__), v) if v is not None
                 else (False, "", 0) for v in row)


def compare_answer(got_rows, want_rows, kinds, ordered: bool) -> dict:
    """One execution's rows against the reference's. Returns
    {"rows_off": n, "decimal_gap_max": x, "rows": n}."""
    plain_ix = [i for i, k in enumerate(kinds) if k != "cents"]
    cents_ix = [i for i, k in enumerate(kinds) if k == "cents"]
    got = []
    for row in got_rows:
        if len(row) != len(kinds):
            return {"rows_off": max(len(got_rows), len(want_rows), 1),
                    "decimal_gap_max": 0.0, "rows": len(want_rows)}
        got.append((tuple(_plain(row[i]) for i in plain_ix),
                    tuple(_hundredths(row[i]) for i in cents_ix)))
    want = [(tuple(r[i] for i in plain_ix),
             tuple(None if r[i] is None else Decimal(r[i]) for i in cents_ix))
            for r in want_rows]
    if not ordered:
        def key(pair):
            return _sort_key(pair[0]) + _sort_key(
                tuple(None if c is None else float(c) for c in pair[1]))
        got, want = sorted(got, key=key), sorted(want, key=key)
    rows_off = abs(len(got) - len(want))
    gap = Decimal(0)
    for (g_plain, g_cents), (w_plain, w_cents) in zip(got, want):
        if g_plain != w_plain:
            rows_off += 1
            continue
        for g, w in zip(g_cents, w_cents):
            if (g is None) != (w is None):
                rows_off += 1
                break
            if g is not None:
                gap = max(gap, abs(g - w) / 100)
    return {"rows_off": rows_off, "decimal_gap_max": float(gap),
            "rows": len(want)}


def compare_all(executions, reference: dict, traffic_queries: dict) -> dict:
    """``executions`` = [{"name", "rows" (list | None)}]; ``reference`` =
    {name: rows}; ``traffic_queries`` = {name: traffic entry}. Returns
    {"correct", "compared": {number: [value, limit]}, "answers", "rows"}."""
    never = rows_off = rows = answers = 0
    gap = 0.0
    worst = None
    for ex in executions:
        if ex.get("rows") is None:
            never += 1
            continue
        entry = traffic_queries[ex["name"]]
        one = compare_answer(ex["rows"], reference[ex["name"]],
                             entry["result"], entry.get("ordered", True))
        answers += 1
        rows += one["rows"]
        rows_off += one["rows_off"]
        if one["rows_off"] or one["decimal_gap_max"] > gap:
            worst = ex["name"]
        gap = max(gap, one["decimal_gap_max"])
    values = {"answers_never_came": never, "rows_off": rows_off,
              "decimal_gap_max": gap}
    compared = {k: [values[k], LIMITS[k]] for k in LIMITS}
    correct = answers > 0 and all(v <= lim for v, lim in compared.values())
    return {"correct": correct, "compared": compared, "answers": answers,
            "rows": rows, "worst": worst}


def report_lines(result: dict) -> list:
    """The numbers compared, each beside its limit, as text lines."""
    head = (f"compared {result['answers']} answers, {result['rows']} "
            f"reference rows; worst: {result['worst']}")
    return [head] + [f"  {k} = {v!r}  limit {lim!r}"
                     for k, (v, lim) in result["compared"].items()]
