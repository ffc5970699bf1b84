# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""The comparison that decides ``correct`` and the plain SQLite reference,
on hand-made rows and raw files."""

import importlib.util
import os
from decimal import Decimal

import pytest

from benchmark import compare

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
KINDS = ["int", "str", "cents"]
WANT = [[1998, "brand #1", 792917], [1998, "brand #2", 535721],
        [1999, "brand #1", None]]


def program_rows(price=Decimal):
    return [(1998, "brand #1", price("7929.17")),
            (1998, "brand #2", price("5357.21")), (1999, "brand #1", None)]


def test_exact_decimals_agree():
    one = compare.compare_answer(program_rows(), WANT, KINDS, True)
    assert one == {"rows_off": 0, "decimal_gap_max": 0.0, "rows": 3}


def test_a_float_sum_reads_above_the_limit():
    rows = program_rows()
    rows[0] = (1998, "brand #1", 7929.170000000001)     # a double's sum
    one = compare.compare_answer(rows, WANT, KINDS, True)
    assert one["rows_off"] == 0 and 0 < one["decimal_gap_max"] < 1e-9


@pytest.mark.parametrize("fault", ["altered_key", "altered_cent", "dropped",
                                   "extra", "null_for_value", "swapped"])
def test_an_altered_answer_is_not_correct(fault):
    rows = program_rows()
    if fault == "altered_key":
        rows[1] = (1998, "brand #3", rows[1][2])
    elif fault == "altered_cent":
        rows[1] = (1998, "brand #2", Decimal("5357.22"))
    elif fault == "dropped":
        rows.pop()
    elif fault == "extra":
        rows.append((2000, "brand #9", Decimal("1.00")))
    elif fault == "null_for_value":
        rows[0] = (1998, "brand #1", None)
    elif fault == "swapped":
        rows[0], rows[1] = rows[1], rows[0]
    verdict = compare.compare_all(
        [{"name": "q", "rows": rows}], {"q": WANT},
        {"q": {"result": KINDS, "ordered": True}})
    assert verdict["correct"] is False
    bad = {k for k, (v, lim) in verdict["compared"].items() if v > lim}
    assert bad and bad <= {"rows_off", "decimal_gap_max"}


def test_unordered_statement_compares_as_a_multiset():
    rows = program_rows()
    rows.reverse()
    assert compare.compare_answer(rows, WANT, KINDS, False)["rows_off"] == 0
    assert compare.compare_answer(rows, WANT, KINDS, True)["rows_off"] > 0


def test_an_answer_that_never_came_and_an_empty_window_are_not_correct():
    traffic = {"q": {"result": KINDS, "ordered": True}}
    verdict = compare.compare_all([{"name": "q", "rows": None},
                                   {"name": "q", "rows": program_rows()}],
                                  {"q": WANT}, traffic)
    assert verdict["compared"]["answers_never_came"] == [1, 0]
    assert verdict["correct"] is False
    assert compare.compare_all([], {"q": WANT}, traffic)["correct"] is False
    lines = compare.report_lines(verdict)
    assert any("answers_never_came = 1" in ln and "limit 0" in ln
               for ln in lines)


@pytest.fixture()
def sqlite_ref():
    spec = importlib.util.spec_from_file_location(
        "ref_under_test",
        os.path.join(REPO, "benchmark", "reference", "sqlite_ref.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_reference_imports_nothing_of_the_program(sqlite_ref):
    with open(sqlite_ref.__file__) as f:
        source = f.read()
    assert "nds_tpu" not in source.replace("nds-tpu", "")
    assert "import jax" not in source


def test_reference_answers_exactly_over_raw_files(sqlite_ref, tmp_path):
    """reason / store_returns rows in the generator's ``|`` format with a
    trailing delimiter, NULLs as empty fields, decimals as text."""
    schema = sqlite_ref.load_schema()

    def line(table, **values):
        return "|".join(str(values.get(n, "")) for n, _ in schema[table]) + "|"
    (tmp_path / "reason").mkdir()
    (tmp_path / "reason" / "reason_1_1.dat").write_text("\n".join([
        line("reason", r_reason_sk=1, r_reason_desc="Package was damaged"),
        line("reason", r_reason_sk=2, r_reason_desc="Stopped working")]) + "\n")
    (tmp_path / "store_returns").mkdir()
    (tmp_path / "store_returns" / "store_returns_1_1.dat").write_text(
        "\n".join([
            line("store_returns", sr_item_sk=1, sr_ticket_number=10,
                 sr_reason_sk=1, sr_return_quantity=3, sr_return_amt="0.10"),
            line("store_returns", sr_item_sk=2, sr_ticket_number=10,
                 sr_reason_sk=1, sr_return_quantity=1, sr_return_amt="0.20"),
            line("store_returns", sr_item_sk=3, sr_ticket_number=11,
                 sr_reason_sk=2, sr_return_quantity=5, sr_return_amt="9.99"),
            line("store_returns", sr_item_sk=4, sr_ticket_number=12,
                 sr_reason_sk=1, sr_return_amt="0.30")]) + "\n")
    sql = ("-- start query 1 in stream 0 using template t.tpl\n"
           "select r_reason_desc, count(*), sum(sr_return_amt),\n"
           "       sum(sr_return_quantity * sr_return_amt)\n"
           "from store_returns, reason where sr_reason_sk = r_reason_sk\n"
           "group by r_reason_desc order by r_reason_desc\n;\n"
           "-- end query 1 in stream 0 using template t.tpl\n")
    scans = {"store_returns": ["sr_reason_sk", "sr_return_quantity",
                               "sr_return_amt", "sr_net_loss"],
             "reason": ["r_reason_sk", "r_reason_desc"]}
    got = sqlite_ref.answers(str(tmp_path), {"q": {"sql": sql,
                                                   "scans": scans}})
    # 0.10 + 0.20 + 0.30 is exactly 60 hundredths (0.6000000000000001 as
    # doubles); 3 * 0.10 + 1 * 0.20 + NULL * 0.30 is 50
    assert got == {"q": [["Package was damaged", 3, 60, 50],
                         ["Stopped working", 1, 999, 4995]]}
