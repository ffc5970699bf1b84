# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""``plan.scan_columns_per_query`` over a synthetic ``phases`` block: the
mean over the statements of the ``plan`` phase's ``scanColumns`` (the
columns the statement's catalog scans kept after the projection
pushdown), and nothing (no raise) where the program's spans state none, as
the commits before the attribute."""

import pytest

from benchmark import manifest

MAN = manifest.Manifest()
METRIC = "plan.scan_columns_per_query"
CELLS = ["sf1_resident.power_scan_join", "sf1_streamed.power_scan_join",
         "sf1_resident_channels.power_multifact",
         "sf1_resident_setops.power_setop_outer"]


def plan(scan_columns=None):
    p = {"ms": 900.0, "count": 1, "syncs": 4, "selfMs": 7.0,
         "syncWaitMs": 0.0, "compileMs": 0.0, "rootMs": 0.0}
    return p if scan_columns is None else dict(p, scanColumns=scan_columns)


CASES = [
    # query10 with its stars under EXISTS pruned, beside a chain join
    ("every_statement", [{"phases": {"plan": plan(29)}},
                         {"phases": {"plan": plan(37)}}], 33.0),
    # a replayed statement opens no plan span: it still counts in the mean
    ("one_statement_without", [{"phases": {"plan": plan(189)}},
                               {"phases": {"replay.drive": plan()}},
                               {"phases": {"plan": plan()}},
                               {"phases": {}}], 47.25),
    # the parent's plan span states no scanColumns
    ("attribute_absent", [{"phases": {"plan": plan()}},
                          {"phases": {}}], None),
    ("no_records", [], None),
]


@pytest.mark.parametrize("records,want", [c[1:] for c in CASES],
                         ids=[c[0] for c in CASES])
def test_scan_columns_reads_the_rollup_and_nothing_without_it(records, want):
    got = MAN.reader(METRIC)({"records": records})
    assert got is None if want is None else got == pytest.approx(want)


def test_scan_columns_entry_fields():
    entry = next(m for m in MAN.doc["per_layer"] if m["name"] == METRIC)
    assert {k: entry[k] for k in entry if k not in ("name", "workloads")} == {
        "unit": "columns", "better": "lower", "source": "program_span",
        "layer": "plan", "moves": "power_query_ms"}


@pytest.mark.parametrize("cell", CELLS)
def test_scan_columns_is_listed_with_every_cell(cell):
    """Every statement of every cell is planned: each cell is IN the
    metric's list (not pinned: a later cell is named there by an entry
    alone), and reports it."""
    entry = next(m for m in MAN.doc["per_layer"] if m["name"] == METRIC)
    assert cell in entry["workloads"]
    assert METRIC in {m["name"] for m in MAN.per_layer(cell)}
