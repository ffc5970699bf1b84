# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""``resident.gather_cells_per_query`` over a synthetic ``phases`` block:
the mean of the ``op.gather`` phase's ``cells`` over the statements, and
nothing (no raise) where the program's spans state no cells, as the
commits before the attribute."""

import pytest

from benchmark import manifest

MAN = manifest.Manifest()
METRIC = "resident.gather_cells_per_query"


def gather(cells=None):
    p = {"ms": 4.0, "count": 3, "syncs": 0, "selfMs": 4.0,
         "syncWaitMs": 0.0, "compileMs": 0.0, "rootMs": 0.0}
    return p if cells is None else dict(p, cells=cells)


CASES = [
    ("every_statement", [{"phases": {"op.gather": gather(4096 * 11 + 128)}},
                         {"phases": {"op.gather": gather(2048 * 10)}}],
     (4096 * 11 + 128 + 2048 * 10) / 2),
    # a statement that gathered nothing still counts in the mean
    ("one_statement_without", [{"phases": {"op.gather": gather(600)}},
                               {"phases": {"op.sort": gather()}}], 300.0),
    ("attribute_absent", [{"phases": {"op.gather": gather()}},
                          {"phases": {}}], None),
    ("no_records", [], None),
]


@pytest.mark.parametrize("records,want", [c[1:] for c in CASES],
                         ids=[c[0] for c in CASES])
def test_gather_cells_reads_the_rollup_and_nothing_without_it(records, want):
    got = MAN.reader(METRIC)({"records": records})
    assert got is None if want is None else got == pytest.approx(want)


def test_gather_cells_is_listed_with_the_resident_cell():
    entry = next(m for m in MAN.doc["per_layer"] if m["name"] == METRIC)
    assert entry == {
        "name": METRIC, "unit": "cells", "better": "lower",
        "source": "program_span", "layer": "resident execution",
        "moves": "power_query_ms",
        "workloads": ["sf1_resident.power_scan_join"]}
