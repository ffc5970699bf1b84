# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""``resident.probe_rows_per_query`` over a synthetic ``phases`` block: the
mean over the statements of the ``op.join`` phase's ``probeRows`` (the
bucket each join's two binary searches ran at), and nothing (no raise)
where the program's spans state none, as the commits before the
attribute."""

import pytest

from benchmark import manifest

MAN = manifest.Manifest()
METRIC = "resident.probe_rows_per_query"
CELL = "sf1_resident_channels.power_multifact"


def join(probe_rows=None, cells=1000):
    p = {"ms": 9.0, "count": 4, "syncs": 2, "selfMs": 8.0,
         "syncWaitMs": 6.0, "compileMs": 0.0, "rootMs": 0.0, "cells": cells}
    return p if probe_rows is None else dict(p, probeRows=probe_rows)


CASES = [
    # four joins narrowed to 64 Ki + 8 Ki + 16 + 16, two at full width
    ("every_statement", [{"phases": {"op.join": join(65536 + 8192 + 32)}},
                         {"phases": {"op.join": join(2 * 4194304)}}],
     (65536 + 8192 + 32 + 2 * 4194304) / 2),
    # a statement without a general join still counts in the mean
    ("one_statement_without", [{"phases": {"op.join": join(4096)}},
                               {"phases": {"op.semi_join": join()}},
                               {"phases": {}}, {"phases": {}}], 1024.0),
    # the parent's op.join states cells and no probeRows
    ("attribute_absent", [{"phases": {"op.join": join()}},
                          {"phases": {}}], None),
    ("no_records", [], None),
]


@pytest.mark.parametrize("records,want", [c[1:] for c in CASES],
                         ids=[c[0] for c in CASES])
def test_probe_rows_reads_the_rollup_and_nothing_without_it(records, want):
    got = MAN.reader(METRIC)({"records": records})
    assert got is None if want is None else got == pytest.approx(want)


def test_probe_rows_is_listed_with_the_multifact_cell():
    """The cell is IN the metric's list (not pinned: a later cell that joins
    facts is named there by an entry alone), and reports it."""
    entry = next(m for m in MAN.doc["per_layer"] if m["name"] == METRIC)
    assert CELL in entry["workloads"]
    assert {k: entry[k] for k in entry if k not in ("name", "workloads")} == {
        "unit": "rows", "better": "lower", "source": "program_span",
        "layer": "resident execution", "moves": "power_query_ms"}
    assert METRIC in {m["name"] for m in MAN.per_layer(CELL)}
