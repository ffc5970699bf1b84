# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""``resident.deferred_gather_arrays_per_query`` over a synthetic ``phases``
block: the mean over the statements of the ``op.gather`` phase's
``deferredArrays`` (the columns' arrays a row gather read through a composed
index, never gathered at the width of the PK-gather join that brought them),
and nothing (no raise) where the program's spans state none, as the commits
before the attribute."""

import pytest

from benchmark import manifest

MAN = manifest.Manifest()
METRIC = "resident.deferred_gather_arrays_per_query"
CELLS = ["sf1_resident.power_scan_join",
         "sf1_resident_channels.power_multifact"]


def gather(deferred=None, cells=1000):
    p = {"ms": 9.0, "count": 4, "syncs": 0, "selfMs": 8.0,
         "syncWaitMs": 0.0, "compileMs": 0.0, "rootMs": 0.0, "cells": cells}
    return p if deferred is None else dict(p, deferredArrays=deferred)


CASES = [
    # the star mix: 10, 10, 6 and 7 arrays by statement
    ("every_statement", [{"phases": {"op.gather": gather(n)}}
                         for n in (10, 10, 6, 7)], 8.25),
    # a statement whose gathers composed nothing still counts in the mean
    ("one_statement_without", [{"phases": {"op.gather": gather(56)}},
                               {"phases": {"op.gather": gather()}},
                               {"phases": {"op.join": gather()}},
                               {"phases": {}}], 14.0),
    # the parent's op.gather states cells and no deferredArrays
    ("attribute_absent", [{"phases": {"op.gather": gather()}},
                          {"phases": {}}], None),
    ("no_records", [], None),
]


@pytest.mark.parametrize("records,want", [c[1:] for c in CASES],
                         ids=[c[0] for c in CASES])
def test_deferred_arrays_reads_the_rollup_and_nothing_without_it(records,
                                                                 want):
    got = MAN.reader(METRIC)({"records": records})
    assert got is None if want is None else got == pytest.approx(want)


@pytest.mark.parametrize("cell", CELLS)
def test_deferred_arrays_is_listed_with_the_resident_cells(cell):
    """Both resident cells are IN the metric's list and report it (not
    pinned: a later resident cell is named there by an entry alone); the
    streamed cell, whose chunk programs never defer, is not."""
    entry = next(m for m in MAN.doc["per_layer"] if m["name"] == METRIC)
    assert cell in entry["workloads"]
    assert "sf1_streamed.power_scan_join" not in entry["workloads"]
    assert {k: entry[k] for k in entry if k not in ("name", "workloads")} == {
        "unit": "arrays", "better": "higher", "source": "program_span",
        "layer": "resident execution", "moves": "power_query_ms"}
    assert METRIC in {m["name"] for m in MAN.per_layer(cell)}
    assert METRIC not in {m["name"] for m in MAN.per_layer(
        "sf1_streamed.power_scan_join")}
