# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""The subquery cell's two span metrics on a recorded rollup, nothing on a
run without the span (the parent's), and the cell's place in the manifest."""

import json
import os

import pytest

from benchmark import manifest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MAN = manifest.Manifest(REPO)
CELL = "sf1_resident_subqueries.power_subquery"
CONFIG = MAN.config(MAN.cell(CELL)["config"])
TRAFFIC = MAN.traffic(MAN.cell(CELL)["traffic"])
METRICS = ["resident.subquery_ms_per_query",
           "resident.subquery_cells_per_query"]


def phase(ms, **attrs):
    return dict({"ms": ms, "count": 2, "syncs": 1, "selfMs": ms / 4,
                 "syncWaitMs": 0.5, "compileMs": 0.0, "rootMs": 0.0}, **attrs)


# what obs.export.rollup gives a statement: a correlated scalar, a residual
# EXISTS beside a plain one, two memberships, and a statement with none
RECORDS = [
    {"phases": {"op.subquery": phase(400.0, cells=2048, planned=1,
                                     correlated=1, residual=0, negated=0),
                "op.join": phase(300.0, cells=4096)}},
    {"phases": {"op.subquery": phase(900.0, cells=60000, planned=2,
                                     correlated=2, residual=1, negated=0),
                "op.semi_join": phase(1.0, cells=5)}},
    {"phases": {"op.subquery": phase(20000.0, cells=33554432, planned=2,
                                     correlated=0, residual=0, negated=0)}},
    {"phases": {"op.filter": phase(1.0)}},
]
# the parent's spans: the evaluators' callees alone
PARENT = [{"phases": {"op.join": phase(300.0, cells=4096),
                      "op.semi_join": phase(1.0, cells=5)}},
          {"phases": {}}]
CASES = [
    ("resident.subquery_ms_per_query", RECORDS, (400.0 + 900.0 + 20000.0) / 4),
    ("resident.subquery_cells_per_query", RECORDS,
     (2048 + 60000 + 33554432) / 4),
    ("resident.subquery_ms_per_query", PARENT, None),
    ("resident.subquery_cells_per_query", PARENT, None),
    ("resident.subquery_ms_per_query", [], None),
    ("resident.subquery_cells_per_query", [], None),
    # a span that stated no cells (never the program's: it always does)
    ("resident.subquery_cells_per_query",
     [{"phases": {"op.subquery": phase(5.0)}}], None),
    ("resident.subquery_ms_per_query",
     [{"phases": {"op.subquery": phase(5.0)}}, {"phases": {}}], 2.5),
]


@pytest.mark.parametrize("metric,records,want", CASES,
                         ids=[f"{m}-{i}" for i, (m, _, _) in enumerate(CASES)])
def test_reader_takes_the_rollup_and_nothing_without_the_span(metric, records,
                                                              want):
    got = MAN.reader(metric)({"records": records})
    assert got is None if want is None else got == pytest.approx(want)


def test_readers_read_what_the_programs_rollup_writes():
    """The names the readers look up are the ones ``obs.export.rollup``
    writes for spans as the planner states them."""
    from nds_tpu.obs import export, trace
    trace.drain_spans()
    with trace.span("statement"):
        with trace.op("subquery", fn="in", negated=0):
            trace.annotate(correlated=0, residual=0, cells=0)
            trace.annotate(planned=1)
            trace.annotate(cells=96)
        with trace.op("subquery", fn="exists", negated=0):
            trace.annotate(correlated=1, residual=1, cells=0, planned=0)
            trace.annotate(cells=4)
    phases = export.rollup(trace.drain_spans())["phases"]
    sub = phases["op.subquery"]
    assert (sub["count"], sub["cells"], sub["planned"], sub["correlated"],
            sub["residual"], sub["negated"]) == (2, 100, 1, 1, 1, 0)
    run = {"records": [{"phases": phases}, {"phases": {}}]}
    assert MAN.reader(METRICS[1])(run) == 50.0
    assert MAN.reader(METRICS[0])(run) == pytest.approx(sub["ms"] / 2)


@pytest.mark.parametrize("metric", METRICS)
def test_new_metric_is_listed_with_the_new_cell_alone(metric):
    entry = next(m for m in MAN.doc["per_layer"] if m["name"] == metric)
    assert entry["workloads"] == [CELL]
    assert entry["layer"] == "resident execution"
    assert entry["moves"] == "power_query_ms"
    assert entry["source"] == "program_span"
    assert entry["better"] == "lower"
    with open(MAN.reader_path(METRICS[0])) as f:
        assert "WAIT-ATTRIBUTED" in f.read()


def test_the_cell_reports_its_metrics_and_states_its_one_setting():
    listed = {m["name"] for m in MAN.per_layer(CELL)}
    assert set(METRICS) < listed
    assert {"kernels.scan_roofline", "device.idle_share",
            "device.peak_hbm_bytes", "resident.host_syncs_per_query",
            "resident.sync_wait_ms_per_query",
            "resident.op_dispatch_ms_per_query",
            "resident.join_ms_per_query", "resident.join_cells_per_query",
            "resident.probe_rows_per_query",
            "resident.deferred_gather_arrays_per_query", "load.tables_s",
            "plan.self_ms_per_query", "plan.scan_columns_per_query",
            "drivers.compile_ms_in_window", "drivers.cache_misses_in_window",
            "drivers.untraced_ms_per_query"} < listed
    assert not [m for m in listed if m.startswith(("stream.",
                                                   "resident.setop"))]
    assert json.dumps(CONFIG["env"]) == '{"NDS_TPU_REPLAY": "off"}'
    assert CONFIG["rehearsal"]["env"] == CONFIG["env"]
    assert "Power Run" in CONFIG["env_why"]["NDS_TPU_REPLAY"]
    assert CONFIG["stream_scans"] == "none" and CONFIG["use_decimal"] is True
    assert CONFIG["scale_factor"] == 1 and CONFIG["tables"] == 24
    assert CONFIG["reference"] == "reference/sqlite_ref_subqueries.py"
    assert MAN.cell(CELL)["chips"] == 1


def test_the_mix_keeps_what_the_cell_is_for():
    """Four names in the stream's order; a residual EXISTS, a correlated
    scalar, an IN or NOT EXISTS over a fact, and a column of cents for the
    control to miss."""
    names = [q["name"] for q in TRAFFIC["queries"]]
    assert len(names) == 4
    assert names == sorted(names, key=lambda n: int(n[len("query"):]))
    assert {"query94", "query16"} & set(names)
    assert {"query1", "query32", "query92", "query6"} & set(names)
    assert {"query95", "query69", "query94", "query16"} & set(names)
    kinds = {k for q in TRAFFIC["queries"] for k in q["result"]}
    assert "cents" in kinds and kinds <= {"int", "str", "cents"}
    for name in names:
        assert name[len("query"):] in CONFIG["source"]
    entry = next(c for c in MAN.doc["configs"]
                 if c["name"] == "nds_sf1_resident_subqueries")
    assert entry["source"] == CONFIG["source"]
    assert entry["reduced"] == CONFIG["reduced"] == ["scale_factor"]
