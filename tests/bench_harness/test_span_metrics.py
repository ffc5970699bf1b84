# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""The per-layer metrics that read the program's span rollup
(``rec["phases"]``): each reader over a recorded ``phases`` dict, and over
what a program without span parents (the parent commit) gives, where it
must read nothing and not raise."""

import pytest

from benchmark import manifest

MAN = manifest.Manifest()


def phase(ms, self_ms=None, wait=0.0, root=0.0, **more):
    return dict({"ms": ms, "count": 1, "syncs": 0,
                 "selfMs": ms if self_ms is None else self_ms,
                 "syncWaitMs": wait, "compileMs": 0.0, "rootMs": root},
                **more)


# two statements of one traced pass, as the program's rollup gives them
RESIDENT = [
    {"call_ms": 110.0, "phases": {
        "statement": phase(100.0, 1.0, root=100.0),
        "parse": phase(2.0), "plan": phase(97.0, 10.0),
        "op.join": phase(60.0, 50.0, wait=40.0),
        "op.filter": phase(20.0, 20.0),
        "op.sort": phase(7.0),
        "materialize": phase(6.0, root=6.0),
        "collect": phase(2.0, root=2.0)}},
    {"call_ms": 50.0, "phases": {
        "statement": phase(45.0, 1.0, root=45.0),
        "parse": phase(1.0), "plan": phase(43.0, 3.0),
        "op.agg": phase(40.0, 40.0, wait=30.0),
        "materialize": phase(3.0, root=3.0),
        "collect": phase(1.0, root=1.0)}},
]
STREAMED = [
    {"call_ms": 200.0, "phases": {
        "statement": phase(190.0, 1.0, root=190.0),
        "parse": phase(2.0), "plan": phase(187.0, 5.0),
        "stream": phase(180.0, 30.0, leadInMs=24.0),
        "prefetch.source": phase(150.0), "prefetch.prepare": phase(12.0),
        "prefetch.backpressure": phase(3.0),
        "materialize": phase(4.0, root=4.0),
        "collect": phase(1.0, root=1.0)}},
    {"call_ms": 100.0, "phases": {
        "statement": phase(96.0, 1.0, root=96.0),
        "parse": phase(1.0), "plan": phase(94.0, 4.0),
        "stream": phase(90.0, 10.0, leadInMs=16.0),
        "prefetch.source": phase(50.0), "prefetch.prepare": phase(8.0),
        "materialize": phase(2.0, root=2.0),
        "collect": phase(1.0, root=1.0)}},
]
# the parent commit's rollup: ms / count / syncs per phase and nothing else
PARENT = [
    {"call_ms": 110.0, "phases": {
        "plan": {"ms": 97.0, "count": 1, "syncs": 2},
        "stream": {"ms": 90.0, "count": 1, "syncs": 1},
        "materialize": {"ms": 6.0, "count": 1, "syncs": 0}}},
    {"call_ms": 50.0, "phases": {}},
]

CASES = [
    ("plan.self_ms_per_query", RESIDENT, (2.0 + 10.0 + 1.0 + 3.0) / 2),
    ("resident.op_dispatch_ms_per_query", RESIDENT,
     ((50.0 - 40.0) + 20.0 + 7.0 + (40.0 - 30.0)) / 2),
    ("stream.lead_in_ms_per_query", STREAMED, 20.0),
    ("stream.encode_ms_per_query", STREAMED, 100.0),
    ("stream.upload_ms_per_query", STREAMED, 10.0),
    ("drivers.untraced_ms_per_query", RESIDENT,
     ((110.0 - 108.0) + (50.0 - 49.0)) / 2),
]


@pytest.mark.parametrize("metric,records,want", CASES,
                         ids=[c[0] for c in CASES])
def test_span_metric_reads_the_rollup_and_nothing_without_it(
        metric, records, want):
    read = MAN.reader(metric)
    assert read({"records": records}) == pytest.approx(want)
    # a program without span parents: nothing to read, and no raise
    assert read({"records": PARENT}) is None
    assert read({"records": []}) is None


@pytest.mark.parametrize("metric", [c[0] for c in CASES])
def test_span_metric_is_listed_with_its_cells(metric):
    entry = next(m for m in MAN.doc["per_layer"] if m["name"] == metric)
    assert entry["source"] == "program_span"
    assert entry["moves"] == "power_query_ms" and entry["unit"] == "ms"
    resident = "sf1_resident.power_scan_join" in entry["workloads"]
    streamed = "sf1_streamed.power_scan_join" in entry["workloads"]
    assert resident == (not metric.startswith("stream."))
    assert streamed == (not metric.startswith("resident."))
