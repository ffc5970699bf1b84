# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""The reduction from a profiler trace to busy time, idle share, the top
operations and the longest idle gaps: on hand-made planes, on a small trace
recorded on the CPU (the ``.xplane.pb`` parser and the annotations), and on
a slice of a trace recorded on a TPU v5e (kept as the plain lists
``read_planes`` gives)."""

import json
import os

import pytest

from benchmark import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000


def planes(ops, notes, modules=()):
    return [
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [[n, s, d] for n, s, d in notes]},
            {"name": "other", "events": [["fusion.1", 0, 5 * MS]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": list(modules)},
            {"name": "XLA Ops", "events": list(ops)}]}]


def test_merge_and_clip():
    assert xplane.merge([[5, 7], [0, 2], [1, 3], [3, 4], [9, 9]]) == \
        [[0, 4], [5, 7]]
    assert xplane.clip([[0, 4], [5, 7]], 2, 6) == [[2, 4], [5, 6]]


def test_busy_is_a_union_clipped_to_the_annotated_window():
    # window 10..110 ms; ops: 0-20 (half outside), 30-50, 40-60 (overlaps),
    # 100-130 (part outside) -> busy 10 + 30 + 10 = 50 of 100 ms
    got = xplane.reduce_trace(planes(
        ops=[["fusion.1", 0, 20 * MS], ["fusion.1", 30 * MS, 20 * MS],
             ["copy.2", 40 * MS, 20 * MS], ["copy.2", 100 * MS, 30 * MS]],
        notes=[("q_a", 10 * MS, 50 * MS), ("q_b", 60 * MS, 50 * MS)]),
        ["q_a", "q_b"])
    assert got["window_s"] == pytest.approx(0.100)
    assert got["busy_s"] == pytest.approx(0.050)
    assert got["idle_share"] == pytest.approx(0.5)
    assert got["devices"] == 1
    # top operations by summed duration, under the trace's own names
    assert got["device_ops"] == [["copy.2", pytest.approx(0.050)],
                                 ["fusion.1", pytest.approx(0.040)]]
    # the longest gap (60-100 ms) falls in q_b; the 20-30 ms one in q_a
    assert got["idle_gaps"][0] == ["q_b", pytest.approx(0.040)]
    assert ["q_a", pytest.approx(0.010)] in got["idle_gaps"]
    assert got["busy_by_annotation"] == {"q_a": pytest.approx(0.040),
                                         "q_b": pytest.approx(0.010)}


def test_host_events_are_not_device_time_and_ops_line_is_the_only_source():
    # the host's fusion.1 (0-5 ms) is no device time; the whole program on
    # the modules line (20-30 ms) overlaps its own operation and is not
    # counted beside it
    got = xplane.reduce_trace(planes(
        ops=[["fusion.7", 22 * MS, 4 * MS]],
        modules=[["jit_step", 20 * MS, 10 * MS]],
        notes=[("q_a", 0, 100 * MS)]), ["q_a"])
    assert got["busy_s"] == pytest.approx(0.004)
    assert got["device_ops"] == [["fusion.7", pytest.approx(0.004)]]


@pytest.mark.parametrize("lacking", ["ops_line", "annotations"])
def test_a_trace_that_lacks_its_yardstick_gives_nothing_to_read(lacking):
    """No second choice: with no ``XLA Ops`` events (whole programs only)
    or with none of the harness's annotations the reduction reads nothing,
    never a number from overlapping lines or the devices' own extent."""
    ops = [] if lacking == "ops_line" else [["fusion.7", 22 * MS, 4 * MS]]
    notes = [] if lacking == "annotations" else [("q_a", 0, 100 * MS)]
    got = xplane.reduce_trace(planes(
        ops=ops, modules=[["jit_step", 20 * MS, 10 * MS]], notes=notes),
        ["q_a"])
    assert got is None


def test_no_operation_on_a_device_gives_nothing_to_read():
    assert xplane.reduce_trace(planes(ops=[], notes=[("q_a", 0, MS)]),
                               ["q_a"]) is None
    host_only = [p for p in planes(ops=[["x", 0, 1]], notes=[])
                 if p["name"].startswith("/host")]
    assert xplane.reduce_trace(host_only, ["q_a"]) is None


def test_busy_is_the_mean_over_devices():
    two = planes(ops=[["a", 0, 40 * MS]], notes=[("q", 0, 100 * MS)])
    two.append({"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [["a", 0, 20 * MS]]}]})
    got = xplane.reduce_trace(two, ["q"])
    assert got["devices"] == 2 and got["busy_s"] == pytest.approx(0.030)


def test_recorded_cpu_trace_parses_and_holds_the_annotations():
    path = xplane.find_xplane(os.path.join(DATA, "cpu_trace"))
    got = xplane.read_planes(path)
    assert any(p["name"] == "/host:CPU" for p in got)
    notes = xplane.annotations(got, ["query_a", "query_b"])
    assert [n for n, _s, _e in notes] == ["query_a", "query_b"]
    assert all(e > s for _n, s, e in notes)
    assert notes[0][2] <= notes[1][1]          # one after the other
    # a CPU trace has no device plane: nothing to read, never a 0
    assert xplane.device_planes(got) == []
    assert xplane.reduce_trace(got, ["query_a", "query_b"]) is None
    assert xplane.summary(got)[0]["plane"]


def test_find_xplane_refuses_an_empty_directory(tmp_path):
    with pytest.raises(FileNotFoundError):
        xplane.find_xplane(str(tmp_path))


def test_recorded_tpu_trace_slice():
    """Two statements of a resident pass on a TPU v5e (my chip run, PR 25),
    operation names cut by ``short_name``. The expected busy time was taken
    independently, from a one-microsecond timeline of the same events."""
    with open(os.path.join(DATA, "tpu_v5e_two_statements.planes.json")) as f:
        recorded = json.load(f)
    got = xplane.reduce_trace(recorded, ["query3", "query42"])
    assert got["devices"] == 1 and got["op_line_events"] == 1759
    assert got["window_s"] == pytest.approx(5.194171906, rel=1e-9)
    assert got["busy_s"] == pytest.approx(5.1588, abs=5e-4)
    assert 100 * got["idle_share"] == pytest.approx(0.68, abs=0.02)
    assert len(got["device_ops"]) == 10 and len(got["idle_gaps"]) == 10
    times = [t for _n, t in got["device_ops"]]
    assert times == sorted(times, reverse=True) and times[0] > 0.5
    assert sum(got["busy_by_annotation"].values()) == \
        pytest.approx(got["busy_s"], abs=1e-6)
    assert {n for n, _t in got["idle_gaps"]} <= {"query3", "query42",
                                                 "between_statements"}


@pytest.mark.parametrize("name,short", [
    ("%while.4 = (u32[]{:T(128)}, s32[4194304]{0:T(1024)S(1)}) "
     "while((u32[]{:T(128)}, s32[4194304]{0:T(1024)}) %tuple.101), "
     "condition=%c, body=%b", "%while.4 while (u32[], s32[4194304])"),
    ("%fusion.35 = u32[4194304]{0:T(1024)S(1)} fusion(u32[524288]{0:T(1024)} "
     "%gte.291), kind=kCustom, calls=%fc", "%fusion.35 fusion u32[4194304]"),
    ("jit_impl(5444588974422126457)", "jit_impl(5444588974422126457)")])
def test_short_name(name, short):
    assert xplane.short_name(name) == short
