# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""The closed loop and the whole-window arithmetic of the end-to-end
metrics, on synthetic logs."""

import math

import pytest

from benchmark import window


def log(walls_ms, stall_ms=0.0, names=("a", "b")):
    """Back-to-back executions, ``stall_ms`` of harness time between them."""
    records, t = [], 0.0
    for i, wall in enumerate(walls_ms):
        records.append({"name": names[i % len(names)], "ok": True,
                        "start_s": t, "end_s": t + wall / 1e3})
        t += (wall + stall_ms) / 1e3
    return records


def test_power_query_ms_is_window_wall_over_completed():
    assert window.power_query_ms(log([100, 300, 100, 300])) == \
        pytest.approx(200.0)


def test_a_stall_between_queries_moves_power_query_ms_only():
    quiet, stalled = log([100, 300] * 3), log([100, 300] * 3, stall_ms=50)
    assert window.power_query_ms(stalled) > window.power_query_ms(quiet) + 40
    assert window.power_geomean_ms(stalled) == \
        pytest.approx(window.power_geomean_ms(quiet))


def test_geomean_is_over_names_of_each_names_mean():
    records = log([100, 400, 300, 400])           # a: 100, 300; b: 400, 400
    assert window.power_geomean_ms(records) == \
        pytest.approx(math.sqrt(200.0 * 400.0))


def test_failed_statements_count_as_time_but_not_as_completed():
    records = log([100, 300, 100, 300])
    records[1]["ok"] = False
    assert window.attempted_failed(records) == (4, 1)
    assert window.power_query_ms(records) == pytest.approx(800.0 / 3)
    for r in records:
        r["ok"] = False
    assert window.power_query_ms(records) is None
    assert window.power_geomean_ms(records) is None


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@pytest.mark.parametrize("seconds,passes", [(0, 1), (0.5, 1), (1.0, 1),
                                            (1.1, 2), (3.5, 4)])
def test_whole_passes_only_and_the_pass_in_flight_ends_the_window(
        seconds, passes):
    clock = FakeClock()
    seen = []

    def execute(name, index):
        clock.t += 0.5                           # every statement takes 0.5 s
        seen.append((name, index))
        return {"ok": True}

    win = window.run_passes(["x", "y"], execute, seconds, clock=clock)
    assert win["passes"] == passes and len(win["records"]) == 2 * passes
    assert [n for n, _ in seen] == ["x", "y"] * passes
    assert win["records"][-1]["end_s"] == pytest.approx(passes * 1.0)
    assert {r["pass"] for r in win["records"]} == set(range(passes))
