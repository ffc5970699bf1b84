# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""CPU rehearsals of a whole run at the configurations' rehearsal scale
(SF0.01): the result line, the comparison failing on a broken timed path,
and the control coming out not correct. Every run is a child process with
``JAX_PLATFORMS=cpu``; the cache goes to a temporary directory (a seed's
data is 300 MB, too much to leave inside the checkout)."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    DOC = json.load(_f)
CELLS = [w["name"] for w in DOC["workloads"]]
SEED = 2_500_000_005          # past 2**31, as the driver's seeds are

# the timed path broken underneath: an answer altered where it is produced
ALTER_AN_ANSWER = """
from nds_tpu.engine import session as _s
_collect = _s.Result.collect
def _broken(self):
    rows = _collect(self)
    if rows and isinstance(rows[0][0], int):
        rows[0] = (rows[0][0] + 1,) + tuple(rows[0][1:])
    return rows
_s.Result.collect = _broken
"""
# the CPU's trace (no device plane) read as if the run held an accelerator
NAME_THE_DEVICE_TPU = """
from benchmark import program as _p
_info = _p.Program.device_info
_p.Program.device_info = lambda self: dict(_info(self), platform="tpu")
"""
WRAPPER = """
import sys
cache, module = sys.argv[1], sys.argv[2]
{patch}
from benchmark import run, control
run.CACHE_DIR = cache
sys.exit({{"run": run, "control": control}}[module].main(sys.argv[3:]))
"""


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    path = tmp_path_factory.mktemp("bench_cache")
    yield str(path)
    shutil.rmtree(path, ignore_errors=True)


def drive(cache, module, argv, patch=""):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "-c", WRAPPER.format(patch=patch), cache, module]
        + argv, cwd=REPO, env=env, capture_output=True, text=True,
        timeout=600)


def result_of(proc):
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    for ln in lines:
        json.loads(ln)                  # every line is one JSON object
    return json.loads(lines[-1]), lines, proc.stderr


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_ends_in_the_contracts_line_and_names_cpu(cache, cell):
    result, lines, stderr = result_of(drive(
        cache, "run", ["--workload", cell, "--seed", str(SEED), "--seconds",
                       "1", "--trace", "0", "--rehearse"]))
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "compared"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 4 and result["attempted"] % 4 == 0
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == 1
    wanted = {m["name"]: m["unit"] for m in DOC["end_to_end"]}
    assert {k: v["unit"] for k, v in result["metrics"].items()} == wanted
    assert all(v["value"] > 0 for v in result["metrics"].values())
    for number, (value, limit) in result["compared"].items():
        assert value <= limit, number
    # the numbers compared, each beside its limit, end standard error
    tail = stderr.strip().splitlines()[-len(result["compared"]):]
    assert all("limit" in ln for ln in tail)
    setup = [json.loads(ln) for ln in lines if '"event": "setup"' in ln][0]
    assert setup["scale"] == "0.01" and len(setup["order"]) == 4


def test_traced_rehearsal_reports_per_layer_metrics_only(cache):
    result, _lines, _err = result_of(drive(
        cache, "run", ["--workload", CELLS[0], "--seed", str(SEED),
                       "--seconds", "1", "--trace", "1", "--rehearse"]))
    assert result["correct"] is True and result["attempted"] == 4
    names = {m["name"] for m in DOC["per_layer"]}
    assert set(result["metrics"]) <= names
    assert not set(result["metrics"]) & {m["name"] for m in DOC["end_to_end"]}
    # nothing ran on a device: no device trace, so no busy time, no idle
    # share and no roofline share is written (never 0, never from the CPU)
    assert not [m for m in result["metrics"] if "roofline" in m or
                m.startswith("device.idle")]
    assert "busy_s" not in result["device"]


def test_a_traced_run_on_a_chip_whose_trace_cannot_be_read_fails(cache):
    """On an accelerator a trace with no ``XLA Ops`` line or no annotation
    ends the run with no result line: no device metric from a second
    yardstick, and none silently left out."""
    proc = drive(cache, "run", ["--workload", CELLS[0], "--seed", str(SEED),
                                "--seconds", "1", "--trace", "1",
                                "--rehearse"], patch=NAME_THE_DEVICE_TPU)
    assert proc.returncode == 5, proc.stderr[-2000:]
    assert '"correct"' not in proc.stdout
    assert "no device metric can be read" in proc.stderr


def test_an_altered_answer_makes_the_run_not_correct(cache):
    result, _lines, stderr = result_of(drive(
        cache, "run", ["--workload", CELLS[0], "--seed", str(SEED),
                       "--seconds", "1", "--trace", "0", "--rehearse"],
        patch=ALTER_AN_ANSWER))
    assert result["correct"] is False
    assert result["compared"]["rows_off"][0] > 0
    assert "rows_off" in stderr


def test_the_control_comes_out_not_correct(cache):
    proc = drive(cache, "control",
                 ["--workload", CELLS[0], "--seeds", str(SEED), "--seconds",
                  "1", "--rehearse"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    (line,) = [json.loads(ln) for ln in proc.stdout.splitlines()
               if ln.startswith("{")]
    assert line["correct"] is False and line["failed"] == 0
    gap, limit = line["compared"]["decimal_gap_max"]
    assert gap > limit == 0
    assert line["compared"]["rows_off"] == [0, 0]


def test_no_result_where_there_is_no_chip(cache):
    proc = drive(cache, "run", ["--workload", CELLS[0], "--seed", str(SEED),
                                "--seconds", "1", "--trace", "0"])
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "no accelerator" in proc.stderr


def test_no_result_in_a_directory_without_the_program(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for p in DOC["paths"]:
        shutil.copytree(os.path.join(REPO, p), tmp_path / p,
                        ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    proc = subprocess.run(
        [sys.executable] + DOC["command"][1:]
        + ["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
           "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=120)
    assert proc.returncode != 0 and '"correct"' not in proc.stdout
