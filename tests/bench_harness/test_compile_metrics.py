# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""The five per-layer metrics of set-up that read the program's compile
table (``nds_tpu/obs/compiles.py``): each reader over a made-up table,
nothing where the program has no such module, every cell listed, and a CPU
rehearsal of a traced run reporting all five."""

import json
import os
import shutil
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import manifest  # noqa: E402

MAN = manifest.Manifest(REPO)
CELLS = [w["name"] for w in MAN.doc["workloads"]]
TOTALS = {"builds": 412, "hits": 380, "misses": 32, "backendMs": 40_500.0,
          "readMs": 12_250.0, "traceMs": 1_500.0, "lowerMs": 2_750.0}
# metric -> (what it reads of TOTALS, unit)
WANTED = {"drivers.setup_compile_s": (40.5, "s"),
          "drivers.setup_cache_read_s": (12.25, "s"),
          "drivers.setup_trace_lower_s": (4.25, "s"),
          "drivers.setup_cache_misses": (32, "count"),
          "drivers.setup_programs": (412, "count")}
MODULE = "nds_tpu.obs.compiles"


@pytest.fixture
def made_up_table(monkeypatch):
    fake = types.ModuleType(MODULE)
    fake.totals = lambda: dict(TOTALS)
    import nds_tpu.obs
    monkeypatch.setitem(sys.modules, MODULE, fake)
    monkeypatch.setattr(nds_tpu.obs, "compiles", fake, raising=False)


@pytest.mark.parametrize("metric", sorted(WANTED))
def test_reader_over_a_made_up_table(metric, made_up_table):
    value, _unit = WANTED[metric]
    assert MAN.reader(metric)({}) == pytest.approx(value)


@pytest.mark.parametrize("metric", sorted(WANTED))
def test_reader_reads_nothing_where_the_program_has_no_table(
        metric, monkeypatch):
    """A parent from before the module: the import fails, the reader
    returns None and the result line leaves the metric out."""
    import nds_tpu.obs
    monkeypatch.setitem(sys.modules, MODULE, None)
    monkeypatch.delattr(nds_tpu.obs, "compiles", raising=False)
    assert MAN.reader(metric)({}) is None


@pytest.mark.parametrize("metric", sorted(WANTED))
def test_entry_moves_setup_and_lists_every_cell(metric):
    entry = MAN._by_name("per_layer", metric)
    assert entry == {"name": metric, "unit": WANTED[metric][1],
                     "better": "lower", "source": "program_counter",
                     "layer": "drivers", "moves": "setup_s",
                     "workloads": CELLS}
    assert [m["name"] for m in MAN.doc["per_layer"]][-5:] == list(WANTED)
    assert any(m["name"] == "setup_s" for m in MAN.end_to_end(CELLS[0]))


WRAPPER = """
import sys
from benchmark import run
run.CACHE_DIR = sys.argv[1]
sys.exit(run.main(sys.argv[2:]))
"""


def test_a_traced_rehearsal_reports_all_five(tmp_path):
    """One cell's ``run.py --rehearse --trace 1`` on the CPU: the five
    metrics are in the result line, the process built programs, and the
    table's split adds up to what ``compile_ns()`` charged the warm-up
    and no more than the warm-up's wall."""
    cache = str(tmp_path / "bench_cache")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    try:
        proc = subprocess.run(
            [sys.executable, "-c", WRAPPER, cache, "--workload", CELLS[0],
             "--seed", "3700003701", "--seconds", "1", "--trace", "1",
             "--rehearse"], cwd=REPO, env=env, capture_output=True,
            text=True, timeout=900)
    finally:
        shutil.rmtree(cache, ignore_errors=True)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.strip()]
    result = lines[-1]
    assert result["correct"] is True
    got = result["metrics"]
    for metric, (_value, unit) in WANTED.items():
        assert got[metric]["unit"] == unit, metric
        assert got[metric]["value"] >= 0, metric
    assert got["drivers.setup_programs"]["value"] > 0
    assert got["drivers.setup_trace_lower_s"]["value"] > 0
    # the CPU of a rehearsal keeps no persistent cache: every build is a
    # compile, none is read or written
    assert got["drivers.setup_compile_s"]["value"] > 0
    assert got["drivers.setup_cache_read_s"]["value"] == 0
    assert got["drivers.setup_cache_misses"]["value"] == 0
    assert got["drivers.compile_ms_in_window"]["value"] == 0
    setup = [ln for ln in lines if ln.get("event") == "setup"][0]
    warm = [ln for ln in lines if ln.get("event") == "warm_pass"]
    built_s = (got["drivers.setup_compile_s"]["value"]
               + got["drivers.setup_trace_lower_s"]["value"])
    assert built_s < setup["load_s"] + setup["warm_s"]
    # what the warm-up's statements were charged is in the table (the
    # load's builds are in it too, and in no statement's compile_ms)
    assert got["drivers.setup_compile_s"]["value"] * 1e3 >= \
        sum(w["compile_ms"] for w in warm) * 0.999
