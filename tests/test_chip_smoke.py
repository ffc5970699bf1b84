# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""The chip smoke's own contract, and the start-up rules it relies on.

``chip_smoke.py`` is what checks the program on a TPU after every PR; it
must fail where there is no chip, never print its ``"ok": true`` line
there, and still be runnable end to end on the CPU (``--rehearse``) so its
control flow is tested without chip time. Also pinned here: no driver ends
on the CPU behind the user's back (``check.select_device``), the compile
cache is placed from outside (``JAX_COMPILATION_CACHE_DIR``) or at
``<checkout>/.jax_cache``, and the bench.py parent never initialises a JAX
backend (one process holds the chip: the serving child).
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run_smoke(tmp_path, *argv, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu", NDS_TPU_COMP_CACHE="force")
    return subprocess.run(
        [sys.executable, SMOKE, "--workdir", str(tmp_path / "work"),
         "--out", str(tmp_path / "out"), *argv],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=timeout)


def _events(stdout):
    out = []
    for line in stdout.splitlines():
        if line.startswith("{"):
            out.append(json.loads(line))
    return out


def test_rehearsal_runs_every_phase_and_prints_no_contract_line(tmp_path):
    """``--rehearse`` at SF0.01 over two queries: the whole script — build,
    generate, Load, stream, resident and streamed Power (two passes each),
    the CPU arm, validation — exits 0 and never prints ``"ok": true``."""
    got = _run_smoke(tmp_path, "--rehearse", "--queries", "query42,query96",
                     timeout=900)
    assert got.returncode == 0, got.stdout[-3000:] + got.stderr[-2000:]
    assert '"ok"' not in got.stdout
    events = _events(got.stdout)
    kinds = [e.get("event") for e in events]
    assert kinds[:2] == ["start", "device"] and "load" in kinds
    assert events[1]["platform"] == "cpu"
    queries = [e for e in events if e.get("event") == "query"]
    assert [(e["phase"], e["query"]) for e in queries] == [
        ("resident", "query42"), ("resident", "query96"),
        ("streamed", "query42"), ("streamed", "query96")]
    assert all(e["status"] == "Completed" for e in queries)
    assert {e["path"] for e in queries if e["phase"] == "streamed"} == {
        "compiled stream"}
    assert all(e["path"] in ("eager", "replay")
               for e in queries if e["phase"] == "resident")
    assert any(e.get("event") == "threshold"
               and "LOWERED" in e["note"] for e in events)
    assert [e["phase"] for e in events if e.get("event") == "validate"
            and e["result"] == "Pass"] == ["resident", "streamed"]
    # the scratch data is gone, the evidence stays
    assert not os.path.exists(tmp_path / "work")
    assert os.path.exists(tmp_path / "out" / "streamed" / "ledger.jsonl")


def test_without_a_chip_it_fails_at_the_device_check(tmp_path):
    """No ``--rehearse`` and ``JAX_PLATFORMS=cpu``: the probe child, run
    before any data is generated, finds no TPU — non-zero exit in
    seconds, no ``"ok": true`` line."""
    got = _run_smoke(tmp_path, timeout=300)
    assert got.returncode != 0
    assert '"ok"' not in got.stdout
    assert "not a TPU" in got.stdout
    kinds = [e.get("event") for e in _events(got.stdout)]
    # the device check is the last thing it did: nothing was generated
    assert kinds[0] == "start" and kinds[-1] == "device"
    assert "load" not in kinds
    assert not os.path.exists(tmp_path / "work")


def test_alone_in_a_directory_it_fails_at_once(tmp_path):
    """A directory that holds chip_smoke.py and nothing else of the repo:
    non-zero exit, no result line."""
    import shutil
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    got = subprocess.run([sys.executable, str(tmp_path / "chip_smoke.py")],
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         cwd=str(tmp_path), capture_output=True, text=True,
                         timeout=120)
    assert got.returncode != 0
    assert '"ok"' not in got.stdout
    assert "not a checkout of nds-tpu" in got.stdout


@pytest.mark.parametrize("device,env,pinned", [
    ("tpu", None, "tpu"),       # unset: a missing chip is JAX's own error
    ("tpu", "cpu", None),       # the caller's explicit choice is respected
    ("tpu", "tpu", None),
    ("cpu", None, "cpu"),
    ("cpu", "tpu", "cpu"),      # --device cpu always pins the host
])
def test_select_device_never_ends_on_the_cpu_quietly(monkeypatch, device,
                                                     env, pinned):
    import jax

    from nds_tpu.check import select_device
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    if env is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", env)
    select_device(device)
    if pinned is None:
        assert updates == [] and os.environ["JAX_PLATFORMS"] == env
    else:
        assert updates == [("jax_platforms", pinned)]
        assert os.environ["JAX_PLATFORMS"] == pinned


@pytest.fixture
def fresh_cache_state(monkeypatch):
    """enable_compile_cache() as a new process would see it, with the
    real jax config put back afterwards."""
    import jax

    import nds_tpu
    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    monkeypatch.setattr(nds_tpu, "_comp_cache_enabled", False)
    monkeypatch.setattr(nds_tpu, "_comp_cache_unwritable", False)
    monkeypatch.setenv("NDS_TPU_COMP_CACHE", "force")   # CPU opts in
    monkeypatch.delenv("NDS_TPU_NO_COMP_CACHE", raising=False)
    yield nds_tpu
    for k, v in keep.items():
        jax.config.update(k, v)


def test_compile_cache_placed_from_outside_is_left_alone(fresh_cache_state,
                                                         monkeypatch):
    import jax
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    before = jax.config.jax_compilation_cache_dir
    assert fresh_cache_state.enable_compile_cache() is True
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_into_the_checkout(fresh_cache_state,
                                                  monkeypatch):
    import jax
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert fresh_cache_state.enable_compile_cache() is True
    got = jax.config.jax_compilation_cache_dir
    root = os.path.join(REPO, ".jax_cache")
    # CPU entries sit in a machine-fingerprint sub-directory of the root
    assert os.path.dirname(got) == root
    assert os.path.basename(got).startswith("cpu_") and os.path.isdir(got)


def test_bench_parent_never_initialises_a_backend():
    """bench.py's parent imports nds_tpu.io / schema / queries / power
    inside ensure_data() and bench_queries() before it starts the serving
    child. Importing is harmless; creating an array or asking for devices
    would take the chip from the child. Pin that it does neither."""
    code = (
        "import sys; sys.argv = ['bench.py']\n"
        "import bench\n"
        "bench.ensure_data(); assert bench.bench_queries()\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized(), "
        "'the bench.py parent initialised a JAX backend'\n"
        "print('parent-clean')\n")
    got = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu", NDS_BENCH_SCALE="0.01"))
    assert got.returncode == 0, got.stderr[-2000:]
    assert "parent-clean" in got.stdout
