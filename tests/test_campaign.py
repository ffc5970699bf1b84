# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Campaign driver tests: arm matrix expansion, env fingerprints,
manifest round-trip, kill-proof resume (SIGKILL mid-arm), classified arm
failures, the bench-side provenance stamp, and the cross-arm report —
all against a FAKE bench child (subprocess stub), no device work."""

import importlib.util
import json
import os
import signal
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools._ledger_load import campaign_mod, ledger_mod  # noqa: E402

C = campaign_mod()
L = ledger_mod()


def _load_tool(name, relpath):
    mod = sys.modules.get(name)
    if mod is None:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(REPO, relpath))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def campaign_tool():
    return _load_tool("_t_campaign_tool", "tools/campaign.py")


@pytest.fixture(scope="module")
def bench_compare():
    return _load_tool("_nds_bench_compare", "tools/bench_compare.py")


@pytest.fixture(autouse=True)
def _clean_knobs(monkeypatch):
    # arm fingerprints must be deterministic regardless of the invoking
    # shell's knob set
    for k in C.FINGERPRINT_KNOBS + ("NDS_CAMPAIGN_ARM", "NDS_FAKE_MODE",
                                    "NDS_FAKE_CALLS"):
        monkeypatch.delenv(k, raising=False)


@pytest.fixture
def no_signals(monkeypatch):
    # in-process driver runs must not install real handlers over
    # pytest's; the driver only needs .signal/.SIGTERM/.SIGINT
    monkeypatch.setattr(C, "signal", types.SimpleNamespace(
        signal=lambda signum, fn: None,
        SIGTERM=signal.SIGTERM, SIGINT=signal.SIGINT))


# the fake bench child: writes a STAMPED ledger exactly like bench.py's
# parent would, honoring resume (a preexisting ledger means the first
# segment's queries are not re-paid). NDS_FAKE_MODE (per-arm overlay):
#   ok            both queries + terminal completed record
#   fail          exit 3 before touching the ledger
#   kill-campaign first segment: query1 then SIGKILL the DRIVER
#                 (resume segment: query2 + terminal record)
_STUB = """\
import json, os, signal, sys
sys.path.insert(0, {repo!r})
from tools._ledger_load import ledger_mod, campaign_mod
L, C = ledger_mod(), campaign_mod()
path = os.environ["NDS_BENCH_RESULTS_JSONL"]
calls = os.environ.get("NDS_FAKE_CALLS")
if calls:
    with open(calls, "a") as f:
        f.write(os.environ.get("NDS_CAMPAIGN_ARM", "?") + "\\n")
mode = os.environ.get("NDS_FAKE_MODE", "ok")
if mode == "fail":
    sys.exit(3)
resuming = os.path.exists(path) and os.path.getsize(path) > 0
led = L.Ledger(path, stamp=C.campaign_stamp(), driver="bench", scale="10")
if not resuming:
    led.query("query1", ms=100.0, hostSyncs=1)
    if mode == "kill-campaign":
        os.kill(os.getppid(), signal.SIGKILL)
        sys.exit(7)
led.query("query2", ms=200.0, hostSyncs=1)
led.close("completed", queries=2)
"""


@pytest.fixture
def stub(tmp_path):
    p = tmp_path / "fake_bench.py"
    p.write_text(_STUB.format(repo=REPO))
    return [sys.executable, str(p)]


def _matrix(*arm_specs):
    return {"v": C.CAMPAIGN_VERSION, "env": {"NDS_BENCH_SCALE": "10"},
            "arms": [{"name": n, "env": e} for n, e in arm_specs]}


class TestArmModel:
    def test_expand_substitutes_dir_and_merges(self, tmp_path):
        arms = C.expand_arms(
            {"env": {"NDS_TPU_CHUNK_STORE": "{dir}/store"},
             "arms": [{"name": "base", "env": {}},
                      {"name": "cold",
                       "env": {"NDS_TPU_CHUNK_STORE": ""}}]},
            str(tmp_path))
        assert arms[0].env["NDS_TPU_CHUNK_STORE"] == \
            str(tmp_path) + "/store"
        assert arms[1].env["NDS_TPU_CHUNK_STORE"] == ""  # unset marker

    @pytest.mark.parametrize("matrix,msg", [
        ({"arms": []}, "non-empty"),
        ({"v": 99, "arms": [{"name": "a"}]}, "version"),
        ({"arms": [{"name": "a"}, {"name": "a"}]}, "duplicate"),
        ({"arms": [{"name": "../evil"}]}, "safe"),
        ({"arms": [{"env": {}}]}, "name"),
    ])
    def test_matrix_validation_is_loud(self, matrix, msg, tmp_path):
        with pytest.raises(C.CampaignError, match=msg):
            C.expand_arms(matrix, str(tmp_path))

    def test_fingerprint_distinguishes_unset_from_value(self):
        a = C.env_fingerprint({})
        b = C.env_fingerprint({"NDS_TPU_PALLAS": "auto"})
        assert a != b and "<unset>" in a and "NDS_TPU_PALLAS=auto" in b

    def test_overlay_removal_changes_fingerprint(self):
        base = {"NDS_TPU_CHUNK_STORE": "/warm"}
        warm = C.arm_fingerprint(C.Arm("w", {}), base)
        cold = C.arm_fingerprint(
            C.Arm("c", {"NDS_TPU_CHUNK_STORE": ""}), base)
        assert "CHUNK_STORE=/warm" in warm
        assert "CHUNK_STORE=<unset>" in cold

    def test_stamp_carries_arm_only_inside_campaign(self):
        assert "arm" not in C.campaign_stamp({})
        st = C.campaign_stamp({"NDS_CAMPAIGN_ARM": "base"})
        assert st["arm"] == "base" and "envFingerprint" in st


class TestManifest:
    def test_round_trip(self, tmp_path):
        arms = C.expand_arms(_matrix(("a", {}), ("b", {})), str(tmp_path))
        m = C.new_manifest(arms, str(tmp_path))
        C.write_manifest(str(tmp_path), m)
        got = C.load_manifest(str(tmp_path))
        assert got == m
        assert [a["name"] for a in got["arms"]] == ["a", "b"]
        assert all(a["fingerprint"] for a in got["arms"])

    def test_missing_is_none_and_unknown_version_refused(self, tmp_path):
        assert C.load_manifest(str(tmp_path)) is None
        with open(C.manifest_path(str(tmp_path)), "w") as f:
            json.dump({"v": 99}, f)
        with pytest.raises(C.CampaignError, match="version"):
            C.load_manifest(str(tmp_path))


class TestLedgerStamp:
    def test_stamp_rides_every_record_including_terminal(self, tmp_path):
        p = tmp_path / "led.jsonl"
        led = L.Ledger(str(p), stamp={"arm": "base",
                                      "envFingerprint": "fp-x"},
                       driver="bench", scale="10")
        led.query("query1", ms=10.0)
        led.progress(done=1)
        led.close("completed", queries=1)
        recs = [json.loads(ln) for ln in open(p)]
        assert {r["kind"] for r in recs} == \
            {"meta", "query", "progress", "end"}
        for r in recs:
            assert r["arm"] == "base" and r["envFingerprint"] == "fp-x"

    def test_unstamped_ledger_unchanged(self, tmp_path):
        p = tmp_path / "led.jsonl"
        led = L.Ledger(str(p), driver="bench")
        led.query("query1", ms=10.0)
        led.close("completed")
        for r in (json.loads(ln) for ln in open(p)):
            assert "arm" not in r and "envFingerprint" not in r


class TestResumeAdmission:
    def _arm(self, tmp_path, **env):
        return C.Arm("a1", {k: str(v) for k, v in env.items()})

    def _write(self, tmp_path, arm, end=None, fingerprint=None):
        path = C.arm_paths(str(tmp_path), arm.name)["ledger"]
        fp = fingerprint or C.arm_fingerprint(arm, {})
        led = L.Ledger(path, stamp={"envFingerprint": fp, "arm": arm.name},
                       driver="bench")
        led.query("query1", ms=10.0)
        led.close(end)
        return path

    def test_pending_partial_done(self, tmp_path):
        arm = self._arm(tmp_path)
        assert C.arm_status(arm, str(tmp_path), {})[0] == "pending"
        self._write(tmp_path, arm)                 # no terminal record
        assert C.arm_status(arm, str(tmp_path), {})[0] == "partial"
        os.remove(C.arm_paths(str(tmp_path), arm.name)["ledger"])
        self._write(tmp_path, arm, end="completed")
        assert C.arm_status(arm, str(tmp_path), {})[0] == "done"

    def test_aborted_round_resumes_not_skips(self, tmp_path):
        arm = self._arm(tmp_path)
        self._write(tmp_path, arm, end="aborted")  # signal-killed round
        assert C.arm_status(arm, str(tmp_path), {})[0] == "partial"

    def test_fingerprint_mismatch_refused_naming_both(self, tmp_path):
        arm = self._arm(tmp_path, NDS_TPU_PALLAS="off")
        self._write(tmp_path, arm, fingerprint="NDS_TPU_PALLAS=auto;...")
        with pytest.raises(C.CampaignResumeError) as ei:
            C.arm_status(arm, str(tmp_path), {})
        msg = str(ei.value)
        assert "NDS_TPU_PALLAS=auto;..." in msg          # recorded
        assert "NDS_TPU_PALLAS=off" in msg               # current
        assert "refusing" in msg

    def test_legacy_unstamped_ledger_resumes_freely(self, tmp_path):
        arm = self._arm(tmp_path)
        path = C.arm_paths(str(tmp_path), arm.name)["ledger"]
        led = L.Ledger(path, driver="bench")       # pre-campaign artifact
        led.query("query1", ms=10.0)
        led.close(None)
        assert C.arm_status(arm, str(tmp_path), {})[0] == "partial"

    def test_corrupt_ledger_reported_not_rerun(self, tmp_path):
        arm = self._arm(tmp_path)
        path = C.arm_paths(str(tmp_path), arm.name)["ledger"]
        os.makedirs(os.path.dirname(path))
        with open(path, "w") as f:
            f.write(json.dumps({"v": 99, "kind": "meta", "t": 0}) + "\n")
        status, why = C.arm_status(arm, str(tmp_path), {})
        assert status == "corrupt" and why


class TestDriver:
    def test_full_matrix_completes_all_arms(self, tmp_path, stub,
                                            no_signals, capsys):
        d = str(tmp_path / "camp")
        arms = C.expand_arms(_matrix(("a1", {}), ("a2", {}), ("a3", {})),
                             d)
        m = C.run_campaign(arms, d, bench_cmd=stub)
        assert [a["status"] for a in m["arms"]] == ["completed"] * 3
        assert m["status"] == "completed" and m["completedArms"] == 3
        assert C.load_manifest(d)["completedArms"] == 3   # durable
        for a in arms:
            data = L.load_ledger(C.arm_paths(d, a.name)["ledger"])
            assert data.end["status"] == "completed"
            assert data.meta["arm"] == a.name             # stamped
            assert data.meta["envFingerprint"] == C.arm_fingerprint(a)

    def test_completed_arms_skipped_on_rerun(self, tmp_path, stub,
                                             no_signals, monkeypatch):
        d = str(tmp_path / "camp")
        calls = tmp_path / "calls.txt"
        monkeypatch.setenv("NDS_FAKE_CALLS", str(calls))
        arms = C.expand_arms(_matrix(("a1", {}), ("a2", {})), d)
        C.run_campaign(arms, d, bench_cmd=stub)
        C.run_campaign(arms, d, bench_cmd=stub)   # same command again
        # rerun invoked NO bench child: both arms carried clean
        # terminal records
        assert calls.read_text().splitlines() == ["a1", "a2"]
        m = C.load_manifest(d)
        assert [a["status"] for a in m["arms"]] == ["done", "done"]

    def test_failing_arm_classified_without_aborting_rest(
            self, tmp_path, stub, no_signals, capsys):
        d = str(tmp_path / "camp")
        arms = C.expand_arms(
            _matrix(("a1", {}), ("bad", {"NDS_FAKE_MODE": "fail"}),
                    ("a3", {})), d)
        m = C.run_campaign(arms, d, bench_cmd=stub)
        by = {a["name"]: a for a in m["arms"]}
        assert by["a1"]["status"] == "completed"
        assert by["a3"]["status"] == "completed"   # ran despite the fail
        rec = by["bad"]
        assert rec["status"] == "failed" and rec["rc"] == 3
        # the fault-matrix ladder, not an ad-hoc label: the bench-child
        # seam's registered class and recovery policy
        assert rec["classified"]["seam"] == "bench-child"
        assert rec["classified"]["class"] == "transient"
        assert "backoff" in rec["classified"]["recovery"]

    def test_spawn_failure_classified(self, tmp_path, no_signals, capsys):
        d = str(tmp_path / "camp")
        arms = C.expand_arms(_matrix(("a1", {})), d)
        m = C.run_campaign(arms, d,
                           bench_cmd=["/nonexistent-bench-binary"])
        rec = m["arms"][0]
        assert rec["status"] == "failed"
        assert rec["classified"]["seam"] == "bench-child"

    def test_injected_spawn_fault_classified(self, tmp_path, stub,
                                             no_signals, monkeypatch,
                                             capsys):
        # the arm spawn is a REGISTERED seam: the fault-injection matrix
        # can prove the ladder end to end without a real failure
        monkeypatch.setenv("NDS_TPU_FAULT", "bench-child:error:1")
        d = str(tmp_path / "camp")
        arms = C.expand_arms(_matrix(("a1", {}), ("a2", {})), d)
        m = C.run_campaign(arms, d, bench_cmd=stub)
        by = {a["name"]: a for a in m["arms"]}
        assert by["a1"]["status"] == "failed"
        assert by["a1"]["classified"]["seam"] == "bench-child"
        monkeypatch.delenv("NDS_TPU_FAULT")
        assert by["a2"]["status"] == "completed"

    def test_mismatched_arm_refused_campaign_continues(
            self, tmp_path, stub, no_signals, capsys):
        d = str(tmp_path / "camp")
        arms = C.expand_arms(_matrix(("a1", {}), ("a2", {})), d)
        # a1's ledger was recorded under OTHER knobs
        path = C.arm_paths(d, "a1")["ledger"]
        led = L.Ledger(path, stamp={"envFingerprint": "alien-fp"},
                       driver="bench")
        led.query("query1", ms=10.0)
        led.close(None)
        m = C.run_campaign(arms, d, bench_cmd=stub)
        by = {a["name"]: a for a in m["arms"]}
        assert by["a1"]["status"] == "failed"
        assert "fingerprint" in by["a1"]["error"]
        assert "alien-fp" in by["a1"]["error"]     # both fps named
        assert by["a2"]["status"] == "completed"


class TestKillResume:
    def test_sigkill_mid_arm_then_rerun_resumes(self, tmp_path):
        """The acceptance scenario: the campaign process is SIGKILLed
        while arm k2 is mid-flight; rerunning the SAME command skips the
        completed arm (its bench child is never re-invoked) and resumes
        the partial arm off its own ledger — the first segment's
        measured query is never re-paid."""
        d = str(tmp_path / "camp")
        stub_py = tmp_path / "fake_bench.py"
        stub_py.write_text(_STUB.format(repo=REPO))
        matrix_path = tmp_path / "arms.json"
        matrix_path.write_text(json.dumps(_matrix(
            ("k1", {}),
            ("k2", {"NDS_FAKE_MODE": "kill-campaign"}),
            ("k3", {}))))
        calls = tmp_path / "calls.txt"
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("NDS_TPU_", "NDS_BENCH_",
                                    "NDS_CAMPAIGN_", "NDS_FAKE_"))}
        env["NDS_FAKE_CALLS"] = str(calls)
        cmd = [sys.executable, os.path.join(REPO, "tools", "campaign.py"),
               "--matrix", str(matrix_path), "--dir", d,
               "--bench-cmd", f"{sys.executable} {stub_py}"]
        r1 = subprocess.run(cmd, env=env, capture_output=True, text=True,
                            timeout=120)
        assert r1.returncode == -signal.SIGKILL, (r1.stdout, r1.stderr)
        # the kill landed mid-k2: k1 clean-completed, k2's ledger holds
        # exactly the first segment, no terminal record
        k2 = L.load_ledger(C.arm_paths(d, "k2")["ledger"])
        assert k2.times() == {"query1": 100.0} and k2.end is None
        r2 = subprocess.run(cmd, env=env, capture_output=True, text=True,
                            timeout=120)
        assert r2.returncode == 0, (r2.stdout, r2.stderr)
        assert "k1: already completed" in r2.stderr
        assert "k2: resuming off its ledger" in r2.stderr
        # k1 ran ONCE across both invocations; k2 ran twice (kill +
        # resume); k3 ran once (after the resume)
        seq = calls.read_text().splitlines()
        assert seq == ["k1", "k2", "k2", "k3"]
        k2 = L.load_ledger(C.arm_paths(d, "k2")["ledger"])
        assert k2.times() == {"query1": 100.0, "query2": 200.0}
        assert k2.end["status"] == "completed"
        m = C.load_manifest(d)
        assert [a["status"] for a in m["arms"]] == \
            ["done", "completed", "completed"]
        assert m["status"] == "completed"


class TestBenchStamp:
    @pytest.fixture()
    def bench(self):
        spec = importlib.util.spec_from_file_location(
            "bench_mod", os.path.join(REPO, "bench.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_every_record_carries_arm_and_fingerprint(
            self, bench, tmp_path, monkeypatch, capsys):
        """bench.py under a campaign arm stamps provenance into EVERY
        ledger record — the query records AND the terminal end record a
        signal handler writes — so cross-arm merges key on recorded
        provenance, not file paths."""
        monkeypatch.setenv("NDS_BENCH_SEED_BASELINE", "1")
        monkeypatch.setattr(bench, "REPO", str(tmp_path))
        monkeypatch.setattr(bench, "ensure_data", lambda: None)
        monkeypatch.setattr(bench, "bench_queries",
                            lambda: [("query1", "s1"), ("query2", "s2")])
        monkeypatch.setattr(bench, "_emitted", False)
        ledger_path = tmp_path / "campaign.jsonl"
        monkeypatch.setenv("NDS_BENCH_RESULTS_JSONL", str(ledger_path))
        monkeypatch.setenv("NDS_BENCH_HEARTBEAT_S", "0")
        monkeypatch.setenv("NDS_CAMPAIGN_ARM", "pallas-off")
        monkeypatch.setenv("NDS_TPU_PALLAS", "off")

        handlers = {}
        monkeypatch.setattr(bench.signal, "signal",
                            lambda signum, fn:
                            handlers.setdefault(signum, fn))
        monkeypatch.setattr(bench.os, "_exit",
                            lambda code: (_ for _ in ()).throw(
                                SystemExit(code)))

        class OneQueryChild:
            def __init__(self):
                self.proc = None
                self.started = False

            def alive(self):
                return self.started

            def start(self, deadline_left):
                self.started = True
                return {"ready": True, "platform": "tpu"}

            def run_query(self, name, timeout):
                if name == "query1":
                    return {"name": "query1", "ms": 123.0, "hostSyncs": 1,
                            "syncWaitMs": 2.0}
                handlers[bench.signal.SIGTERM](bench.signal.SIGTERM, None)
                raise AssertionError("handler must not return")

            def stop(self):
                pass

        monkeypatch.setattr(bench, "ChildServer", OneQueryChild)
        import time as _time
        with pytest.raises(SystemExit):
            bench.run_parent(_time.perf_counter())
        capsys.readouterr()
        expect_fp = C.env_fingerprint()
        recs = [json.loads(ln) for ln in open(ledger_path)]
        kinds = {r["kind"] for r in recs}
        assert "end" in kinds and "query" in kinds
        for r in recs:
            assert r["arm"] == "pallas-off", r
            assert r["envFingerprint"] == expect_fp, r
        assert "NDS_TPU_PALLAS=off" in expect_fp

    def test_load_resume_refuses_mismatched_fingerprint(
            self, bench, tmp_path, monkeypatch):
        """Satellite: a resumed run under DIFFERENT knobs must refuse
        loudly instead of silently mixing two arms into one artifact —
        CampaignResumeError names both fingerprints."""
        p = tmp_path / "results.jsonl"
        monkeypatch.setenv("NDS_TPU_PALLAS", "auto")
        led = L.Ledger(str(p), stamp=C.campaign_stamp(), driver="bench")
        led.query("query1", ms=10.0)
        led.close(None)
        recorded = C.env_fingerprint()
        monkeypatch.setenv("NDS_TPU_PALLAS", "off")
        with pytest.raises(C.CampaignResumeError) as ei:
            bench.load_resume(str(p), {}, {})
        assert recorded in str(ei.value)
        assert "NDS_TPU_PALLAS=off" in str(ei.value)
        # same knobs: resumes normally
        monkeypatch.setenv("NDS_TPU_PALLAS", "auto")
        times = {}
        bench.load_resume(str(p), times, {})
        assert times == {"query1": 10.0}


def _arm_ledger(path, arm, times, ici=0, stall=0.0, exchange_ms=0.0):
    led = L.Ledger(str(path), stamp={"arm": arm, "envFingerprint": "fp-t"},
                   driver="bench", platform="tpu", scale="10")
    for q, ms in times.items():
        scan = {"chunks": 4, "syncs": 0, "bytesH2d": 1_000_000,
                "path": "compiled", "prefetchStallMs": stall}
        if ici:
            scan["bytesIci"] = ici
            scan["shards"] = 2
            scan["collectives"] = 2
        phases = {"query": {"ms": ms}, "plan": {"ms": ms}}
        if exchange_ms:
            phases["stream.exchange"] = {"ms": exchange_ms}
        led.query(q, ms=ms, hostSyncs=2, streamedScans=[scan],
                  tracePhases={"phases": phases})
    led.close("completed", queries=len(times))
    return str(path)


class TestCrossArm:
    def test_bench_compare_multi_round_table(self, bench_compare,
                                             tmp_path, capsys):
        """Satellite: >2 ledgers render the cross-arm table (labeled by
        RECORDED arm names), while --gate keeps its strict two-round
        contract."""
        paths = [
            _arm_ledger(tmp_path / f"{n}.jsonl", n,
                        {"query1": t, "query2": 2 * t})
            for n, t in (("base", 100.0), ("pallas-off", 150.0),
                         ("prefetch-off", 120.0))]
        rc = bench_compare.main(paths)
        out = capsys.readouterr().out
        assert rc == 0
        assert "cross-arm" in out and "primary = base" in out
        for label in ("base", "pallas-off", "prefetch-off"):
            assert f"| {label} |" in out
        assert "x1.50" in out            # pallas-off mover named
        with pytest.raises(SystemExit) as ei:
            bench_compare.main(paths + ["--gate"])
        assert ei.value.code == 2        # gate stays two-round

    def test_two_round_diff_unchanged(self, bench_compare, tmp_path,
                                      capsys):
        a = _arm_ledger(tmp_path / "a.jsonl", "base", {"query1": 100.0})
        b = _arm_ledger(tmp_path / "b.jsonl", "arm-b", {"query1": 100.0})
        assert bench_compare.main([a, b, "--gate"]) == 0
        assert "geomean" in capsys.readouterr().out

    def test_report_renders_named_deltas(self, campaign_tool, tmp_path,
                                         capsys):
        """Acceptance: the merged cross-arm report renders the fused/
        prefetch/shard delta lines and the static-roofline column from
        the arm ledgers alone."""
        d = str(tmp_path / "camp")
        arms = C.expand_arms(
            _matrix(("base", {}), ("pallas-off", {}),
                    ("prefetch-off", {}), ("shards-2", {})), d)
        specs = {
            "base": dict(times={"query1": 100.0, "query2": 50.0},
                         stall=5.0),
            "pallas-off": dict(times={"query1": 160.0, "query2": 80.0}),
            "prefetch-off": dict(times={"query1": 130.0, "query2": 60.0},
                                 stall=0.0),
            "shards-2": dict(times={"query1": 90.0, "query2": 45.0},
                             ici=50_000_000, exchange_ms=10.0),
        }
        for a in arms:
            path = C.arm_paths(d, a.name)["ledger"]
            os.makedirs(os.path.dirname(path), exist_ok=True)
            _arm_ledger(path, a.name, **specs[a.name])
        lines = campaign_tool.report_lines(arms, d, "base")
        text = "\n".join(lines)
        assert "| base |" in text and "primary = base" in text
        assert "pallas-kernel delta" in text and "x1.60" in text
        assert "prefetch overlap delta" in text
        assert "# shard scaling: shards-2" in text
        assert "static-roofline %" in text       # column present
        assert "ici GB/s" in text
        # ici GB/s = 50 MB over 10 ms exchange wall = 5.0 GB/s
        assert "| 5.0 |" in text

    def test_report_written_to_campaign_dir(self, campaign_tool, stub,
                                            tmp_path, monkeypatch,
                                            capsys, no_signals):
        d = str(tmp_path / "camp")
        matrix = tmp_path / "m.json"
        matrix.write_text(json.dumps(_matrix(("base", {}))))
        rc = campaign_tool.main(
            ["--matrix", str(matrix), "--dir", d,
             "--bench-cmd", " ".join(stub)])
        assert rc == 0
        assert os.path.exists(os.path.join(d, "report.md"))
        assert "| base |" in open(os.path.join(d, "report.md")).read()


class TestCLI:
    def test_dry_run_prints_exact_matrix(self, campaign_tool, capsys):
        """Acceptance: --preset sf10-full --dry-run prints every arm
        with its env overlay, fingerprint and ledger path, and runs
        nothing."""
        assert campaign_tool.main(["--preset", "sf10-full",
                                   "--dry-run"]) == 0
        out = capsys.readouterr().out
        assert "9 arms" in out
        for arm in ("base", "pallas-off", "prefetch-off", "store-cold",
                    "encoded-off", "shards-1", "shards-2", "shards-4",
                    "shards-8"):
            assert f"arm {arm}\n" in out
        assert "NDS_TPU_PALLAS=off" in out
        assert "NDS_TPU_STREAM_SHARDS=8" in out
        assert "NDS_TPU_CHUNK_STORE=<unset>" in out     # store-cold
        assert "fingerprint: " in out and "ledger: " in out

    def test_unknown_preset_refused(self, campaign_tool, capsys):
        assert campaign_tool.main(["--preset", "nope",
                                   "--dry-run"]) == 2
        assert "unknown preset" in capsys.readouterr().err

    def test_list_presets(self, campaign_tool, capsys):
        assert campaign_tool.main(["--list-presets"]) == 0
        out = capsys.readouterr().out
        assert "sf10-full: 9 arms" in out
