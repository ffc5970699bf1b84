# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Bench harness policy tests (no device work)."""

import importlib.util
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

spec = importlib.util.spec_from_file_location(
    "bench_mod", os.path.join(REPO, "bench.py"))
bench = importlib.util.module_from_spec(spec)
spec.loader.exec_module(bench)


def _times(ms, n, start=0):
    return {f"query{i}": float(ms) for i in range(start, start + n)}


@pytest.fixture(autouse=True)
def _allow_seed(monkeypatch):
    # tests exercise lineage mechanics from scratch; production refuses a
    # missing baseline unless seeding is explicit (see the refusal test)
    monkeypatch.setenv("NDS_BENCH_SEED_BASELINE", "1")


class TestResolveBaseline:
    def test_first_full_run_writes_baseline(self, tmp_path):
        f = tmp_path / "base.json"
        vs = bench.resolve_baseline(str(f), _times(100, 99), 99)
        assert vs == 1.0
        assert json.load(open(f))["n_queries"] == 99

    def test_missing_baseline_refused_without_explicit_seed(
            self, tmp_path, monkeypatch):
        """Losing the committed lineage must be LOUD, not a silent
        restart: vs_baseline degrades to 0.0 and nothing is written
        (round-3 verdict weak #1)."""
        monkeypatch.delenv("NDS_BENCH_SEED_BASELINE", raising=False)
        f = tmp_path / "base.json"
        vs = bench.resolve_baseline(str(f), _times(100, 99), 99)
        assert vs == 0.0
        assert not f.exists()

    def test_note_field_survives_merge(self, tmp_path):
        f = tmp_path / "base.json"
        bench.resolve_baseline(str(f), _times(100, 95), 99)
        d = json.load(open(f))
        d["note"] = "lineage provenance"
        json.dump(d, open(f, "w"))
        bench.resolve_baseline(str(f), _times(90, 99), 99)
        assert json.load(open(f))["note"] == "lineage provenance"

    def test_same_set_compares(self, tmp_path):
        f = tmp_path / "base.json"
        bench.resolve_baseline(str(f), _times(100, 99), 99)
        vs = bench.resolve_baseline(str(f), _times(50, 99), 99)
        assert abs(vs - 2.0) < 1e-9            # 2x faster than baseline

    def test_partial_run_compares_common_set_without_overwriting(self, tmp_path):
        f = tmp_path / "base.json"
        bench.resolve_baseline(str(f), _times(100, 99), 99)
        vs = bench.resolve_baseline(str(f), _times(10, 95), 99)  # wedged chunk
        assert abs(vs - 10.0) < 1e-9   # geomean over the 95 common queries
        assert abs(json.load(open(f))["value"] - 100.0) < 1e-6   # no clobber
        assert abs(bench.resolve_baseline(str(f), _times(100, 99), 99)
                   - 1.0) < 1e-9

    def test_faster_partial_with_more_queries_never_clobbers(self, tmp_path):
        # a later, slower run that happens to measure MORE queries must not
        # replace existing first-recorded entries, only fill in new ones
        f = tmp_path / "base.json"
        bench.resolve_baseline(str(f), _times(100, 95), 99)
        bench.resolve_baseline(str(f), _times(200, 96), 99)
        base = json.load(open(f))["times"]
        assert len(base) == 96
        assert base["query0"] == 100.0       # first recording kept
        assert base["query95"] == 200.0      # gap filled

    def test_disjoint_partial_is_neutral(self, tmp_path):
        f = tmp_path / "base.json"
        bench.resolve_baseline(str(f), _times(100, 50), 50)
        vs = bench.resolve_baseline(str(f), _times(10, 5, start=90), 99)
        assert vs == 1.0                       # nothing comparable

    def test_ratchet_growth_extends_baseline(self, tmp_path):
        f = tmp_path / "base.json"
        bench.resolve_baseline(str(f), _times(100, 80), 80)
        vs = bench.resolve_baseline(str(f), _times(120, 99), 99)  # set grew
        assert abs(vs - 100.0 / 120.0) < 1e-9  # compared over 80 common
        assert json.load(open(f))["n_queries"] == 99

    def test_legacy_value_only_baseline_is_migrated(self, tmp_path):
        f = tmp_path / "base.json"
        json.dump({"value": 100.0, "n_queries": 99}, open(f, "w"))
        vs = bench.resolve_baseline(str(f), _times(50, 99), 99)
        assert vs == 1.0                      # nothing comparable yet
        assert json.load(open(f))["times"]    # migrated to per-query format
        vs2 = bench.resolve_baseline(str(f), _times(25, 99), 99)
        assert abs(vs2 - 2.0) < 1e-9


def test_load_resume_prepopulates_and_skips(tmp_path):
    """A results JSONL from an interrupted campaign must pre-load times
    and perf (at-scale runs are resumable; round-4 SF10 lost 30 measured
    queries to a budget kill)."""
    p = tmp_path / "results.jsonl"
    with open(p, "w") as f:
        f.write(json.dumps({"name": "query3", "ms": 1234.5,
                            "hostSyncs": 2, "warmS": 9.8,
                            "compileS": 7.7}) + "\n")
        f.write("not json\n")                        # tolerated garbage
        f.write(json.dumps({"name": "query9", "error": "boom"}) + "\n")
    times, perf = {}, {}
    assert bench.load_resume(str(p), times, perf) is None
    assert times == {"query3": 1234.5}
    assert perf["query3"]["compileS"] == 7.7
    assert "query9" not in times                     # errors not resumed


def test_load_resume_recovers_platform(tmp_path):
    """A rerun satisfied entirely from the resume file never starts a
    child — load_resume must return the original campaign's platform meta
    line so PERF.md's provenance doesn't regress to 'unknown'."""
    p = tmp_path / "results.jsonl"
    with open(p, "w") as f:
        f.write(json.dumps({"name": "query1", "ms": 10.0,
                            "hostSyncs": 1}) + "\n")
        f.write(json.dumps({"platform": "tpu"}) + "\n")
    times, perf = {}, {}
    assert bench.load_resume(str(p), times, perf) == "tpu"
    assert times == {"query1": 10.0}


def test_bench_queries_names_match_stream_names():
    queries = bench.bench_queries()
    names = [n for n, _ in queries]
    assert len(names) == len(set(names))
    assert all(n.startswith("query") for n in names)
    # the four split templates surface as _part1/_part2 names
    if len(names) > 1:
        assert "query14_part1" in names and "query14_part2" in names


def test_first_partial_run_seeds_baseline(tmp_path):
    """A query that can never run (OOM-bound outlier) must not block
    baselining forever: the first run seeds whatever it measured."""
    f = tmp_path / "base.json"
    vs = bench.resolve_baseline(str(f), _times(100, 102), 103)
    assert vs == 1.0
    assert len(json.load(open(f))["times"]) == 102
    assert json.load(open(f))["n_queries"] == 102   # what was measured
    vs2 = bench.resolve_baseline(str(f), _times(50, 102), 103)
    assert abs(vs2 - 2.0) < 1e-9


def test_derive_budgets_from_baseline(tmp_path):
    """Per-query budgets: baseline wall x headroom, clamped to
    [floor, cap]; queries with no history keep the cap (their first
    measurement must not be killed by a budget nobody derived)."""
    f = tmp_path / "base.json"
    json.dump({"times": {"q_cheap": 10.0, "q_mid": 2000.0,
                         "q_heavy": 200000.0}}, open(f, "w"))
    budgets = bench.derive_budgets(
        ["q_cheap", "q_mid", "q_heavy", "q_new"], str(f),
        headroom=30.0, floor_s=30.0, cap_s=100.0)
    assert budgets["q_cheap"] == 30.0        # floor absorbs cold compile
    assert budgets["q_mid"] == 60.0          # 2 s x 30
    assert budgets["q_heavy"] == 100.0       # capped at the old allowance
    assert budgets["q_new"] == 100.0         # no history -> cap
    # a missing/unreadable baseline derives nothing: every query keeps
    # the cap (never a zero budget)
    budgets = bench.derive_budgets(["q1"], str(tmp_path / "nope.json"),
                                   headroom=30.0, floor_s=30.0,
                                   cap_s=100.0)
    assert budgets == {"q1": 100.0}


def test_derive_budgets_off_at_foreign_scale(tmp_path, monkeypatch):
    """The committed baseline is bench-scale (0.05) history: at SF10 the
    walls are incommensurable (minutes/query), so derivation must stay
    OFF — every query keeps the cap — unless the operator sets the
    headroom explicitly for that campaign."""
    monkeypatch.delenv("NDS_BENCH_BUDGET_HEADROOM", raising=False)
    f = tmp_path / "base.json"
    json.dump({"times": {"q1": 800.0}}, open(f, "w"))
    assert bench.derive_budgets(["q1"], str(f), floor_s=30.0, cap_s=400.0,
                                scale="10") == {"q1": 400.0}
    # bench scale: derivation active
    assert bench.derive_budgets(["q1"], str(f), floor_s=30.0, cap_s=400.0,
                                scale="0.05") == {"q1": 30.0}
    # explicit opt-in at scale: active again
    monkeypatch.setenv("NDS_BENCH_BUDGET_HEADROOM", "200")
    assert bench.derive_budgets(["q1"], str(f), floor_s=30.0, cap_s=400.0,
                                scale="10") == {"q1": 160.0}


def test_budget_enforcement_hung_child(tmp_path, monkeypatch, capsys):
    """A run that ended at rc 124 with no value, pinned as a regression:
    one query hangs past its DERIVED budget — the round must finish, with that
    query marked ``timeout`` in the ledger, a NON-NULL geomean over the
    completed queries, and finalize()'s output complete (PERF.md + a
    terminal ``completed`` record; the hang cost its budget, not the
    campaign)."""
    monkeypatch.setattr(bench, "REPO", str(tmp_path))
    monkeypatch.setattr(bench, "ensure_data", lambda: None)
    monkeypatch.setattr(bench, "bench_queries",
                        lambda: [("query1", "s1"), ("query2", "s2"),
                                 ("query3", "s3")])
    monkeypatch.setattr(bench, "_emitted", False)
    json.dump({"times": {"query1": 100.0, "query2": 1000.0,
                         "query3": 100.0}},
              open(tmp_path / "BASELINE_TIMES.json", "w"))
    ledger_path = tmp_path / "campaign.jsonl"
    monkeypatch.setenv("NDS_BENCH_RESULTS_JSONL", str(ledger_path))
    monkeypatch.setenv("NDS_BENCH_BUDGET_FLOOR_S", "5")
    monkeypatch.setenv("NDS_BENCH_BUDGET_HEADROOM", "2")
    monkeypatch.setenv("NDS_BENCH_HEARTBEAT_S", "0")   # deterministic file

    deadlines = {}

    class HangingChild:
        def __init__(self):
            self.proc = None
            self.started = False

        def alive(self):
            return self.started

        def start(self, deadline_left):
            self.started = True
            return {"ready": True, "platform": "tpu"}

        def run_query(self, name, timeout):
            deadlines.setdefault(name, timeout)
            if name == "query2":
                return None        # hung in-flight: supervisor's timeout
            return {"name": name, "ms": 100.0, "hostSyncs": 1,
                    "syncWaitMs": 1.0}

        def stop(self):
            self.started = False   # the hung child gets killed

    monkeypatch.setattr(bench, "ChildServer", HangingChild)
    import time as _time
    bench.run_parent(_time.perf_counter())
    out = capsys.readouterr()
    msg = json.loads(out.out.strip().splitlines()[-1])
    assert msg["n_queries"] == 2
    assert msg["value"] == pytest.approx(100.0)        # non-null geomean
    assert "aborted" not in msg                        # the round FINISHED
    # the derived budget was enforced: query2's baseline wall (1 s) x
    # headroom 2 = 2 s, floored at 5 s — not the 420 s global cap
    assert deadlines["query2"] == pytest.approx(5.0)
    assert "timeout after 5s (budget)" in out.err
    data = bench.ledger_mod().load_ledger(str(ledger_path))
    assert data.queries["query2"]["status"] == "timeout"
    assert [r["status"] for r in data.attempts
            if r["name"] == "query2"] == ["timeout", "timeout"]
    assert data.queries["query2"]["budgetS"] == pytest.approx(5.0)
    assert data.times() == {"query1": 100.0, "query3": 100.0}
    assert data.complete() and data.end["status"] == "completed"
    assert data.end["queries"] == 2 and data.end["platform"] == "tpu"
    assert "query1" in open(tmp_path / "chiprun_out" / "BENCH_PERF.md").read()


def test_setup_timeout_circuit_breaker(monkeypatch, capsys):
    """Two consecutive child-setup failures must trip the breaker: stop
    burning budget and emit a LABELED partial artifact (a run once spent
    its entire 3000s on six 300s setup timeouts, yielding n_queries: 0
    with no indication why)."""
    starts = []

    class DeadChild:
        def __init__(self):
            self.proc = None

        def alive(self):
            return False

        def start(self, deadline_left):
            starts.append(deadline_left)
            return None                         # setup timeout / dead child

        def stop(self):
            pass

    monkeypatch.setattr(bench, "ChildServer", DeadChild)
    monkeypatch.setattr(bench, "ensure_data", lambda: None)
    monkeypatch.setattr(bench, "bench_queries",
                        lambda: [("query1", "select 1")])
    monkeypatch.setattr(bench, "_emitted", False)
    import time as _time
    with pytest.raises(SystemExit):
        bench.run_parent(_time.perf_counter())
    assert len(starts) == 2, "breaker must trip after exactly 2 failures"
    out = capsys.readouterr()
    msg = json.loads(out.out.strip().splitlines()[-1])
    assert msg["n_queries"] == 0
    assert msg["aborted"] == "child-setup-failure"
    assert "failing fast" in out.err


def test_external_timeout_flushes_partial_geomean(tmp_path, monkeypatch,
                                                  capsys):
    """An external `timeout` kill (rc=124) mid-campaign must still record
    the partial geomean of every COMPLETED query — PERF.md + metric line
    — not {"value": null, "n_queries": 0}. Simulated: the
    child serves query1, then the SIGTERM handler fires while query2 is
    in flight. The handler must also close the ledger with a terminal
    ``aborted`` record (reason: signal) so the artifact is
    self-describing — a resume sees query1 done, query2 unfinished."""
    monkeypatch.setattr(bench, "REPO", str(tmp_path))
    monkeypatch.setattr(bench, "ensure_data", lambda: None)
    monkeypatch.setattr(bench, "bench_queries",
                        lambda: [("query1", "select 1"),
                                 ("query2", "select 2")])
    monkeypatch.setattr(bench, "_emitted", False)
    ledger_path = tmp_path / "campaign.jsonl"
    monkeypatch.setenv("NDS_BENCH_RESULTS_JSONL", str(ledger_path))
    monkeypatch.setenv("NDS_BENCH_HEARTBEAT_S", "0")

    handlers = {}
    monkeypatch.setattr(bench.signal, "signal",
                        lambda signum, fn: handlers.setdefault(signum, fn))

    def fake_exit(code):
        raise SystemExit(code)

    monkeypatch.setattr(bench.os, "_exit", fake_exit)

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
            # query2 in flight when the external timeout lands
            handlers[bench.signal.SIGTERM](bench.signal.SIGTERM, None)
            raise AssertionError("handler must not return")

        def stop(self):
            pass

    monkeypatch.setattr(bench, "ChildServer", OneQueryChild)
    import time as _time
    with pytest.raises(SystemExit):
        bench.run_parent(_time.perf_counter())
    out = capsys.readouterr()
    msg = json.loads(out.out.strip().splitlines()[-1])
    assert msg["n_queries"] == 1
    assert msg["value"] == pytest.approx(123.0)
    perf_text = open(tmp_path / "chiprun_out" / "BENCH_PERF.md").read()
    assert "query1" in perf_text and "platform: tpu." in perf_text
    # terminal ledger record: the kill is labeled, not inferred
    data = bench.ledger_mod().load_ledger(str(ledger_path))
    assert data.times() == {"query1": 123.0}
    assert data.complete() and data.end["status"] == "aborted"
    assert data.end["reason"] == "signal"
    assert data.end["queries"] == 1 and data.end["platform"] == "tpu"


def test_round_budget_exhaustion_labeled_truthfully(tmp_path, monkeypatch,
                                                    capsys):
    """A healthy query killed because the ROUND's budget ran out must be
    labeled 'round-budget', not blamed on a per-query budget that never
    limited it (the ledger is the durable post-hoc record — the cause
    must be the real one)."""
    monkeypatch.setattr(bench, "REPO", str(tmp_path))
    monkeypatch.setattr(bench, "ensure_data", lambda: None)
    monkeypatch.setattr(bench, "bench_queries",
                        lambda: [("query1", "s1")])
    monkeypatch.setattr(bench, "_emitted", False)
    # round budget leaves ~8s; the per-query floor is far larger, so the
    # deadline is the round remainder, not the derived budget
    monkeypatch.setenv("NDS_BENCH_BUDGET_S", "28")
    monkeypatch.setenv("NDS_BENCH_RESULTS_JSONL",
                       str(tmp_path / "led.jsonl"))
    monkeypatch.setenv("NDS_BENCH_HEARTBEAT_S", "0")

    class HungChild:
        def __init__(self):
            self.proc = None
            self.started = False

        def alive(self):
            return self.started

        def start(self, deadline_left):
            self.started = True
            return {"ready": True, "platform": "tpu"}

        def run_query(self, name, timeout):
            return None                  # hung until the deadline

        def stop(self):
            self.started = False

    monkeypatch.setattr(bench, "ChildServer", HungChild)
    import time as _time
    with pytest.raises(SystemExit):      # nothing measured -> exit 1
        bench.run_parent(_time.perf_counter())
    err = capsys.readouterr().err
    assert "(round-budget)" in err and "(budget)" not in err
    data = bench.ledger_mod().load_ledger(str(tmp_path / "led.jsonl"))
    assert data.queries["query1"]["status"] == "timeout"
    assert "round-budget" in data.queries["query1"]["error"]


def test_round_with_hang_and_sigterm_still_yields_ledger(
        tmp_path, monkeypatch, capsys):
    """The acceptance scenario end to end: ONE round suffers an injected
    hang (query2 blows its derived budget) AND an injected SIGTERM
    (while query4 is in flight) — and still produces a complete ledger
    (timeout attempt + terminal aborted record), a non-null geomean over
    the completed queries, and a regenerated PERF.md."""
    monkeypatch.setattr(bench, "REPO", str(tmp_path))
    monkeypatch.setattr(bench, "ensure_data", lambda: None)
    monkeypatch.setattr(bench, "bench_queries",
                        lambda: [(f"query{i}", f"s{i}")
                                 for i in (1, 2, 3, 4)])
    monkeypatch.setattr(bench, "_emitted", False)
    json.dump({"times": {f"query{i}": 100.0 * i for i in (1, 2, 3, 4)}},
              open(tmp_path / "BASELINE_TIMES.json", "w"))
    ledger_path = tmp_path / "campaign.jsonl"
    monkeypatch.setenv("NDS_BENCH_RESULTS_JSONL", str(ledger_path))
    monkeypatch.setenv("NDS_BENCH_BUDGET_FLOOR_S", "5")
    monkeypatch.setenv("NDS_BENCH_HEARTBEAT_S", "0")

    handlers = {}
    monkeypatch.setattr(bench.signal, "signal",
                        lambda signum, fn: handlers.setdefault(signum, fn))

    def fake_exit(code):
        raise SystemExit(code)

    monkeypatch.setattr(bench.os, "_exit", fake_exit)

    class ChaosChild:
        def __init__(self):
            self.proc = None
            self.started = False

        def alive(self):
            return self.started

        def start(self, deadline_left):
            self.started = True
            return {"ready": True, "platform": "tpu"}

        def run_query(self, name, timeout):
            if name == "query2":
                return None              # the injected hang
            if name == "query4":
                # the injected external kill, mid-flight
                handlers[bench.signal.SIGTERM](bench.signal.SIGTERM, None)
                raise AssertionError("handler must not return")
            return {"name": name, "ms": 100.0, "hostSyncs": 1,
                    "syncWaitMs": 1.0}

        def stop(self):
            self.started = False

    monkeypatch.setattr(bench, "ChildServer", ChaosChild)
    import time as _time
    with pytest.raises(SystemExit):
        bench.run_parent(_time.perf_counter())
    out = capsys.readouterr()
    msg = json.loads(out.out.strip().splitlines()[-1])
    assert msg["n_queries"] == 2
    assert msg["value"] == pytest.approx(100.0)        # non-null geomean
    data = bench.ledger_mod().load_ledger(str(ledger_path))
    assert data.times() == {"query1": 100.0, "query3": 100.0}
    assert data.queries["query2"]["status"] == "timeout"
    assert data.complete() and data.end["status"] == "aborted"
    assert data.end["reason"] == "signal" and data.end["queries"] == 2
    perf_text = open(tmp_path / "chiprun_out" / "BENCH_PERF.md").read()
    assert "query1" in perf_text and "query3" in perf_text


def test_write_perf_stamps_platform_and_streamed(tmp_path, monkeypatch):
    """PERF.md header carries the measured jax platform (provenance) and
    the streamed->HBM scan path aggregate when any query streamed."""
    monkeypatch.setattr(bench, "REPO", str(tmp_path))
    times = {"query1": 100.0, "query2": 50.0}
    perf = {
        "query1": {"hostSyncs": 2, "syncWaitMs": 5.0,
                   "streamedScans": [
                       {"table": "store_sales", "chunks": 12, "syncs": 1,
                        "path": "compiled"},
                       {"table": "catalog_sales", "chunks": 4, "syncs": 9,
                        "path": "eager", "reason": "not chunk-invariant"}]},
        "query2": {"hostSyncs": 1, "syncWaitMs": 1.0},
    }
    bench.write_perf(times, perf, platform="tpu")
    text = open(tmp_path / "chiprun_out" / "BENCH_PERF.md").read()
    assert "platform: tpu." in text
    assert "attached chip" not in text
    assert "Streamed >HBM scans: 2 (1 compiled chunk pipeline, "\
           "1 eager fallback)." in text


def test_collect_sf10_failure_capture_excludes_restart_suffix(tmp_path):
    """The abort-regex capture must stop at the cause: the launcher's
    '; restarting child' suffix is launcher noise, not failure reason
    (ADVICE.md round-5 item 4)."""
    spec2 = importlib.util.spec_from_file_location(
        "collect_sf10", os.path.join(REPO, "tools", "collect_sf10.py"))
    collect = importlib.util.module_from_spec(spec2)
    spec2.loader.exec_module(collect)
    jsonl = tmp_path / "results.jsonl"
    jsonl.write_text(json.dumps({"name": "query1", "ms": 1234.5}) + "\n")
    log = tmp_path / "stderr.log"
    log.write_text(
        "# query9 aborted (timeout after 600s); restarting child\n"
        "# query70 failed: ExecError boom; restarting child\n"
        "# query88 failed: plain failure line\n")
    out = tmp_path / "SF10.json"
    argv = sys.argv
    sys.argv = ["collect_sf10.py", str(jsonl), str(log), str(out)]
    try:
        collect.main()
    finally:
        sys.argv = argv
    doc = json.load(open(out))
    assert doc["queries"]["query1"]["timed_s"] == 1.234
    assert doc["failures"]["query9"] == "(timeout after 600s)"
    assert doc["failures"]["query70"] == "ExecError boom"
    assert doc["failures"]["query88"] == "plain failure line"


def test_restart_backoff_deterministic_and_jittered(monkeypatch):
    """The jittered backoff between child restarts (the bench-child
    seam's spacing policy): zero before the FIRST start, exponential +
    deterministic hash-jitter afterwards — the same index always yields
    the same delay (tests and wall bounds hold), 0 disables."""
    monkeypatch.setenv("NDS_BENCH_RESTART_BACKOFF_S", "1.0")
    assert bench.restart_backoff_s(1) == 0.0
    b2, b3, b4 = (bench.restart_backoff_s(n) for n in (2, 3, 4))
    assert 1.0 <= b2 <= 1.5 and 2.0 <= b3 <= 3.0 and 4.0 <= b4 <= 6.0
    assert bench.restart_backoff_s(2) == b2, "jitter must be deterministic"
    assert bench.restart_backoff_s(20) <= 30.0, "backoff must cap"
    monkeypatch.setenv("NDS_BENCH_RESTART_BACKOFF_S", "0")
    assert bench.restart_backoff_s(5) == 0.0


def test_restart_backoff_applied_between_restarts(monkeypatch, capsys):
    """The parent loop backs off (visibly) between consecutive child
    restarts before the 2-strike breaker trips."""
    monkeypatch.setenv("NDS_BENCH_RESTART_BACKOFF_S", "0.01")

    class DeadChild:
        def __init__(self):
            self.proc = None

        def alive(self):
            return False

        def start(self, deadline_left):
            return None

        def stop(self):
            pass

    monkeypatch.setattr(bench, "ChildServer", DeadChild)
    monkeypatch.setattr(bench, "ensure_data", lambda: None)
    monkeypatch.setattr(bench, "bench_queries",
                        lambda: [("query1", "select 1")])
    monkeypatch.setattr(bench, "_emitted", False)
    import time as _time
    with pytest.raises(SystemExit):
        bench.run_parent(_time.perf_counter())
    err = capsys.readouterr().err
    assert "backing off" in err, "no backoff between restarts"
    assert "failing fast" in err, "breaker must still trip"


def test_bench_child_fault_injection_degrades_to_restart_path(monkeypatch):
    """The bench-child seam: an injected start fault takes the same path
    as a real setup failure (start returns None — the caller's backoff +
    breaker own the recovery) and records the FaultEvent."""
    F = bench.faults_mod()
    F.reset_fault_counts()
    F.drain_fault_events()
    monkeypatch.setenv("NDS_TPU_FAULT", "bench-child:error:1")
    try:
        cs = bench.ChildServer()
        assert cs.start(5.0) is None, "injected start fault must degrade"
        events = F.drain_fault_events()
        assert [(e.seam, e.action) for e in events] == \
            [("bench-child", "degrade")], events
    finally:
        F.reset_fault_counts()


def test_heartbeat_survives_beat_exception(tmp_path):
    """A heartbeat-thread exception must record a ledger progress note
    and CONTINUE beating — a silently dead liveness thread would
    un-detect the very hangs it exists to surface."""
    import time as _time
    lm = bench.ledger_mod()
    path = str(tmp_path / "l.jsonl")
    led = lm.Ledger(path, driver="bench")
    hb = lm.Heartbeat(0.05, ledger=led, out=None)
    orig = led.progress
    calls = {"n": 0}

    def flaky(**fields):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("beat bug")      # escapes beat()
        return orig(**fields)

    led.progress = flaky
    hb.start()
    deadline = _time.monotonic() + 10.0
    while (hb.beats < 3 or hb._survived < 1) and \
            _time.monotonic() < deadline:
        _time.sleep(0.02)
    hb.stop()
    led.close(None)
    assert hb._survived >= 1, "loop never saw the exception"
    assert hb.beats >= 3, "heartbeat died instead of continuing"
    recs = [rec for _ln, rec in lm.iter_ledger(path)
            if rec["kind"] == "progress"]
    notes = [r for r in recs if r.get("note") == "heartbeat-exception"]
    assert notes and "beat bug" in notes[0]["error"], \
        "exception note must land in the ledger"
    assert any("beat" in r for r in recs if r is not notes[0]), \
        "beats must continue after the note"


def test_ledger_write_fault_retries_then_degrades(tmp_path, monkeypatch):
    """The ledger-write seam: one injected write fault recovers through
    the bounded retry (record lands, zero write_failures); a persistent
    failure degrades — record dropped with a note, campaign continues."""
    lm = bench.ledger_mod()
    F = bench.faults_mod()
    path = str(tmp_path / "l.jsonl")
    led = lm.Ledger(path, driver="bench")
    F.reset_fault_counts()
    monkeypatch.setenv("NDS_TPU_FAULT", "ledger-write:error:1")
    led.query("query1", status="ok", ms=1.0)
    monkeypatch.delenv("NDS_TPU_FAULT")
    F.reset_fault_counts()
    assert led.write_failures == 0, "one injected fault must retry clean"
    data = lm.load_ledger(path)
    assert "query1" in data.queries, "retried record must persist"
    # persistent failure: every attempt raises -> degrade, keep serving
    real_open_write = led._f.write

    def broken(_s):
        raise OSError("disk full")

    led._f.write = broken
    led.query("query2", status="ok", ms=2.0)
    assert led.write_failures == 1, "persistent failure must degrade"
    led._f.write = real_open_write
    led.query("query3", status="ok", ms=3.0)
    led.close("completed")
    data = lm.load_ledger(path)
    assert "query3" in data.queries and "query2" not in data.queries


def test_server_error_result_drains_fault_events():
    """The serving loop's FAILURE path must drain the thread's fault
    ring into the failed query's own result line: left behind, a failed
    query's events (incl. the watchdog's `timeout`) would misattribute
    to the NEXT query's success-path drain."""
    from nds_tpu.engine import faults as F
    F.drain_fault_events()
    F.record_fault_event("sync", "timeout", detail="blocked")
    out = bench.error_result("query9", F.StatementTimeout("sync", "late"))
    assert out["timeout"] is True
    assert [e["seam"] for e in out["faultEvents"]] == ["sync"]
    assert not F.drain_fault_events(), \
        "the failure path must leave the ring EMPTY for the next query"
    # and a plain error with no events carries neither key
    out2 = bench.error_result("query10", ValueError("boom"))
    assert "faultEvents" not in out2 and "timeout" not in out2


def test_drain_parent_faults_ledgers_bench_child_events(tmp_path):
    """bench-child seam evidence is recorded in the PARENT's ring (the
    child is the thing that failed): run_parent's drain must land it in
    the campaign ledger as a progress note, not let it die in the
    ring."""
    lm = bench.ledger_mod()
    F = bench.faults_mod()
    F.drain_fault_events()
    path = str(tmp_path / "l.jsonl")
    led = lm.Ledger(path, driver="bench")
    F.record_fault_event("bench-child", "degrade", detail="injected")
    events = bench.drain_parent_faults(led)
    led.close(None)
    assert [(e.seam, e.action) for e in events] == \
        [("bench-child", "degrade")]
    assert not F.drain_fault_events(), "ring must be drained"
    recs = [rec for _ln, rec in lm.iter_ledger(path)
            if rec["kind"] == "progress"]
    (note,) = [r for r in recs if r.get("note") == "fault-event"]
    assert note["seam"] == "bench-child" and note["action"] == "degrade"
    # ledger off: events still drain (no misattribution), none written
    F.record_fault_event("bench-child", "degrade")
    assert len(bench.drain_parent_faults(None)) == 1
