# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Campaign evidence ledger (nds_tpu/obs/ledger.py) and its consumers:
schema round-trip, version/torn-line handling, the heartbeat, and the
tools/bench_compare.py diff/gate/emit-perf/evidence-audit surface."""

import importlib.util
import io
import json
import os

import pytest

from nds_tpu.obs import ledger as L

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name, relpath):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def bench_compare():
    return _load_tool("bench_compare_mod", "tools/bench_compare.py")


# ---------------------------------------------------------------------------
# schema round-trip
# ---------------------------------------------------------------------------


def test_ledger_round_trip(tmp_path):
    """write -> load -> validate: every record kind survives, evidence
    is derived from streamedScans, ok-wins-over-timeout resume
    semantics, and the terminal record closes the campaign."""
    p = tmp_path / "campaign.jsonl"
    led = L.Ledger(str(p), driver="bench", platform="tpu", scale="10")
    led.query("query1", status="ok", ms=123.4, hostSyncs=3,
              streamedScans=[
                  {"table": "store_sales", "chunks": 10, "syncs": 2,
                   "path": "compiled", "bytesH2d": 1000, "rows": 50,
                   "partitions": 2, "partRows": [30, 20]},
                  {"table": "catalog_sales", "chunks": 4, "syncs": 9,
                   "path": "eager", "reason": "not chunk-invariant"}])
    led.query("query2", status="timeout", error="timeout after 90s",
              budgetS=90.0)
    led.query("query2", status="ok", ms=80.0)        # retry succeeded
    led.progress(query="query3", done=2, total=3)
    led.close("completed", queries=2, wallS=200.0)

    data = L.load_ledger(str(p))
    assert data.platform == "tpu"
    assert data.meta["scale"] == "10"
    assert data.complete() and data.end["status"] == "completed"
    assert data.end["queries"] == 2
    assert data.progress == 1
    assert not data.torn
    assert data.times() == {"query1": 123.4, "query2": 80.0}
    ev = data.queries["query1"]["evidence"]
    assert ev["scans"] == 2 and ev["compiled"] == 1 and ev["eager"] == 1
    assert ev["syncs"] == 11 and ev["bytesH2d"] == 1000
    assert ev["partitions"] == 2
    assert ev["fallbackReasons"] == ["not chunk-invariant"]
    # the retry history is preserved even though ok wins
    assert [r["status"] for r in data.attempts
            if r["name"] == "query2"] == ["timeout", "ok"]


def test_unknown_version_rejected(tmp_path):
    """A ledger from a FUTURE schema must refuse loudly — silently
    misreading fields would corrupt a resume or a comparison."""
    p = tmp_path / "future.jsonl"
    p.write_text(json.dumps({"v": 99, "kind": "query", "t": 0,
                             "name": "q", "status": "ok"}) + "\n")
    with pytest.raises(L.LedgerError, match="version 99"):
        L.load_ledger(str(p))


def test_unknown_metrics_version_rejected(tmp_path):
    """A ``metrics`` record whose metricsV is not the pinned rollup
    schema must refuse loudly — quantile/bucket fields from a future
    shape silently misread would poison cross-arm rollups. A valid-
    version record loads into ``data.metrics`` (legacy ledgers simply
    leave it empty)."""
    p = tmp_path / "metrics.jsonl"
    p.write_text(json.dumps({"v": 1, "kind": "metrics", "t": 0,
                             "scope": "query", "metricsV": 99}) + "\n")
    with pytest.raises(L.LedgerError, match="metrics record version 99"):
        L.load_ledger(str(p))
    p.write_text(json.dumps({"v": 1, "kind": "metrics", "t": 0,
                             "scope": "query"}) + "\n")
    with pytest.raises(L.LedgerError, match="metrics record version"):
        L.load_ledger(str(p))            # missing metricsV is unknown too
    p.write_text(json.dumps({"v": 1, "kind": "metrics", "t": 0,
                             "scope": "stream", "qps": 2.5,
                             "metricsV": L.METRICS_VERSION}) + "\n")
    data = L.load_ledger(str(p))
    assert len(data.metrics) == 1 and data.metrics[0]["qps"] == 2.5


def test_malformed_v1_record_rejected(tmp_path):
    p = tmp_path / "bad.jsonl"
    p.write_text(json.dumps({"v": 1, "kind": "query", "t": 0}) + "\n")
    with pytest.raises(L.LedgerError, match="missing required"):
        L.load_ledger(str(p))
    p.write_text(json.dumps({"v": 1, "kind": "query", "t": 0,
                             "name": "q", "status": "exploded"}) + "\n")
    with pytest.raises(L.LedgerError, match="status"):
        L.load_ledger(str(p))
    p.write_text(json.dumps({"v": 1, "kind": "wat", "t": 0}) + "\n")
    with pytest.raises(L.LedgerError, match="unknown record kind"):
        L.load_ledger(str(p))


def test_ledger_shaped_record_missing_version_rejected(tmp_path):
    """A record that claims to be ledger-shaped ('kind' present) but
    lacks 'v' must raise, not vanish — silently dropping it would
    re-pay or undercount a measured query."""
    p = tmp_path / "noversion.jsonl"
    p.write_text(json.dumps({"kind": "query", "name": "query9",
                             "ms": 5100.0, "status": "ok"}) + "\n")
    with pytest.raises(L.LedgerError, match="version"):
        L.load_ledger(str(p))


def test_torn_final_line_absorbed(tmp_path):
    """A kill mid-write tears the LAST line: the loader must absorb
    exactly that (report it, keep everything before it) — a torn final
    write must not poison the resume."""
    p = tmp_path / "killed.jsonl"
    good = json.dumps({"v": 1, "kind": "query", "t": 1.0,
                       "name": "query1", "status": "ok", "ms": 50.0})
    p.write_text(good + "\n"
                 + '{"v": 1, "kind": "query", "name": "query2", "st')
    data = L.load_ledger(str(p))
    assert data.torn
    assert data.times() == {"query1": 50.0}
    assert data.end is None              # no terminal record = killed


def test_resume_over_torn_tail_seals_it(tmp_path):
    """Reopening a killed campaign's ledger must SEAL the torn tail
    (newline) before appending, or the first resumed record would merge
    into the fragment and both would be lost."""
    p = tmp_path / "killed.jsonl"
    good = json.dumps({"v": 1, "kind": "query", "t": 1.0,
                       "name": "query1", "status": "ok", "ms": 50.0})
    p.write_text(good + "\n" + '{"v": 1, "kind": "query", "na')
    led = L.Ledger(str(p), driver="bench")
    led.query("query2", status="ok", ms=60.0)
    led.close("completed", queries=2)
    data = L.load_ledger(str(p))
    assert data.times() == {"query1": 50.0, "query2": 60.0}
    assert data.complete()


def test_legacy_resume_lines_normalized(tmp_path):
    """Pre-ledger bench.py resume files (bare result lines + platform
    meta line + stray chatter) still load."""
    p = tmp_path / "legacy.jsonl"
    with open(p, "w") as f:
        f.write(json.dumps({"name": "query3", "ms": 1234.5,
                            "hostSyncs": 2}) + "\n")
        f.write("stray non-json chatter\n")
        f.write(json.dumps({"name": "query9", "error": "boom"}) + "\n")
        f.write(json.dumps({"platform": "tpu"}) + "\n")
    data = L.load_ledger(str(p))
    assert data.times() == {"query3": 1234.5}
    assert data.queries["query9"]["status"] == "error"
    assert data.platform == "tpu"


def test_stale_end_record_cleared_by_resumed_activity(tmp_path):
    """A completed segment's ``end`` record must stop counting as
    terminal once a RESUMED run appends new activity — otherwise a
    SIGKILL of the resumed run would masquerade as 'completed (clean)'
    with the old segment's query count."""
    p = tmp_path / "resumed.jsonl"
    led = L.Ledger(str(p), driver="bench")
    led.query("q1", status="ok", ms=1.0)
    led.close("completed", queries=1)
    led2 = L.Ledger(str(p), driver="bench")
    led2.query("q2", status="ok", ms=2.0)    # resumed run, then SIGKILL
    led2.close(None)
    data = L.load_ledger(str(p))
    assert not data.complete(), \
        "stale end record must not close a resumed segment"
    assert data.times() == {"q1": 1.0, "q2": 2.0}
    # a fresh terminal record closes it again
    led3 = L.Ledger(str(p), driver="bench")
    led3.close("completed", queries=2)
    assert L.load_ledger(str(p)).complete()


def test_stream_evidence_matches_json_derivation():
    """listener.stream_evidence (live StreamEvent objects — what the
    bench child stamps into its result) must agree exactly with the
    ledger's JSON-side derivation."""
    from nds_tpu.listener import (StreamEvent, stream_event_json,
                                  stream_evidence)
    events = [StreamEvent("store_sales", 10, 2, "compiled", rows=50,
                          partitions=2, part_rows=(30, 20),
                          bytes_h2d=1000),
              StreamEvent("item", 4, 9, "eager",
                          reason="not chunk-invariant")]
    ev = stream_evidence(events)
    assert ev == L.evidence_from_scans(
        [stream_event_json(e) for e in events])
    assert ev["compiled"] == 1 and ev["eager"] == 1 and ev["syncs"] == 11


def test_ledger_append_resumes_without_duplicate_meta(tmp_path):
    p = tmp_path / "c.jsonl"
    led = L.Ledger(str(p), driver="bench")
    led.query("q1", status="ok", ms=1.0)
    led.close(None)                      # kill signature: no end record
    led2 = L.Ledger(str(p), driver="bench")
    led2.query("q2", status="ok", ms=2.0)
    led2.close("completed", queries=2)
    lines = [json.loads(ln) for ln in open(p).read().splitlines()]
    assert sum(1 for r in lines if r["kind"] == "meta") == 1
    data = L.load_ledger(str(p))
    assert len(data.times()) == 2 and data.complete()


def test_write_validates_before_touching_disk(tmp_path):
    led = L.Ledger(str(tmp_path / "v.jsonl"), driver="bench")
    with pytest.raises(L.LedgerError):
        led.query("q", status="not-a-status")
    led.close("completed")
    data = L.load_ledger(str(tmp_path / "v.jsonl"))
    assert data.queries == {}            # nothing invalid landed


# ---------------------------------------------------------------------------
# heartbeat
# ---------------------------------------------------------------------------


def test_heartbeat_writes_progress_and_stderr(tmp_path):
    p = tmp_path / "hb.jsonl"
    led = L.Ledger(str(p), driver="bench")
    out = io.StringIO()
    hb = L.Heartbeat(0.05, ledger=led,
                     status=lambda: {"query": "query7", "done": 3},
                     out=out)
    with hb:
        import time
        deadline = time.time() + 2.0
        while hb.beats < 2 and time.time() < deadline:
            time.sleep(0.01)
    led.close(None)
    assert hb.beats >= 2
    data = L.load_ledger(str(p))
    assert data.progress >= 2
    text = out.getvalue()
    assert "heartbeat" in text and "query=query7" in text
    recs = [json.loads(ln) for ln in open(p).read().splitlines()]
    beats = [r for r in recs if r["kind"] == "progress"]
    assert beats and beats[0]["query"] == "query7"
    assert beats[0]["done"] == 3 and "elapsedS" in beats[0]


def test_heartbeat_survives_status_exception():
    hb = L.Heartbeat(0.05, status=lambda: 1 / 0, out=None)
    fields = hb.beat()                   # must not raise
    assert fields["beat"] == 1


# ---------------------------------------------------------------------------
# bench_compare: diff, gate, drift self-test, emit-perf
# ---------------------------------------------------------------------------


def _campaign(path, times, syncs=None, eager=0):
    led = L.Ledger(str(path), driver="bench", platform="cpu", scale="1")
    for q, ms in times.items():
        led.query(q, status="ok", ms=ms,
                  hostSyncs=(syncs or {}).get(q, 2), syncWaitMs=1.0,
                  scanBytes=1000000, scanGBps=0.5, warmS=1.0,
                  compileS=0.5,
                  streamedScans=[{"table": "store_sales", "chunks": 10,
                                  "syncs": (syncs or {}).get(q, 2),
                                  "path": "compiled", "bytesH2d": 5000}]
                  + [{"table": "item", "chunks": 2, "syncs": 9,
                      "path": "eager", "reason": "r"}] * eager)
    led.close("completed", queries=len(times))
    return str(path)


def test_gate_passes_identical_rounds(tmp_path, bench_compare, capsys):
    a = _campaign(tmp_path / "a.jsonl", {"q1": 100.0, "q2": 200.0})
    rc = bench_compare.main([a, a, "--gate"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "no regressions" in out
    assert "ratio 1.0000" in out


def test_gate_fails_on_wall_regression(tmp_path, bench_compare, capsys):
    a = _campaign(tmp_path / "a.jsonl", {"q1": 100.0, "q2": 200.0})
    b = _campaign(tmp_path / "b.jsonl", {"q1": 400.0, "q2": 800.0})
    rc = bench_compare.main([a, b, "--gate"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "geomean regressed" in out
    # without --gate the report prints violations but exits 0
    assert bench_compare.main([a, b]) == 0


def test_gate_fails_on_evidence_regression(tmp_path, bench_compare,
                                           capsys):
    """Deterministic evidence regresses at ZERO tolerance: same walls,
    +syncs and a new eager fallback must fail the gate."""
    a = _campaign(tmp_path / "a.jsonl", {"q1": 100.0})
    b = _campaign(tmp_path / "b.jsonl", {"q1": 100.0},
                  syncs={"q1": 4}, eager=1)
    rc = bench_compare.main([a, b, "--gate"])
    out = capsys.readouterr().out
    assert rc == 1
    # scan-level and statement-level sync counters gate under their own
    # keys (never compared against each other)
    assert "streamed-scan syncs 2 -> 13" in out   # +9 on the new eager
    assert "host syncs 2 -> 4" in out
    assert "eager fallbacks 0 -> 1" in out


def test_gate_fails_when_query_stops_completing(tmp_path, bench_compare,
                                                capsys):
    """ok in A -> error/timeout in B is the worst regression there is;
    it must fail the gate, not vanish from the common-set comparison."""
    a = _campaign(tmp_path / "a.jsonl", {"q1": 100.0, "q2": 200.0})
    led = L.Ledger(str(tmp_path / "b.jsonl"), driver="bench",
                   platform="cpu", scale="1")
    led.query("q1", status="ok", ms=100.0, hostSyncs=2)
    led.query("q2", status="error", error="ExecError: boom")
    led.close("completed", queries=1)
    rc = bench_compare.main([a, str(tmp_path / "b.jsonl"), "--gate"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "q2: ok in A, error in B" in out
    assert "NOW FAILING" in out
    # a ROUND-budget kill is not the query's fault: it gates as COVERAGE
    # loss (incomplete round), never as 'stopped completing', and
    # --allow-missing blesses the partial comparison entirely
    led2 = L.Ledger(str(tmp_path / "c.jsonl"), driver="bench",
                    platform="cpu", scale="1")
    led2.query("q1", status="ok", ms=100.0, hostSyncs=2)
    led2.query("q2", status="timeout", error="timeout after 8s "
               "(round-budget)", limiter="round-budget", budgetS=8.0)
    led2.close("aborted", reason="incomplete", queries=1)
    rc2 = bench_compare.main([a, str(tmp_path / "c.jsonl"), "--gate"])
    out2 = capsys.readouterr().out
    assert rc2 == 1 and "missing from B" in out2
    assert "stopped completing" not in out2
    rc3 = bench_compare.main([a, str(tmp_path / "c.jsonl"), "--gate",
                              "--allow-missing"])
    capsys.readouterr()
    assert rc3 == 0


def test_gate_hung_query_not_shadowed_by_round_budget_retry(
        tmp_path, bench_compare, capsys):
    """A genuinely hung query (budget-limited timeout) whose RETRY was
    killed by round-budget exhaustion must still gate as 'stopped
    completing': the later round-budget record must not shadow the
    budget-limited attempt."""
    a = _campaign(tmp_path / "a.jsonl", {"q1": 100.0, "q2": 200.0})
    led = L.Ledger(str(tmp_path / "b.jsonl"), driver="bench",
                   platform="cpu", scale="1")
    led.query("q1", status="ok", ms=100.0, hostSyncs=2)
    led.query("q2", status="timeout", error="timeout after 5s (budget)",
              limiter="budget", budgetS=5.0, attempt=1)
    led.query("q2", status="timeout",
              error="timeout after 2s (round-budget)",
              limiter="round-budget", budgetS=2.0, attempt=2)
    led.close("aborted", reason="incomplete", queries=1)
    rc = bench_compare.main([a, str(tmp_path / "b.jsonl"), "--gate",
                             "--allow-missing"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "q2: ok in A, timeout in B (query stopped completing)" in out


def test_gate_fails_on_killed_round_without_terminal_record(
        tmp_path, bench_compare, capsys):
    """A round B ledger with NO terminal record is a killed campaign:
    the gate must fail rather than bless whatever it measured."""
    a = _campaign(tmp_path / "a.jsonl", {"q1": 100.0})
    led = L.Ledger(str(tmp_path / "b.jsonl"), driver="bench",
                   platform="cpu", scale="1")
    led.query("q1", status="ok", ms=100.0, hostSyncs=2)
    led.close(None)                      # SIGKILL: no end record
    rc = bench_compare.main([a, str(tmp_path / "b.jsonl"), "--gate"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "no terminal record" in out
    assert bench_compare.main([a, str(tmp_path / "b.jsonl"), "--gate",
                               "--allow-missing"]) == 0


def test_gate_inject_drift_self_test(tmp_path, bench_compare, capsys):
    """--inject-drift must make the gate FAIL on identical rounds (and
    the command succeeds only because the failure was required)."""
    a = _campaign(tmp_path / "a.jsonl", {"q1": 100.0, "q2": 200.0})
    rc = bench_compare.main([a, a, "--gate", "--inject-drift"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "drift fixture correctly rejected" in out


def test_gate_refuses_disjoint_rounds(tmp_path, bench_compare, capsys):
    a = _campaign(tmp_path / "a.jsonl", {"q1": 100.0})
    b = _campaign(tmp_path / "b.jsonl", {"q9": 100.0})
    rc = bench_compare.main([a, b, "--gate"])
    assert rc == 1
    assert "nothing was compared" in capsys.readouterr().out


def test_compare_accepts_baseline_times_json(tmp_path, bench_compare):
    a = _campaign(tmp_path / "a.jsonl", {"q1": 100.0, "q2": 200.0})
    bj = tmp_path / "base.json"
    bj.write_text(json.dumps({"metric": "power_geomean_ms",
                              "times": {"q1": 50.0, "q2": 100.0}}))
    cmp = bench_compare.compare(bench_compare.load_round(str(bj)),
                                bench_compare.load_round(a))
    assert cmp["common"] == ["q1", "q2"]
    assert abs(cmp["geomean_ratio"] - 2.0) < 1e-9


def test_emit_perf_deterministic(tmp_path, bench_compare, capsys):
    """PERF.md as a derived artifact: the same ledger renders the
    identical document, twice, and it carries the ledger's platform."""
    a = _campaign(tmp_path / "a.jsonl", {"q1": 100.0, "q2": 200.0})
    p1, p2 = tmp_path / "P1.md", tmp_path / "P2.md"
    assert bench_compare.main([a, "--emit-perf", str(p1)]) == 0
    assert bench_compare.main([a, "--emit-perf", str(p2)]) == 0
    t1 = p1.read_text()
    assert t1 == p2.read_text()
    assert "platform: cpu." in t1
    assert "Scale factor 1;" in t1       # FROM the ledger meta
    assert "| q1 | 100 |" in t1
    assert "Streamed >HBM scans" in t1
    # a ledger with no recorded scale must say so, never fall into the
    # reader's env default
    led = L.Ledger(str(tmp_path / "noscale.jsonl"), driver="power",
                   platform="cpu")
    led.query("q1", status="ok", ms=10.0, hostSyncs=1)
    led.close("completed", queries=1)
    p3 = tmp_path / "P3.md"
    assert bench_compare.main([str(tmp_path / "noscale.jsonl"),
                               "--emit-perf", str(p3)]) == 0
    assert "Scale factor unknown;" in p3.read_text()


# ---------------------------------------------------------------------------
# the A/B evidence cross-validation (ledger vs exec/mem audits)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def ab_ledger(bench_compare, tmp_path_factory):
    """One recorded A/B mini-sweep ledger, shared by the audit tests
    (the sweep executes the pinned templates — record once)."""
    path = str(tmp_path_factory.mktemp("ab") / "ab.jsonl")
    bench_compare.record_ab(path)
    return path


def test_ab_ledger_evidence_matches_audits(bench_compare, ab_ledger):
    """The recorded warm evidence (syncs, rows, h2d bytes, collectives)
    must fit the exec/mem audit predictions — the differential-harness
    lockstep contract, applied to the durable artifact."""
    ok, lines = bench_compare.audit_ab(ab_ledger)
    assert ok, "\n".join(lines)
    assert any(ln.startswith("ok [ab1]") for ln in lines)
    # the sharded mini-sweep recorded collective evidence
    data = L.load_ledger(ab_ledger)
    sharded = [r for n, r in data.queries.items() if n.endswith("@sharded")]
    assert sharded, "sharded A/B records missing (no multi-device mesh?)"
    assert any(s.get("collectives", 0) > 0
               for r in sharded for s in r.get("streamedScans") or [])


def test_ab_audit_inject_drift_must_fail(bench_compare, ab_ledger):
    ok, lines = bench_compare.audit_ab(ab_ledger, inject=True)
    assert not ok, "zeroed bounds/flipped paths must be rejected"
    assert any("MISMATCH" in ln for ln in lines)


def test_ab_ledger_byte_evidence_matches_cost_model(bench_compare,
                                                    ab_ledger):
    """--audit-perf: the recorded ``bytesH2d`` per compiled scan must
    EQUAL the static cost-model prediction (nds_tpu/analysis/perf_audit)
    rebuilt from the ledger's own rowBounds meta, and the sharded
    records' ``bytesIci`` must equal the exchange+reduce arithmetic —
    the campaign ledger lands pre-wired to its static denominator."""
    ok, lines = bench_compare.audit_perf(ab_ledger)
    assert ok, "\n".join(lines)
    ab1 = [ln for ln in lines if ln.startswith("ok [ab1]")]
    assert ab1 and "== static" in ab1[0] and "roofline" in ab1[0]
    # every template in the mini-sweep got a verdict line
    assert sum(1 for ln in lines if ln.startswith("ok [")) == 14


def test_ab_perf_audit_inject_drift_must_fail(bench_compare, ab_ledger):
    ok, lines = bench_compare.audit_perf(ab_ledger, inject=True)
    assert not ok, "zeroed byte predictions must be rejected"
    assert any("EXACTNESS LOST" in ln for ln in lines)


def test_ab_ledger_overflow_evidence_matches_num_audit(bench_compare,
                                                       ab_ledger):
    """--audit-num: every pinned A/B statement's numeric proofs (codec
    fit, rebase, accumulator range, hash bits) must hold at the ledger's
    own rowBounds, and the recorded scans must carry NO bound-bucket
    overflow rerun — the static verdict and the recorded overflow-flag
    evidence agree on the durable artifact."""
    ok, lines = bench_compare.audit_num(ab_ledger)
    assert ok, "\n".join(lines)
    assert any(ln.startswith("ok [ab1]") and "checks proven" in ln
               for ln in lines)
    assert sum(1 for ln in lines if ln.startswith("ok [")) == 14


def test_ab_num_audit_inject_drift_must_fail(bench_compare, ab_ledger):
    """Both drift directions: stamped overflow reasons under proven
    verdicts, and x10^9 row bounds (widened static ranges) over a clean
    record — each MUST be rejected on its own."""
    ok_r, lines_r = bench_compare.audit_num(ab_ledger, inject="runtime")
    assert not ok_r, "stamped overflow evidence must be rejected"
    assert any("overflow rerun" in ln for ln in lines_r)
    ok_s, lines_s = bench_compare.audit_num(ab_ledger, inject="static")
    assert not ok_s, "widened static ranges must be rejected"
    assert any("statically unproven" in ln for ln in lines_s)


def test_ab_ledger_compile_evidence_matches_param_audit(bench_compare,
                                                        ab_ledger):
    """--audit-param: every pinned A/B statement the param audit proves
    bindable slots for must carry compiled-path streamed-scan evidence
    in the ledger (the one-compile-many-params contract needs a
    compiled program to re-serve), and compiled evidence must never sit
    under a non-streamed classification. The sweep must yield at least
    one bindable slot — the rule going dark is itself a failure."""
    ok, lines = bench_compare.audit_param(ab_ledger)
    assert ok, "\n".join(lines)
    assert sum(1 for ln in lines if ln.startswith("ok [")) == 14
    # the streamed-fact direct-comparand statements carry signatures
    assert any("bindable slots [" in ln for ln in lines)


def test_ab_param_audit_inject_drift_must_fail(bench_compare, ab_ledger):
    """Both drift directions: eager-rewritten scan paths under proven
    bindable slots, and an empty streamed set (every classification
    drifts off compiled-stream) against compiled evidence — each MUST
    be rejected on its own."""
    ok_r, lines_r = bench_compare.audit_param(ab_ledger,
                                              inject="runtime")
    assert not ok_r, "eager-rewritten paths must be rejected"
    assert any("no compiled program" in ln for ln in lines_r)
    ok_s, lines_s = bench_compare.audit_param(ab_ledger, inject="static")
    assert not ok_s, "drifted classifications must be rejected"
    assert any("misclassified statement" in ln for ln in lines_s)


# ---------------------------------------------------------------------------
# evidence schema round-trip: every event field reaches the ledger
# ---------------------------------------------------------------------------


def test_stream_and_fault_event_fields_all_ledgered(bench_compare,
                                                    tmp_path):
    """Every StreamEvent / FaultEvent dataclass field must be carried by
    its ONE JSON shape (stream_event_json / fault_event_json) and
    survive ledger write -> load -> bench_compare aggregate. Asserted as
    FIELD-SET equality against an explicit field->key map, so adding an
    event field without wiring it through the evidence path (or wiring a
    key without a field) fails here by construction."""
    import dataclasses

    from nds_tpu.engine.faults import FaultEvent, fault_event_json
    from nds_tpu.listener import StreamEvent, stream_event_json

    STREAM_FIELD_TO_KEY = {
        "where": "table", "chunks": "chunks", "syncs": "syncs",
        "path": "path", "reason": "reason", "rows": "rows",
        "partitions": "partitions", "part_rows": "partRows",
        "bytes_h2d": "bytesH2d", "shards": "shards",
        "collectives": "collectives", "bytes_ici": "bytesIci",
        "shard_rows": "shardRows",
        "prefetch_stall_ms": "prefetchStallMs",
    }
    fields = {f.name for f in dataclasses.fields(StreamEvent)}
    assert set(STREAM_FIELD_TO_KEY) == fields, \
        "new StreamEvent field: add it to stream_event_json AND this map"
    # every optional field set to an EMITTING value -> every key present
    ev = StreamEvent(where="store_sales", chunks=4, syncs=1,
                     path="compiled", reason="note", rows=50,
                     partitions=2, part_rows=(30, 20), bytes_h2d=100,
                     shards=2, collectives=7, bytes_ici=64,
                     shard_rows=(28, 22), prefetch_stall_ms=1.25)
    j = stream_event_json(ev)
    assert set(j) == set(STREAM_FIELD_TO_KEY.values())
    assert j["table"] == "store_sales" and j["bytesH2d"] == 100
    assert j["partRows"] == [30, 20] and j["shardRows"] == [28, 22]

    FAULT_FIELD_TO_KEY = {"seam": "seam", "action": "action",
                          "attempt": "attempt", "detail": "detail"}
    ffields = {f.name for f in dataclasses.fields(FaultEvent)}
    assert set(FAULT_FIELD_TO_KEY) == ffields, \
        "new FaultEvent field: add it to fault_event_json AND this map"
    fj = fault_event_json(FaultEvent(seam="h2d-upload", action="recovered",
                                     attempt=2, detail="boom"))
    assert set(fj) == set(FAULT_FIELD_TO_KEY.values())

    # the durable round trip: write -> load verbatim -> aggregate
    p = str(tmp_path / "rt.jsonl")
    led = L.Ledger(p, driver="test", platform="cpu")
    led.query("q1", status="ok", ms=5.0, hostSyncs=1,
              streamedScans=[j], faultEvents=[fj])
    led.close("completed", queries=1)
    rec = L.load_ledger(p).queries["q1"]
    assert rec["streamedScans"][0] == j     # verbatim through the ledger
    assert rec["faultEvents"][0] == fj
    evd = bench_compare.load_round(p)["evidence"]["q1"]
    for key, want in [("bytesH2d", 100), ("bytesIci", 64),
                      ("collectives", 7), ("chunks", 4), ("syncs", 1),
                      ("partitions", 2), ("shards", 2),
                      ("prefetchStallMs", 1.25), ("compiled", 1),
                      ("eager", 0), ("scans", 1), ("hostSyncs", 1)]:
        assert evd.get(key) == want, (key, evd)


def test_ab_ledger_feeds_trace_report_and_sync_profile(ab_ledger,
                                                       tmp_path, capsys):
    """Post-hoc analysis on a completed round: both tools accept the
    ledger file directly."""
    tr = _load_tool("trace_report_mod", "tools/trace_report.py")
    rc = tr.main([ab_ledger])
    out = capsys.readouterr().out
    assert rc == 0
    assert "next bottleneck" in out
    assert "%HBM roof" in out
    sp = _load_tool("sync_profile_mod", "tools/sync_profile.py")
    lines = sp.ledger_histograms(ab_ledger)
    text = "\n".join(lines)
    assert "== ab1:" in text and "syncs" in text
