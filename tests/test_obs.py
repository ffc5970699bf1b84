# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Query-trace subsystem (nds_tpu/obs): the zero-added-sync contract,
thread scoping, ring bounds, Chrome export, driver wiring and the trace
report aggregator."""

import importlib.util
import json
import os
import threading

import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from nds_tpu.engine import ops as E
from nds_tpu.engine.session import Session
from nds_tpu.obs import export as obs_export
from nds_tpu.obs import trace as obs_trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _synccount_fixtures():
    """The pinned A/B templates + chunked session builder from
    tests/test_synccount.py (same import-by-path discipline as
    tools/exec_audit_diff.py: one set of fixtures, everywhere)."""
    path = os.path.join(REPO, "tests", "test_synccount.py")
    spec = importlib.util.spec_from_file_location("_synccount_fx", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod._STREAM_AB_QUERIES, mod._chunked_star_session


def _span_names(records):
    return [r.name for r in records if isinstance(r, obs_trace.SpanRecord)]


# ---------------------------------------------------------------------------
# the acceptance contract: tracing adds ZERO host syncs
# ---------------------------------------------------------------------------


def test_tracing_adds_zero_syncs(tmp_path, monkeypatch):
    """ops.sync_count() must be IDENTICAL for a traced vs untraced run of
    the A/B templates (chunked star join + streamed-fact filter): spans
    read host clocks and existing counters only, never the device. Both
    arms rebuild their session from the same seed and run cold (the
    pipeline/rank caches key on buffer identity, so fresh sessions miss
    equally). The TRACED arm additionally runs under a live campaign
    heartbeat (nds_tpu/obs/ledger.py) whose status callable reads the
    sync counters — the heartbeat thread is part of the zero-added-sync
    contract now that bench.py runs one for the whole campaign — WITH
    live metrics ON: the arm feeds the default registry per query and
    the heartbeat exports the NDS_TPU_METRICS_FILE snapshot, so the
    whole metrics plane (feed + rollup + atomic export) is inside the
    parity pin."""
    from nds_tpu.obs import metrics as obs_metrics
    from nds_tpu.obs.ledger import Heartbeat
    queries, make_session = _synccount_fixtures()
    ab = [q for q, _must in queries[:2]]
    assert obs_trace.on(), "tracing must be default-on"
    live_file = str(tmp_path / "metrics.json")
    monkeypatch.setenv("NDS_TPU_METRICS_FILE", live_file)
    reg = obs_metrics.default()
    reg.reset()

    def run_arm(feed):
        s = make_session(np.random.default_rng(42))
        # a DeviceCount left pending on this thread by whatever ran
        # before (another test file on the same xdist worker) is drained
        # — one batched transfer, one sync — by the first statement that
        # resolves counts: it would charge the first arm alone, so the
        # parity flipped with the worker's file order (ROADMAP Design
        # item 12). Each arm starts from a drained thread.
        E.resolve_counts()
        obs_trace.drain_spans()
        out = []
        for q in ab:
            before = E.sync_count()
            rows = s.sql(q).collect()
            out.append(E.sync_count() - before)
            assert rows
            if feed:                  # the drivers' drain-point feeds
                reg.inc("queries.total")
                reg.inc("queries.ok")
                reg.observe(obs_metrics.QUERY_WALL, 1.0 + len(out))
        return out

    hb = Heartbeat(0.01, ledger=None,
                   status=lambda: {"syncs": E.sync_count()}, out=None)
    with hb:
        traced = run_arm(feed=True)
    # the parity below covers the whole tree: the statement root, the
    # engine-primitive spans, the ring worker's re-recorded stages and
    # the sync sites (each a live TraceAnnotation too)
    names = set(_span_names(obs_trace.drain_spans()))
    assert {"statement", "parse", "plan", "stream", "prefetch.source",
            "prefetch.prepare", "materialize", "collect"} <= names, names
    assert any(n.startswith("op.") for n in names), names
    assert hb.beats > 0, "heartbeat must have fired during the arm"
    assert os.path.exists(live_file), \
        "heartbeat must have exported the live metrics snapshot"
    with open(live_file) as f:
        snap = json.load(f)
    assert snap["metricsV"] == obs_metrics.METRICS_VERSION
    assert snap["counters"]["queries.total"] >= 1
    monkeypatch.delenv("NDS_TPU_METRICS_FILE")
    obs_trace.set_enabled(False)
    try:
        untraced = run_arm(feed=False)
    finally:
        obs_trace.set_enabled(True)
    assert traced == untraced, \
        f"tracing (+heartbeat+metrics) changed sync counts: " \
        f"traced={traced} untraced={untraced}"
    reg.reset()
    obs_trace.drain_spans()                     # leftovers from this test


def test_span_and_annotate_noop_under_replay():
    """Under a replay re-trace both span() AND annotate() must be no-ops:
    the caller's own span is a null context there, so an annotate would
    stamp its attrs onto whatever OUTER span is open (e.g. the compile
    span) at jit-trace time."""
    obs_trace.drain_spans()
    with obs_trace.span("outer") as outer:
        with E.replaying([]):
            with obs_trace.span("inner"):
                obs_trace.annotate(path="eager", reason="bogus")
    assert _span_names(obs_trace.drain_spans()) == ["outer"]
    assert "path" not in outer.attrs and "reason" not in outer.attrs


def test_disabled_tracing_records_nothing():
    obs_trace.drain_spans()
    obs_trace.set_enabled(False)
    try:
        with obs_trace.span("nope"):
            pass
    finally:
        obs_trace.set_enabled(True)
    assert "nope" not in _span_names(obs_trace.drain_spans())


# ---------------------------------------------------------------------------
# thread scoping (mirrors Manager.unattributed semantics)
# ---------------------------------------------------------------------------


def test_spans_thread_scoped_two_streams():
    """Two concurrent in-process query streams (the Throughput Run shape)
    each drain ONLY their own spans; a span finished on a thread that
    never attached a ring lands in the unattributed diagnostics deque,
    never in another stream's drain."""
    results = {}
    barrier = threading.Barrier(2)

    def stream(name, n_queries):
        s = Session()
        s.create_temp_view(name, pa.table(
            {"v": pa.array(list(range(50)), pa.int64())}), base=True)
        barrier.wait()
        for _ in range(n_queries):
            s.sql(f"select count(*) c from {name} where v < 10").collect()
        results[name] = obs_trace.drain_spans()

    t1 = threading.Thread(target=stream, args=("ta", 2))
    t2 = threading.Thread(target=stream, args=("tb", 3))
    t1.start(); t2.start(); t1.join(); t2.join()
    assert _span_names(results["ta"]).count("plan") == 2
    assert _span_names(results["tb"]).count("plan") == 3

    # unattributed: a bare thread (no Session.sql, no attach) opening a
    # span must land in the diagnostics ring — mirroring
    # Manager.unattributed for failures on shared callback threads
    obs_trace.unattributed.clear()

    def orphan():
        with obs_trace.span("orphan-span"):
            pass

    t3 = threading.Thread(target=orphan)
    t3.start(); t3.join()
    assert any(getattr(r, "name", "") == "orphan-span"
               for r in obs_trace.unattributed)
    # and it must NOT appear in the main thread's ring
    assert "orphan-span" not in _span_names(obs_trace.drain_spans())


# ---------------------------------------------------------------------------
# ring-buffer bounds (listener satellite)
# ---------------------------------------------------------------------------


def test_stream_event_ring_keeps_newest_1000():
    from nds_tpu.listener import drain_stream_events, record_stream_event
    drain_stream_events()
    for i in range(1100):
        record_stream_event(str(i), 1, 0, "eager")
    got = drain_stream_events()
    assert len(got) == 1000
    assert got[0].where == "100" and got[-1].where == "1099", \
        "eviction must drop oldest-first and preserve drain order"
    assert drain_stream_events() == []


def test_manager_unattributed_keeps_newest_1000():
    from nds_tpu.listener import Manager
    Manager.unattributed.clear()

    def storm():
        # a thread with no scoped listener: everything goes unattributed
        for i in range(1100):
            Manager.notify_all(f"w{i}", "boom")

    t = threading.Thread(target=storm)
    t.start(); t.join()
    assert len(Manager.unattributed) == 1000
    assert Manager.unattributed[0].where == "w100"
    assert Manager.unattributed[-1].where == "w1099"
    Manager.unattributed.clear()


def test_span_ring_bounded(monkeypatch):
    # the capacity is read at ring-ATTACH time (the read-at-use knob
    # contract), so pin the env and force a fresh ring for this thread —
    # the live env can differ from whatever sized an earlier ring (e.g.
    # tools/sync_profile.py raises the default at import)
    monkeypatch.setenv("NDS_TPU_TRACE_RING", "96")
    obs_trace._tls.ring = None
    obs_trace.drain_spans()              # re-attaches at the pinned size
    ring_max = 96
    for i in range(ring_max + 50):
        with obs_trace.span("s", i=i):
            pass
    got = obs_trace.drain_spans()
    assert len(got) == ring_max
    assert got[-1].attrs["i"] == ring_max + 49  # newest kept
    obs_trace._tls.ring = None           # restore default-size ring


# ---------------------------------------------------------------------------
# chunked pipeline phases + Chrome export + report
# ---------------------------------------------------------------------------


@pytest.fixture
def chunked_trace(tmp_path):
    """Run one compiled-stream query and one eager-fallback query on a
    chunked session; write both Chrome traces into a tmp trace dir."""
    queries, make_session = _synccount_fixtures()
    s = make_session(np.random.default_rng(42))
    from nds_tpu.listener import drain_stream_events
    drain_stream_events()
    obs_trace.drain_spans()
    tdir = tmp_path / "traces"
    tdir.mkdir()
    out = {}
    # queries[0] pins the compiled pipeline. The IN-subquery template
    # streams compiled now (multi-pass residuals), so the canonical
    # automatic eager fallback is a CARTESIAN layout in the streamed
    # graph — unconnected parts lay out their pair expansion from host
    # row counts, which is never chunk-invariant.
    fallback_sql = ("select count(*) c from store_sales, item "
                    "where ss_ext_sales_price > 9990 and i_brand_id = 1")
    for label, (sql, _must) in (("compiled", queries[0]),
                                ("fallback", (fallback_sql, False))):
        rows = s.sql(sql).collect()
        assert rows
        records = obs_trace.drain_spans()
        obs_export.write_chrome_trace(
            str(tdir / f"{label}.trace.json"), records, query=label)
        out[label] = records
    return tdir, out


def test_chrome_trace_nested_phases(chunked_trace):
    tdir, records = chunked_trace
    with open(tdir / "compiled.trace.json") as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    assert events and all(e["ph"] == "X" for e in events)
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    for phase in ("plan", "stream", "stream.record", "stream.compile",
                  "stream.drive", "stream.materialize", "materialize"):
        assert phase in by_name, f"missing {phase} span in {sorted(by_name)}"

    def contains(outer, inner):
        return (outer["ts"] <= inner["ts"] and
                inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"])

    plan = by_name["plan"][0]
    for phase in ("stream.record", "stream.compile", "stream.drive",
                  "stream.materialize"):
        assert contains(plan, by_name[phase][0]), \
            f"{phase} must nest inside the plan span"
    # 10 chunks: 1 compile dispatch + 9 drive dispatches
    assert len(by_name["stream.compile"]) == 1
    assert len(by_name["stream.drive"]) == 9
    # the stream span carries the path + the pipeline-cache outcome
    sargs = by_name["stream"][0]["args"]
    assert sargs["path"] == "compiled" and sargs["chunks"] == 10
    assert sargs["pipelineCache"] == "miss"
    # sync-site events carry the first-class host_read attribution
    sync_ev = [e for e in events if e["cat"] == "sync"]
    assert sync_ev and all(":" in e["args"]["site"] for e in sync_ev)
    # rollup rides in the file for readers that skip re-aggregation
    assert "plan" in doc["nds"]["rollup"]["phases"]


def test_eager_fallback_span_carries_reason(chunked_trace):
    tdir, records = chunked_trace
    stream = [r for r in records["fallback"]
              if isinstance(r, obs_trace.SpanRecord) and r.name == "stream"]
    assert stream and stream[0].attrs.get("path") == "eager"
    assert stream[0].attrs.get("reason"), "fallback span must name why"
    names = _span_names(records["fallback"])
    assert "stream.eager" in names
    roll = obs_export.rollup(records["fallback"])
    assert roll["fallbacks"][0]["reason"] == stream[0].attrs["reason"]


def test_trace_report_aggregates_dir(chunked_trace, capsys):
    tdir, _records = chunked_trace
    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(REPO, "tools", "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rc = mod.main([str(tdir)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "2 queries" in out
    assert "stream.drive" in out and "stream.compile" in out
    assert "compile/drive ratio" in out
    assert "top host-sync sites" in out
    assert "eager-fallback cost by reason" in out
    assert "trace diverged" in out or "not chunk-invariant" in out
    # the ranking is PRICED: each fallback line projects the savings of a
    # conversion from this run's own compiled per-chunk drive cost
    assert "projected" in out and "saved" in out
    fallback_lines = [ln for ln in out.splitlines()
                      if "not chunk-invariant" in ln
                      or "trace diverged" in ln]
    assert fallback_lines and all("saved" in ln for ln in fallback_lines)


def test_span_syncs_match_stream_event(chunked_trace):
    """The per-scan stream span must charge exactly the syncs its
    StreamEvent recorded — the zero-added-sync bridge exec_audit_diff
    gates in tier-1, asserted here at the unit level too."""
    queries, make_session = _synccount_fixtures()
    from nds_tpu.listener import drain_stream_events
    s = make_session(np.random.default_rng(7))
    drain_stream_events()
    obs_trace.drain_spans()
    s.sql(queries[0][0]).collect()
    events = drain_stream_events()
    spans = [r for r in obs_trace.drain_spans()
             if isinstance(r, obs_trace.SpanRecord) and r.name == "stream"]
    assert len(events) == 1 and len(spans) == 1
    assert spans[0].syncs == events[0].syncs
    assert spans[0].attrs["path"] == events[0].path


# ---------------------------------------------------------------------------
# driver wiring: power.py --trace-dir
# ---------------------------------------------------------------------------


def test_power_run_writes_trace_files(tmp_path, monkeypatch):
    """A CPU run of the Power driver with trace_dir must produce, per
    query, a valid Chrome trace_event JSON with nested spans, and stamp
    the per-phase rollup into the query's JSON summary next to the sync
    counters."""
    import pyarrow.parquet as pq
    from collections import OrderedDict

    from nds_tpu import power
    from nds_tpu.schema import get_schemas
    from nds_tpu.types import to_arrow as to_pa
    fields = get_schemas(use_decimal=True)["item"]
    monkeypatch.setattr(power, "get_schemas",
                        lambda use_decimal: {"item": fields})
    data = tmp_path / "data"
    (data / "item").mkdir(parents=True)
    cols = {f.name: pa.array([None, None], to_pa(f.type)) for f in fields}
    cols["i_item_sk"] = pa.array([1, 2], to_pa(fields[0].type))
    pq.write_table(pa.table(cols), data / "item" / "part-0.parquet")
    tdir = tmp_path / "traces"
    jdir = tmp_path / "json"
    power.run_query_stream(str(data), None,
                           OrderedDict(q="select count(*) c from item"),
                           str(tmp_path / "t.csv"),
                           json_summary_folder=str(jdir),
                           trace_dir=str(tdir))
    trace_file = tdir / "q.trace.json"
    assert trace_file.exists()
    with open(trace_file) as f:
        doc = json.load(f)
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"query", "plan", "materialize"} <= names
    q = [e for e in doc["traceEvents"] if e["name"] == "query"][0]
    p = [e for e in doc["traceEvents"] if e["name"] == "plan"][0]
    assert q["ts"] <= p["ts"] and \
        p["ts"] + p["dur"] <= q["ts"] + q["dur"], "plan nests under query"
    summaries = list(jdir.glob("*.json"))
    assert summaries
    with open(summaries[0]) as f:
        summary = json.load(f)
    assert "plan" in summary["trace"]["phases"]
    assert "syncSites" in summary["trace"]


def test_power_run_writes_ledger(tmp_path, monkeypatch):
    """The Power driver with a ledger path must append one validated
    query record per query (phase rollup + sync counters aboard) and a
    terminal ``completed`` record — the campaign evidence ledger is the
    durable unification of what the JSON summaries record per file."""
    import pyarrow.parquet as pq
    from collections import OrderedDict

    from nds_tpu import power
    from nds_tpu.obs.ledger import load_ledger
    from nds_tpu.schema import get_schemas
    from nds_tpu.types import to_arrow as to_pa
    fields = get_schemas(use_decimal=True)["item"]
    monkeypatch.setattr(power, "get_schemas",
                        lambda use_decimal: {"item": fields})
    data = tmp_path / "data"
    (data / "item").mkdir(parents=True)
    cols = {f.name: pa.array([None, None], to_pa(f.type)) for f in fields}
    cols["i_item_sk"] = pa.array([1, 2], to_pa(fields[0].type))
    pq.write_table(pa.table(cols), data / "item" / "part-0.parquet")
    ledger_path = tmp_path / "campaign.jsonl"
    power.run_query_stream(str(data), None,
                           OrderedDict(q="select count(*) c from item"),
                           str(tmp_path / "t.csv"),
                           ledger_path=str(ledger_path))
    led = load_ledger(str(ledger_path))
    assert led.meta["driver"] == "power"
    assert led.complete() and led.end["status"] == "completed"
    assert led.end["queries"] == 1
    rec = led.queries["q"]
    assert rec["status"] == "ok" and rec["ms"] >= 0
    assert rec["phase"] == "Power"
    assert "hostSyncs" in rec and "compileMs" in rec
    assert "plan" in rec["tracePhases"]["phases"]


def test_trace_report_ledger_parity_on_byte_columns(tmp_path):
    """The byte/roofline/pf-stall/static-cost columns must render
    IDENTICALLY from a trace dir and from the equivalent campaign
    ledger (including a legacy ledger record that predates the derived
    ``evidence`` field — the aggregate is re-derived from its
    ``streamedScans``). Post-hoc analysis on a completed round must not
    read differently from live traces."""
    import json
    spec = importlib.util.spec_from_file_location(
        "trace_report_p", os.path.join(REPO, "tools", "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    # one measured run of the corpus template "query3" (a name the
    # static cost model prices, so the static columns engage): 120 ms
    # wall, 20 ms of it the collective/materialize phase
    scan = {"table": "store_sales", "chunks": 4, "syncs": 0,
            "path": "compiled", "bytesH2d": 4_000_000, "shards": 2,
            "shardRows": [10, 10], "collectives": 5,
            "bytesIci": 1_000_000, "prefetchStallMs": 2.5}
    tdir = tmp_path / "traces"
    tdir.mkdir()
    events = [
        {"ph": "X", "name": "stream", "ts": 0, "dur": 120_000,
         "args": {"path": "compiled", "bytesH2d": scan["bytesH2d"],
                  "bytesLogical": scan["bytesH2d"],
                  "bytesIci": scan["bytesIci"],
                  "prefetchStallMs": scan["prefetchStallMs"]}},
        {"ph": "X", "name": "stream.materialize", "ts": 100_000,
         "dur": 20_000, "args": {}},
    ]
    (tdir / "query3.trace.json").write_text(json.dumps(
        {"traceEvents": events, "nds": {"query": "query3", "rollup": {
            "phases": {
                "stream": {"ms": 120.0, "count": 1, "selfMs": 100.0,
                           "rootMs": 120.0},
                "stream.materialize": {"ms": 20.0, "count": 1,
                                       "selfMs": 20.0}}}}}))

    # the equivalent ledger record, legacy-shaped: NO derived
    # ``evidence`` field, only the per-scan streamedScans evidence
    led = tmp_path / "round.jsonl"
    led.write_text(json.dumps(
        {"v": 1, "kind": "query", "t": 1.0, "name": "query3",
         "status": "ok", "ms": 120.0, "hostSyncs": 0,
         "tracePhases": {"phases": {
             "stream": {"ms": 120.0},
             "stream.materialize": {"ms": 20.0}}},
         "streamedScans": [scan]}) + "\n")

    def row(lines):
        hits = [ln for ln in lines if ln.startswith("| query3 |")]
        assert len(hits) == 1, "\n".join(lines)
        return [c.strip() for c in hits[0].strip("|").split("|")]

    t_lines = mod.render(mod.collect_from_traces(str(tdir)), "t")
    l_lines = mod.render(mod.collect_from_ledger(str(led)), "l")
    t_row, l_row = row(t_lines), row(l_lines)
    # both renders carry the static cost-model columns in the header
    assert any("static-roofline %" in ln for ln in t_lines)
    assert any("static-roofline %" in ln for ln in l_lines)
    # same wall, and the 10 tail cells — logical MB, h2d MB, eff GB/s,
    # %HBM roof, ici MB, ici GB/s, %ICI roof, pf-stall ms,
    # static-roofline %, unexplained ms — byte-identical across inputs
    assert t_row[1] == l_row[1] == "120.0"
    assert t_row[-10:] == l_row[-10:], (t_row, l_row)
    assert t_row[-10] == "4.0"          # logical MB from bytesH2d
    assert t_row[-3] == "2.5"           # pf-stall ms
    # static columns engaged (a priced corpus name, not "-")
    assert t_row[-2] != "-" and t_row[-1] != "-"


# ---------------------------------------------------------------------------
# one statement, one tree: sid / parent / qid, self time, worker stages
# ---------------------------------------------------------------------------


def _spans(records):
    return [r for r in records if isinstance(r, obs_trace.SpanRecord)]


@pytest.fixture
def two_statements():
    """Two statements on the chunked session (a streamed star join, then
    a streamed-fact filter), warm, drained together."""
    queries, make_session = _synccount_fixtures()
    s = make_session(np.random.default_rng(42))
    for sql, _must in queries[:2]:
        s.sql(sql).collect()                 # cold: record + compile
    obs_trace.drain_spans()
    obs_trace.unattributed.clear()
    for sql, _must in queries[:2]:
        assert s.sql(sql).collect()
    return obs_trace.drain_spans()


def test_two_statements_form_two_trees(two_statements):
    """Every record carries sid / parent / qid; grouped by qid the spans
    of a two-statement run are two trees with one ``statement`` root
    each (the fetch spans that run after ``Session.sql`` returned are
    parentless and carry the statement's qid)."""
    records = two_statements
    spans = _spans(records)
    by_sid = {r.sid: r for r in spans}
    assert len(by_sid) == len(spans), "sids must be unique"
    assert not any(hasattr(r, "depth") for r in records)
    qids = sorted({r.qid for r in records})
    assert len(qids) == 2 and None not in qids
    for qid in qids:
        mine = [r for r in spans if r.qid == qid]
        roots = [r for r in mine if r.parent is None]
        assert [r.name for r in roots].count("statement") == 1
        assert {r.name for r in roots} <= {"statement", "materialize",
                                           "collect"}
        statement = next(r for r in roots if r.name == "statement")
        for r in mine:
            if r.parent is None:
                continue
            # the chain of parents stays inside the statement and ends
            # at its root
            hops, cur = 0, r
            while cur.parent is not None:
                cur = by_sid[cur.parent]
                assert cur.qid == qid
                hops += 1
                assert hops < 64
            assert cur is statement
        names = {r.name for r in mine}
        assert {"parse", "plan", "stream", "materialize",
                "collect"} <= names, names
    # sync sites hang off the span that paid them
    sites = [r for r in records if isinstance(r, obs_trace.SyncSite)]
    assert sites and all(r.parent in by_sid and r.qid == by_sid[r.parent].qid
                         for r in sites)
    assert not obs_trace.unattributed, list(obs_trace.unattributed)


def test_self_ms_plus_children_is_the_duration(two_statements):
    """``selfMs`` of a span plus the durations of its direct children of
    the same thread equals its duration, and over a drain the self times
    of the driver's spans add up to what the roots cover."""
    records = two_statements
    spans = _spans(records)
    roll = obs_export.rollup(records)["phases"]
    for name in ("statement", "plan", "stream"):
        parents = [r for r in spans if r.name == name]
        sids = {r.sid for r in parents}
        kids_ms = sum(r.dur_ns for r in spans
                      if r.parent in sids and r.thread == "driver") / 1e6
        assert roll[name]["selfMs"] + kids_ms == \
            pytest.approx(roll[name]["ms"], abs=0.02), name
        assert 0 <= roll[name]["selfMs"] <= roll[name]["ms"]
    driver = {r.name for r in spans if r.thread == "driver"}
    worker = {r.name for r in spans if r.thread == "worker"}
    assert worker <= {"prefetch.source", "prefetch.prepare",
                      "prefetch.backpressure"} and worker
    # prefetch.* phases mix driver (chunk 0) and worker records: take
    # the driver share from the records themselves
    self_driver = sum(roll[n]["selfMs"] for n in driver - worker) + sum(
        r.dur_ns / 1e6 for r in spans
        if r.thread == "driver" and r.name in worker)
    assert self_driver == pytest.approx(
        sum(p["rootMs"] for p in roll.values()), abs=0.5)
    # the self share of the blocked time adds up to the roots' total
    assert sum(p["syncWaitMs"] for p in roll.values()) == pytest.approx(
        sum(r.sync_wait_ns for r in spans if r.parent is None) / 1e6,
        abs=0.05)
    lead = roll["stream"]["leadInMs"]
    assert 0 < lead < roll["statement"]["ms"]


@pytest.mark.parametrize("depth", ["2", "0"], ids=["ring", "inline"])
def test_worker_stages_come_back_under_the_stream_span(depth, monkeypatch):
    """The ring worker's per-chunk stages are re-recorded on the driver's
    ring with the scan's ``stream`` span as parent (ring on: marked
    thread="worker"; depth 0: ordinary driver spans), in the statement's
    own drain, and nothing lands in ``unattributed``."""
    monkeypatch.setenv("NDS_TPU_PREFETCH_DEPTH", depth)
    queries, make_session = _synccount_fixtures()
    s = make_session(np.random.default_rng(42))
    s.sql(queries[0][0]).collect()
    obs_trace.drain_spans()
    obs_trace.unattributed.clear()
    assert s.sql(queries[0][0]).collect()
    spans = _spans(obs_trace.drain_spans())
    (stream,) = [r for r in spans if r.name == "stream"]
    for stage in ("prefetch.source", "prefetch.prepare"):
        got = [r for r in spans if r.name == stage]
        # 10 chunks: chunk 0 on the driver, 1..9 through the ring
        assert sorted(r.attrs["chunk"] for r in got) == list(range(10))
        assert all(r.qid == stream.qid for r in got)
        ring = [r for r in got if r.attrs["chunk"] > 0]
        if depth == "0":
            assert all(r.thread == "driver" for r in got)
        else:
            assert all(r.parent == stream.sid for r in got)
            assert all(r.thread == "worker" for r in ring)
            assert all(stream.ts_ns <= r.ts_ns and
                       r.ts_ns + r.dur_ns <= stream.ts_ns + stream.dur_ns
                       for r in ring)
    back = [r for r in spans if r.name == "prefetch.backpressure"]
    assert (len(back) == 9) == (depth != "0")
    assert not obs_trace.unattributed, list(obs_trace.unattributed)


def test_profile_holds_the_statement_tree(tmp_path):
    """With a profile being taken the program's spans lie in the host
    plane as ``nds:`` annotations (sid / parent / qid as stats), and
    ``tools/trace_report.py --profile`` reads the capture."""
    import jax
    spec = importlib.util.spec_from_file_location(
        "trace_report_prof", os.path.join(REPO, "tools", "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    queries, make_session = _synccount_fixtures()
    s = make_session(np.random.default_rng(42))
    s.sql(queries[0][0]).collect()
    obs_trace.drain_spans()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    with jax.profiler.trace(str(tmp_path), profiler_options=options):
        assert s.sql(queries[0][0]).collect()
    spans = _spans(obs_trace.drain_spans())
    (path,) = [os.path.join(d, f) for d, _dirs, files in os.walk(tmp_path)
               for f in files if f.endswith(".xplane.pb")]
    notes = mod.read_profile(path)["notes"]
    names = {n["name"] for n in notes}
    assert {"statement", "plan", "parse", "stream", "materialize",
            "collect", "sync:stream_final"} <= names, names
    assert any(n.startswith("op.") for n in names), names
    # the worker's live annotations, and the ids of the ring's records
    assert "prefetch.source" in names
    by_sid = {r.sid: r for r in spans}
    tagged = [n for n in notes if n["sid"]]
    # (a dropped span, the ring's end-of-stream probe, annotates but
    # leaves no record)
    assert {n["name"] for n in tagged if n["sid"] not in by_sid} <= \
        {"stream.prefetch"}
    tagged = [n for n in tagged if n["sid"] in by_sid]
    assert tagged and all(by_sid[n["sid"]].name == n["name"] and
                          by_sid[n["sid"]].qid == n["qid"] and
                          by_sid[n["sid"]].parent == n["parent"]
                          for n in tagged)
    lines = mod.profile_report(str(tmp_path))
    text = "\n".join(lines)
    assert "device time by scope" in text and "idle gaps over" in text
    (window,) = mod.profile_statements({"notes": notes, "ops": []})
    assert window[3] == spans[0].qid


# ---------------------------------------------------------------------------
# tools/trace_report.py --profile: the parts a CPU capture cannot exercise
# ---------------------------------------------------------------------------


def _pb(fields):
    """Protobuf wire bytes of ``[(field number, int | bytes | str)]``."""
    def varint(n):
        out = bytearray()
        while True:
            b = n & 0x7F
            n >>= 7
            out.append(b | (0x80 if n else 0))
            if not n:
                return bytes(out)
    out = b""
    for no, val in fields:
        if isinstance(val, int):
            out += varint(no << 3) + varint(val)
        else:
            raw = val.encode() if isinstance(val, str) else val
            out += varint(no << 3 | 2) + varint(len(raw)) + raw
    return out


def _trace_report():
    spec = importlib.util.spec_from_file_location(
        "trace_report_units", os.path.join(REPO, "tools", "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_profile_reader_takes_op_names_from_event_metadata(tmp_path):
    """A TPU capture keeps an operation's ``op_name`` (stat ``tf_op``) and
    ``program_id`` on the event's METADATA entry: the reader decodes just
    those fields of the file, per plane, name and program."""
    mod = _trace_report()
    stat_names = [_pb([(1, 1), (2, _pb([(1, 1), (2, "tf_op")]))]),
                  _pb([(1, 2), (2, _pb([(1, 2), (2, "program_id")]))]),
                  _pb([(1, 3), (2, _pb([(1, 3), (2, "flops")]))])]

    def event_meta(mid, name, op_name, program):
        stats = [(5, _pb([(1, 3), (3, 99)])),
                 (5, _pb([(1, 2), (3, program)]))]
        if op_name:
            stats.append((5, _pb([(1, 1), (5, op_name)])))
        return _pb([(1, mid), (2, _pb([(1, mid), (2, name)] + stats))])
    deep = ("jit(traced)/nds.stream.chunk/nds.join/jit(_key_hash_impl)/"
            "nds.join.key_hash/xor:")
    plane = _pb([(2, "/device:TPU:0")]
                + [(4, event_meta(1, "%fusion.35 = u32[8]{0} fusion()",
                                  deep, 7))]
                + [(4, event_meta(2, "%fusion.35 = u32[8]{0} fusion()",
                                  "jit(f)/nds.sort/sort:", 8))]
                + [(4, event_meta(3, "%copy-done.4 = s32[8]{0} copy-done()",
                                  None, 7))]
                + [(5, s) for s in stat_names])
    host = _pb([(2, "/host:CPU")])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_pb([(1, plane), (1, host)]))
    meta = mod._event_metadata(str(path))
    assert meta == {"/device:TPU:0": {
        "%fusion.35 = u32[8]{0} fusion()": {7: deep,
                                            8: "jit(f)/nds.sort/sort:"}}}
    assert mod.scope_of(deep) == ["stream.chunk", "join", "join.key_hash"]
    assert mod.scope_of("jit(cumsum)/cumsum:") == [] == mod.scope_of(None)
    assert mod.short_hlo(
        "%while.4 = (u32[]{:S(2)}, s32[4194304]{0:T(1024)}) while((u32[], "
        "s32[4194304]) %tuple), condition=%c, body=%b") == \
        "%while.4 while (u32[], s32[4194304])"


def test_profile_reader_self_time_and_gap_attribution():
    """Device time by scope adds each operation's SELF time (a ``while``
    holds its body's operations), and an idle gap's time goes to the
    innermost ``nds:`` annotation open on the driver's thread, children
    found by ``parent`` id; a ring-worker annotation (another thread
    than its parent) is listed beside."""
    mod = _trace_report()
    ops = [("d", "%while.4", 0, 100, [], "jit__pk_gather_impl"),
           ("d", "%fusion.35", 10, 30, ["pk_gather"], "jit__pk_gather_impl"),
           ("d", "%fusion.35", 50, 30, ["pk_gather"], "jit__pk_gather_impl"),
           ("d", "%fusion.1", 200, 50, ["gather"], "jit__gather_cols_impl")]
    assert mod._self_ns(ops) == [40, 30, 30, 50]
    main, worker = ("/host:CPU", 1), ("/host:CPU", 2)

    def note(name, start, end, sid, parent, line=main):
        return {"name": name, "start": start, "end": end, "sid": sid,
                "parent": parent, "qid": 1, "line": line}
    notes = [note("statement", 0, 1000, 1, None),
             note("plan", 10, 900, 2, 1),
             note("op.filter", 100, 300, 3, 2),
             note("sync:counts1", 250, 290, None, 3),
             note("stream", 400, 900, 4, 2),
             note("prefetch.source", 420, 600, None, 4, line=worker)]
    driver, beside = mod._host_during(notes, 200, 500)
    assert dict(driver) == {"op.filter": 60, "sync:counts1": 40,
                            "plan": 100, "stream": 100}
    assert dict(beside) == {"prefetch.source": 80}
    assert mod.profile_statements({"notes": notes, "ops": ops}) == \
        [("qid 1", 0, 1000, 1)]


# ---------------------------------------------------------------------------
# op.join / op.semi_join state the cells they touch; the probe's scope (PR 28)
# ---------------------------------------------------------------------------

_CELLS_QUERIES = {
    # a general hash join between two tables on a two-column key (full
    # outer: the unmatched-row indices of both sides are counted too)
    "op.join": "select count(*), sum(a.v), sum(b.w) from ta a full outer "
               "join tb b on (a.k = b.k and a.j = b.j)",
    # one integer key: the sort arm, no op.join inside
    "op.semi_join": "select count(*) from ta where exists "
                    "(select * from tb where tb.k = ta.k)",
    # a two-column key: the hash arm, which opens an op.join
    "op.semi_join-hash": "select count(*) from ta where exists "
                         "(select * from tb where tb.k = ta.k "
                         "and tb.j = ta.j)",
}


def _cells_session():
    rng = np.random.default_rng(28)
    s = Session()
    s.create_temp_view("ta", pa.table({
        "k": pa.array(rng.integers(0, 50, 300), pa.int64()),
        "j": pa.array(rng.integers(0, 4, 300), pa.int64()),
        "v": pa.array(rng.integers(0, 1000, 300), pa.int64())}))
    s.create_temp_view("tb", pa.table({
        "k": pa.array(rng.integers(25, 75, 100), pa.int64()),
        "j": pa.array(rng.integers(0, 4, 100), pa.int64()),
        "w": pa.array(rng.integers(0, 1000, 100), pa.int64())}))
    return s


def _traced_and_untraced(s, q):
    """(rows, span records) of ``q`` with tracing on, after checking that
    tracing off gives the same rows with the same count of host reads."""
    def run():
        E.resolve_counts()                # start from a drained thread
        obs_trace.drain_spans()
        before = E.sync_count()
        rows = s.sql(q).collect()
        return rows, E.sync_count() - before, obs_trace.drain_spans()

    rows_on, syncs_on, records = run()
    obs_trace.set_enabled(False)
    try:
        rows_off, syncs_off, nothing = run()
    finally:
        obs_trace.set_enabled(True)
    assert rows_on == rows_off and rows_on
    assert syncs_on == syncs_off and not nothing
    return rows_on, records


@pytest.mark.parametrize("case", sorted(_CELLS_QUERIES))
def test_join_and_semi_join_spans_state_cells_without_a_sync(case):
    """The attribute is built from host-known shapes alone: the rollup sums
    it, the statement's rows and its count of host reads are the same with
    tracing off, and the number is what the shapes give."""
    s = _cells_session()
    ta, tb = s.catalog["ta"], s.catalog["tb"]
    phase = case.split("-")[0]
    _rows, records = _traced_and_untraced(s, _CELLS_QUERIES[case])
    phases = obs_export.rollup(records)["phases"]
    stated = [r.attrs["cells"] for r in records
              if isinstance(r, obs_trace.SpanRecord) and r.name == phase
              and "cells" in r.attrs]
    cells = phases[phase]["cells"]
    assert len(stated) == 1 and cells == stated[0]
    if case == "op.join":
        # stated once, by the span that builds the pair indices (the
        # materialising span around it leaves its gathers to op.gather):
        # both sides' two key columns at their buckets, the pair indices
        # (two arrays at the candidates' bucket) and the unmatched rows
        keys = 2 * ta.plen + 2 * tb.plen
        assert cells > keys and (cells - keys) % E.bucket_len(0) == 0
    elif case == "op.semi_join":
        # the probe side's key and the mask out at its bucket, the build
        # side's key at the bucket the subquery's rows came out at
        build = cells - 2 * ta.plen
        assert E.bucket_len(0) <= build <= tb.plen and not build & (build - 1)
        assert "op.join" not in phases
    else:
        # the hash arm: the mask out alone, its keys and pair indices are
        # counted once, by the op.join span it opens
        assert cells == ta.plen
        assert phases["op.join"]["cells"] > 2 * ta.plen


def test_the_join_probes_searches_run_under_their_scope():
    """Each binary search of ``_probe_candidates``' eager arm is a jitted
    body named ``nds.join.probe`` (a by-scope reader of the device trace
    sees it), with the offsets ``searchsorted`` gives."""
    rng = np.random.default_rng(28)
    build = jnp.sort(jnp.asarray(rng.integers(0, 100, 64), jnp.uint32))
    probe = jnp.asarray(rng.integers(0, 100, 128), jnp.uint32)
    for side in ("left", "right"):
        text = E._probe_search_impl.lower(build, probe, side=side).as_text(
            debug_info=True)
        assert "nds.join.probe" in text
        assert np.array_equal(
            E._probe_search_impl(build, probe, side=side),
            np.searchsorted(np.asarray(build), np.asarray(probe), side))


# ---------------------------------------------------------------------------
# op.setop[fn], and the cells of op.setop / op.concat / op.window (PR 33)
# ---------------------------------------------------------------------------

_SETOP_QUERIES = {
    # each operand keeps duplicates and a NULL key: the DISTINCT and the
    # null-safe membership both have work to do
    "union": "select count(*) from (select k, j from ta union "
             "select k, j from tb) u",
    "intersect": "select count(*) from (select k, j from ta intersect "
                 "select k, j from tb) u",
    "except": "select count(*) from (select k, j from ta except "
              "select k, j from tb) u",
    "concat": "select count(*), sum(v) from (select k, v from ta union all "
              "select k, w from tb) u",
    "window": "select k, v, rank() over (partition by j order by v desc) r, "
              "sum(v) over (partition by j) t from ta order by k, v, r",
}


def _nullable_cells_session():
    s = _cells_session()
    for name in ("ta", "tb"):
        t = s.catalog[name].to_arrow()
        k = t.column("k").to_pylist()
        k[::7] = [None] * len(k[::7])
        s.create_temp_view(name, t.set_column(0, "k", pa.array(k, pa.int64())))
    return s


@pytest.mark.parametrize("case", sorted(_SETOP_QUERIES))
def test_setop_concat_and_window_spans_state_cells_without_a_sync(case):
    """``op.setop`` carries ``fn`` and the key arrays its DISTINCT reads;
    ``op.concat`` the arrays it appends at the output's bucket;
    ``op.window`` rows sorted x arrays scanned. All from host-known shapes:
    the rows and the count of host reads are the same with tracing off, and
    the rollup sums each."""
    s = _nullable_cells_session()
    ta, tb = s.catalog["ta"], s.catalog["tb"]
    rows, records = _traced_and_untraced(s, _SETOP_QUERIES[case])
    phases = obs_export.rollup(records)["phases"]
    spans = [r for r in records if isinstance(r, obs_trace.SpanRecord)]
    setops = [r for r in spans if r.name == "op.setop"]
    arrays = 2 + 1                        # k with its validity, j
    if case in ("union", "intersect", "except"):
        assert [r.attrs["fn"] for r in setops] == [case]
        (span,) = setops
        assert phases["op.setop"]["cells"] == span.attrs["cells"]
        assert phases["op.setop"]["ms"] > 0
    if case == "union":
        # the DISTINCT reads the appended table: both operands' rows at the
        # bucket the append came out at; the append states its own
        out_bucket = E.bucket_len(ta.nrows + tb.nrows)
        assert span.attrs["cells"] == arrays * out_bucket
        assert phases["op.concat"]["cells"] == arrays * out_bucket
        assert "op.semi_join" not in phases
    elif case in ("intersect", "except"):
        # the left operand's key arrays at its bucket; the membership's
        # keys and mask are stated by the op.join / op.semi_join it opens
        assert span.attrs["cells"] == arrays * ta.plen
        inner = [r for r in spans if r.name in ("op.semi_join", "op.join")
                 and r.parent is not None]
        assert {r.name for r in inner} == {"op.semi_join", "op.join"}
        assert phases["op.semi_join"]["cells"] > 0
        assert phases["op.join"]["cells"] > 0
        assert "op.concat" not in phases
    elif case == "concat":
        assert not setops
        (span,) = [r for r in spans if r.name == "op.concat"]
        # k (nullable in both operands) and v / w, at the output's bucket
        assert span.attrs["cells"] == 3 * E.bucket_len(ta.nrows + tb.nrows)
        assert phases["op.concat"]["cells"] == span.attrs["cells"]
        assert rows[0][0] == ta.nrows + tb.nrows
    else:
        wins = [r for r in spans if r.name == "op.window"]
        assert [r.attrs["fn"] for r in wins] == ["rank", "sum"]
        # rank: the spec's sort reads j and v, the result goes back; sum
        # (another spec: no order) sorts by j, reads v, writes the sums
        # with their validity
        assert wins[0].attrs["cells"] == 3 * ta.plen
        assert wins[1].attrs["cells"] == 4 * ta.plen
        assert phases["op.window"]["cells"] == 7 * ta.plen
        assert len(rows) == ta.nrows


def test_a_full_outer_join_states_the_unmatched_rows_of_both_sides():
    """``op.join``'s ``cells`` = both sides' key arrays at their buckets +
    the two pair-index arrays + the unmatched-row indices of the LEFT and
    of the RIGHT side (a full outer join keeps both)."""
    s = _cells_session()
    ta, tb = s.catalog["ta"], s.catalog["tb"]
    obs_trace.drain_spans()
    l_idx, r_idx, _n, l_extra, n_lx, r_extra, n_rx = E.join_indices(
        [ta["k"], ta["j"]], [tb["k"], tb["j"]], "full",
        n_left=ta.nrows, n_right=tb.nrows)
    (span,) = [r for r in obs_trace.drain_spans()
               if isinstance(r, obs_trace.SpanRecord) and r.name == "op.join"]
    assert n_lx > 0 and n_rx > 0
    assert span.attrs["cells"] == (
        2 * ta.plen + 2 * tb.plen + 2 * int(l_idx.shape[0])
        + int(l_extra.shape[0]) + int(r_extra.shape[0]))
    assert int(l_extra.shape[0]) == E.bucket_len(n_lx)
    assert int(r_extra.shape[0]) == E.bucket_len(n_rx)
