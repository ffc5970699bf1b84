#!/usr/bin/env python3
# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Driver benchmark: Power-Run geomean query time on the available chip.

Generates raw data with the native generator, registers the tables, runs the
supported TPC-DS query set through the engine (per-query warm-up pass for
compilation, then a timed pass — the reference's Power Run times a warmed
JVM the same way), and prints ONE JSON line:

    {"metric": "power_geomean_ms", "value": N, "unit": "ms", "vs_baseline": N}

Execution model: ONE persistent child process serves queries over a line
protocol (stdin: query name, stdout: one JSON result line). The parent
enforces a per-query deadline; a wedged device RPC or crash costs only that
query — the child is killed and restarted for the remainder (a
blocked-in-C device call can hang indefinitely, which in-process watchdogs
cannot interrupt). The PARENT only imports: it never creates an array or
asks for devices, so it never initialises a JAX backend and the chip is
the child's alone (tests/test_chip_smoke.py pins that). A persistent child
amortizes the per-process costs (JAX init, 24-table load) that a chunk-per-process
model paid ~13 times over.

Deadline safety: the budget clock starts at process entry (not after data
generation), queries run cheapest-first (by baseline history) so a timeout
maximizes measured coverage, and the final JSON line is also emitted from a
SIGTERM/SIGINT handler so an external `timeout` kill still yields a parsed
result for whatever was measured.

Evidence ledger: when ``NDS_BENCH_RESULTS_JSONL`` names a file, every
measurement lands there as one validated, schema-versioned record
(nds_tpu/obs/ledger.py), flushed per query — the same file doubles as the
resume artifact. Per-query timeout budgets derive from the committed
BASELINE_TIMES.json walls x NDS_BENCH_BUDGET_HEADROOM (floor
NDS_BENCH_BUDGET_FLOOR_S, cap NDS_BENCH_QUERY_TIMEOUT_S), so ONE
pathological query gets marked ``timeout`` and the round completes instead
of dying at rc=124; a heartbeat thread (NDS_BENCH_HEARTBEAT_S) writes a
progress record + stderr line so a hung child is visible within seconds;
and finalize()/the signal handler write a terminal ``end`` record
(completed/aborted, queries done, wall) so every campaign artifact is
self-describing.

``vs_baseline`` compares against this framework's own first recorded
per-query times in the COMMITTED ``BASELINE_TIMES.json`` (cross-round
lineage, recomputable from git alone); the reference publishes no absolute
numbers (BASELINE.md).
"""

import argparse
import json
import math
import os
import queue
import signal
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

SCALE = os.environ.get("NDS_BENCH_SCALE", "0.05")
CACHE = os.path.join(REPO, ".bench_cache", f"sf{SCALE}")
PQ_CACHE = os.path.join(REPO, ".bench_cache", f"sf{SCALE}_parquet")
NDSGEN = os.path.join(REPO, "native", "ndsgen", "ndsgen")
# generous per-query allowance: cold compiles on the chip run minutes.
# This is the CAP; per-query budgets derived from baseline history
# (derive_budgets) tighten it so one wedged query can't eat the round.
PER_QUERY_TIMEOUT_S = float(os.environ.get("NDS_BENCH_QUERY_TIMEOUT_S", "420"))
# child startup: JAX init + backend attach + 24-table device load
SETUP_TIMEOUT_S = float(os.environ.get("NDS_BENCH_SETUP_TIMEOUT_S", "300"))

# per-query result fields mirrored into the in-memory perf dict (PERF.md
# columns + evidence) — ONE list, shared by the live loop and the resume
# loader so a resumed campaign regenerates an identical PERF.md
PERF_KEYS = ("hostSyncs", "syncWaitMs", "scanBytes", "scanGBps", "warmS",
             "compileS", "streamedScans", "tracePhases", "evidence",
             "faultEvents")

def ledger_mod():
    """nds_tpu/obs/ledger.py imported BY FILE PATH (shared helper): the
    module is stdlib-only, and loading it this way keeps the parent
    process off the jax import (the package root pulls jax; the chip
    belongs to the serving child alone)."""
    from tools._ledger_load import ledger_mod as _lm
    return _lm()


def faults_mod():
    """The fault registry (engine/faults.py, stdlib-only), by file path
    via the ledger loader — the ``bench-child`` seam and the restart
    backoff policy live against it without touching jax."""
    return ledger_mod()._faults_mod()


def campaign_mod():
    """The campaign module (nds_tpu/obs/campaign.py, stdlib-only), by
    file path: the arm/env-fingerprint stamp every ledger record
    carries, and the resume-fingerprint refusal."""
    from tools._ledger_load import campaign_mod as _cm
    return _cm()


def metrics_mod():
    """The live-metrics registry (nds_tpu/obs/metrics.py, stdlib-only),
    by file path: rolling throughput for the heartbeat, per-query
    ``metrics`` ledger records, and the NDS_TPU_METRICS_FILE exporter
    the heartbeat drives — all without touching jax in the parent."""
    from tools._ledger_load import metrics_mod as _mm
    return _mm()


def restart_backoff_s(restart_n: int) -> float:
    """Deterministic-JITTERED backoff before child restart ``restart_n``
    (2nd start onwards): exponential base (NDS_BENCH_RESTART_BACKOFF_S,
    default 1.0) with a hash-derived jitter fraction so co-scheduled
    campaigns against one flaky backend don't restart in lockstep —
    deterministic per restart index, so tests and wall bounds hold. The
    2-strike setup circuit breaker still bounds the total: backoff
    spaces the retries the breaker allows, it never extends them."""
    try:
        base = float(os.environ.get("NDS_BENCH_RESTART_BACKOFF_S", "1.0"))
    except ValueError:
        base = 1.0
    if base <= 0 or restart_n <= 1:
        return 0.0
    raw = base * (2 ** min(restart_n - 2, 4))
    jitter = ((restart_n * 2654435761) % 1000) / 1000.0  # [0, 1)
    return min(raw * (1.0 + 0.5 * jitter), 30.0)


def drain_parent_faults(ledger):
    """Drain the PARENT-process fault ring into ledger progress notes:
    the ``bench-child`` seam records its degrade events in THIS process
    (the child is the thing that failed), so without a parent-side drain
    that evidence would die in the ring instead of reaching the
    campaign ledger. Returns the drained events either way."""
    F = faults_mod()
    events = F.drain_fault_events()
    if ledger is not None:
        for e in events:
            ledger.progress(note="fault-event", **F.fault_event_json(e))
    return events


def ensure_data():
    if not os.path.exists(NDSGEN):
        subprocess.run(["make", "-C", os.path.dirname(NDSGEN)], check=True,
                       capture_output=True)
    marker = os.path.join(CACHE, ".complete")
    if not os.path.exists(marker):
        os.makedirs(CACHE, exist_ok=True)
        subprocess.run([NDSGEN, "-scale", SCALE, "-dir", CACHE], check=True)
        with open(marker, "w"):
            pass
    # one-time transcode: children load parquet ~5x faster than raw CSV;
    # invalidated whenever the CSV cache is newer (regenerated data)
    pq_marker = os.path.join(PQ_CACHE, ".complete")
    stale = (os.path.exists(pq_marker) and
             os.path.getmtime(pq_marker) < os.path.getmtime(marker))
    if stale or not os.path.exists(pq_marker):
        import pyarrow.parquet as pq

        from nds_tpu.io import read_raw_table
        from nds_tpu.schema import get_schemas
        os.makedirs(PQ_CACHE, exist_ok=True)
        for table, fields in get_schemas(use_decimal=True).items():
            path = os.path.join(CACHE, f"{table}.dat")
            if os.path.exists(path):
                pq.write_table(read_raw_table(path, fields),
                               os.path.join(PQ_CACHE, f"{table}.parquet"))
        with open(pq_marker, "w"):
            pass
    return PQ_CACHE


def bench_queries():
    """Supported query set: generated stream when present, else builtin q3."""
    try:
        from nds_tpu.queries import generate_query_streams, SUPPORTED_QUERIES
        from nds_tpu.power import gen_sql_from_stream
        if SUPPORTED_QUERIES:
            # stream cache keyed by scale (predicate vocabularies band by
            # scale) and by the size of the supported-query ratchet
            qdir = os.path.join(
                REPO, ".bench_cache",
                f"stream_sf{SCALE}_n{len(SUPPORTED_QUERIES)}")
            os.makedirs(qdir, exist_ok=True)
            stream_file = os.path.join(qdir, "query_0.sql")
            if not os.path.exists(stream_file):
                generate_query_streams(qdir, streams=1, rngseed=0,
                                       templates=SUPPORTED_QUERIES,
                                       scale=float(SCALE))
            queries = gen_sql_from_stream(stream_file)
            if queries:
                return list(queries.items())
    except ImportError:
        pass
    return [("query3", """
            select dt.d_year, item.i_brand_id brand_id, item.i_brand brand,
                   sum(ss_ext_sales_price) sum_agg
            from date_dim dt, store_sales, item
            where dt.d_date_sk = store_sales.ss_sold_date_sk
              and store_sales.ss_item_sk = item.i_item_sk
              and item.i_manufact_id = 128
              and dt.d_moy = 11
            group by dt.d_year, item.i_brand_id, item.i_brand
            order by dt.d_year, sum_agg desc, brand_id
            limit 100
        """)]


def order_by_history(names, baseline_file):
    """Cheapest-first by baseline history; unmeasured queries go last.

    When the budget runs out mid-run this maximizes the number of measured
    queries, and pushes historically-absent outliers (e.g. an OOM-prone
    query) where their failure can't shadow cheap coverage."""
    try:
        with open(baseline_file) as f:
            hist = json.load(f).get("times") or {}
    except (OSError, ValueError):
        hist = {}
    known = sorted((n for n in names if n in hist), key=lambda n: hist[n])
    unknown = [n for n in names if n not in hist]
    return known + unknown


def derive_budgets(names, baseline_file, headroom=None, floor_s=None,
                   cap_s=None, scale=None):
    """Per-query timeout budgets (seconds) from the committed baseline
    walls x a headroom factor — the fix for a run that ended at rc 124
    with no value: the kill ate the whole round because the only
    deadline was the generous global cap, so one
    wedged query cost everything after it. A query with history gets
    ``baseline_ms/1000 x headroom`` clamped to [floor, cap]; the floor
    absorbs cold-compile time (up to ~35 s on the widest templates —
    warm baseline walls don't include it), the cap is the old global
    allowance. Queries with no history keep the cap: their first
    measurement must not be killed by a budget nobody derived.

    The committed baseline lineage is BENCH-SCALE history (SF 0.05): at
    any other ``scale`` the walls are incommensurable (SF10 runs
    minutes/query), so derivation is OFF — every query keeps the cap —
    unless the operator opted in by setting NDS_BENCH_BUDGET_HEADROOM
    (or passing ``headroom``) explicitly for that campaign."""
    explicit = (headroom is not None
                or "NDS_BENCH_BUDGET_HEADROOM" in os.environ)
    if headroom is None:
        headroom = float(os.environ.get("NDS_BENCH_BUDGET_HEADROOM", "25"))
    if floor_s is None:
        floor_s = float(os.environ.get("NDS_BENCH_BUDGET_FLOOR_S", "90"))
    if cap_s is None:
        cap_s = PER_QUERY_TIMEOUT_S
    if scale not in (None, "0.05") and not explicit:
        return {n: cap_s for n in names}
    try:
        with open(baseline_file) as f:
            hist = json.load(f).get("times") or {}
    except (OSError, ValueError):
        hist = {}
    return {n: min(max(hist[n] / 1e3 * headroom, floor_s), cap_s)
            if n in hist else cap_s for n in names}


def run_server():
    """Persistent child: load tables once, then serve query names from
    stdin, one JSON result line on stdout each."""
    data_dir = ensure_data()
    from nds_tpu.engine.session import Session
    from nds_tpu.schema import get_schemas

    wanted = dict(bench_queries())
    sess = Session()
    for table, fields in get_schemas(use_decimal=True).items():
        path = os.path.join(data_dir, f"{table}.parquet")
        if os.path.exists(path):
            sess.read_columnar_view(
                table, path, "parquet",
                canonical_types={f.name: f.type for f in fields})
    # provenance: the device that actually executes, as JAX reports it,
    # stamped into PERF.md by the parent (a run once spent 3000 s against
    # a chip that never came up — the header must say what really ran,
    # not assume). Asking must not fail quietly: a child that cannot name
    # its device dies here and the parent's setup breaker counts it.
    import jax as _jax
    devices = _jax.devices()
    device = devices[0]
    print(json.dumps({"ready": True, "platform": device.platform,
                      "device_kind": device.device_kind,
                      "device_count": len(devices)}), flush=True)

    from nds_tpu.engine import ops as _ops

    _ops.enable_compile_meter()
    for line in sys.stdin:
        name = line.strip()
        if not name:
            break
        try:
            sql = wanted[name]
            c0 = _ops.compile_ns()
            tw = time.perf_counter()
            sess.sql(sql).collect()                  # warmup: compile
            # hybrid replay ('auto'): a high-sync query transitions
            # eager -> record+compile -> first trace over its next sights;
            # fold those into warmup so the timed passes below measure
            # steady state (the reference times a warmed JVM the same way)
            for _ in range(3):
                if not sess.replay_pending(sql):
                    break
                sess.sql(sql).collect()
            # min of two timed passes: a one-chip machine shares its
            # host's cores, and host-clock walls have shown 2x swings on a
            # fixed query; min-of-2 reports the steadier of the two
            t0 = time.perf_counter()
            sess.sql(sql).collect()
            t1 = time.perf_counter()
            # roofline decomposition measured on the final pass (sync
            # counts are deterministic per query; wait time is weather)
            from nds_tpu.obs import evidence as obs_evidence
            from nds_tpu.obs import export as obs_export
            evidence = obs_evidence.begin()   # only the final pass's scans
            sess.sql(sql).collect()
            t2 = time.perf_counter()
            ev = evidence.end()
            stream_events = ev["streamEvents"]
            ms = min(t1 - t0, t2 - t1) * 1000.0
            syncs = ev["hostSyncs"]
            sync_ms = ev["syncWaitMs"]
            scan = sum(getattr(sess, "last_scanned", {}).values())
            gbps = scan / max(t2 - t1, 1e-9) / 1e9
            # measured compile split (jax monitoring): the warm pass's
            # XLA backend-compile seconds — ~0 on a persistent-cache hit
            compile_s = (_ops.compile_ns() - c0) / 1e9
            print(f"# {name}: warm {t0 - tw:.1f}s (compile "
                  f"{compile_s:.1f}s) timed {ms/1000:.2f}s "
                  f"syncs {syncs} syncWait {sync_ms:.0f}ms "
                  f"scan {gbps:.2f}GB/s",
                  file=sys.stderr)
            result = {
                "name": name, "ms": ms, "hostSyncs": syncs,
                "syncWaitMs": round(sync_ms, 1), "scanBytes": scan,
                "scanGBps": round(gbps, 3),
                # warm pass wall = XLA compile (+1 exec): the per-query
                # compile-cost axis the SF10 scaling question turns on
                "warmS": round(t0 - tw, 2),
                "compileS": round(compile_s, 2)}
            if stream_events:
                # >HBM streamed scans: which path served each (compiled
                # chunk pipeline vs eager chunk loop), chunk/sync counts
                # — the per-query face of the streamed sync budget —
                # plus the aggregated evidence dict the campaign ledger
                # records (computed HERE from the live events, so the
                # parent's ledger write need not re-derive it)
                from nds_tpu.listener import stream_evidence
                result["streamedScans"] = ev["streamedScans"]
                result["evidence"] = stream_evidence(stream_events)
            # fault-recovery evidence (engine/faults.py): every seam
            # recovery since the previous query — retries, degradation
            # ladder steps, watchdog timeouts — next to streamedScans,
            # so a fallback that fired in production is benchmark
            # evidence, not log noise
            if ev["faultEvents"]:
                result["faultEvents"] = ev["faultEvents"]
            roll = ev["rollup"]
            if ev["records"]:
                # per-phase attribution of the final timed pass (obs
                # layer; zero added syncs): plan vs drive vs materialize
                # per query, plus top sync-charging host-read sites
                result["tracePhases"] = roll
                trace_d = os.environ.get("NDS_BENCH_TRACE_DIR")
                if trace_d:
                    os.makedirs(trace_d, exist_ok=True)
                    obs_export.write_chrome_trace(
                        os.path.join(trace_d, f"{name}.trace.json"),
                        ev["records"], query=name, roll=roll)
            # per-query HBM footprint where the backend exposes
            # allocator stats (a TPU does; the CPU backend returns None)
            stats = device.memory_stats()
            if stats:
                result["hbmBytesInUse"] = int(
                    stats.get("bytes_in_use", 0))
                result["peakHbmBytes"] = int(
                    stats.get("peak_bytes_in_use", 0))
            print(json.dumps(result), flush=True)
        except Exception as e:                        # keep serving
            print(json.dumps(error_result(name, e)), flush=True)


def error_result(name, exc):
    """The serving loop's one failure-path result line (child side,
    engine loaded): classified status plus THIS query's drained fault
    evidence — left in the thread ring, a failed query's events (incl.
    the watchdog's `timeout`) would misattribute to the NEXT query's
    drain on the success path."""
    from nds_tpu.engine.faults import (StatementTimeout,
                                       drain_fault_events,
                                       fault_event_json)
    out = {"name": name, "error": f"{type(exc).__name__}: {exc}"[:300]}
    fault_events = drain_fault_events()
    if fault_events:
        out["faultEvents"] = [fault_event_json(ev) for ev in fault_events]
    if isinstance(exc, StatementTimeout):
        # the statement watchdog fired: the parent marks the query
        # `timeout` (its classified status), not `error`
        out["timeout"] = True
    return out


def _geomean(vals):
    return math.exp(sum(math.log(max(v, 1e-3)) for v in vals) / len(vals))


def resolve_baseline(baseline_file, times, n_total):
    """vs_baseline policy: the baseline stores each query's FIRST recorded
    time. Any run fills in queries the baseline lacks (so a partial run
    seeds, and an OOM-bound outlier joins whenever it first succeeds) but
    never overwrites an existing entry — the comparison stays longitudinal
    against the first measurement. vs_baseline is the geomean ratio over
    the common query set.

    The baseline is a COMMITTED file (BASELINE_TIMES.json): losing it
    would silently restart the lineage and make vs_baseline compare a
    round against itself (this happened in round 3 when the scratch copy
    was reseeded). A missing file is therefore an explicit, loud event."""
    base = None
    if os.path.exists(baseline_file):
        try:
            with open(baseline_file) as f:
                base = json.load(f)
        except ValueError:
            base = None
    if base is None and not os.environ.get("NDS_BENCH_SEED_BASELINE"):
        print(f"# {os.path.basename(baseline_file)} missing or unreadable: "
              "REFUSING to start a new baseline lineage (restore it from "
              "git, or set NDS_BENCH_SEED_BASELINE=1 to seed one on "
              "purpose); vs_baseline reported as 0.0", file=sys.stderr)
        return 0.0
    base_times = (base or {}).get("times") or {}
    common = sorted(set(times) & set(base_times))
    vs = (_geomean([base_times[q] for q in common]) /
          _geomean([times[q] for q in common])) if common else 1.0
    merged = dict(base_times)
    for q, t in times.items():
        merged.setdefault(q, t)
    if merged != base_times:
        out = {"metric": "power_geomean_ms",
               "value": _geomean(list(merged.values())),
               "n_queries": len(merged), "times": merged}
        if isinstance(base, dict) and "note" in base:
            out["note"] = base["note"]
        with open(baseline_file, "w") as f:
            json.dump(out, f, indent=1, sort_keys=True)
    return vs


class ChildServer:
    """Supervises the persistent serving child with per-request deadlines."""

    def __init__(self):
        self.proc = None
        self.lines = None

    def _reader(self, proc, q):
        for line in proc.stdout:
            q.put(line)
        q.put(None)

    def start(self, deadline_left):
        self.stop()
        F = faults_mod()
        try:
            # bench-child seam (transient): an injected start fault
            # takes the same path as a real setup failure — the caller's
            # backoff + 2-strike circuit breaker own the recovery
            F.fault_point("bench-child")
        except F.FaultInjected as exc:
            F.record_fault_event("bench-child", "degrade",
                                 detail=str(exc)[:200])
            return None
        self.proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--serve"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        self.lines = queue.Queue()
        threading.Thread(target=self._reader,
                         args=(self.proc, self.lines), daemon=True).start()
        msg = self._next_json(min(SETUP_TIMEOUT_S, deadline_left))
        if not (msg and msg.get("ready")):
            # a slow-to-start child left alive would desync the protocol:
            # its late "ready" line would be consumed as a query response
            self.stop()
            return None
        return msg

    def _next_json(self, timeout):
        end = time.perf_counter() + timeout
        while True:
            left = end - time.perf_counter()
            if left <= 0:
                return None
            try:
                line = self.lines.get(timeout=left)
            except queue.Empty:
                return None
            if line is None:
                return None
            try:
                return json.loads(line)
            except ValueError:
                continue                              # stray non-JSON chatter

    def run_query(self, name, timeout):
        try:
            self.proc.stdin.write(name + "\n")
            self.proc.stdin.flush()
        except (BrokenPipeError, OSError):
            return None
        return self._next_json(timeout)

    def alive(self):
        return self.proc is not None and self.proc.poll() is None

    def stop(self):
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass
        self.proc = None


def perf_text(times, perf, platform="unknown", scale=None):
    """Render the PERF.md roofline table as text — DETERMINISTIC in its
    inputs (sorted queries, no clocks), so the same ledger always
    regenerates the identical document (``tools/bench_compare.py
    --emit-perf`` makes PERF.md a derived artifact, never hand-edited)."""
    scale = SCALE if scale is None else scale
    rows = sorted(times)
    tot_sync = sum(p.get("syncWaitMs", 0) for p in perf.values())
    tot_ms = sum(times.values())
    streamed = [e for p in perf.values()
                for e in p.get("streamedScans", [])]
    out = ["# Power Run roofline decomposition", "",
           f"Scale factor {scale}; warm min-of-2 wall times; "
           f"platform: {platform}.",
           f"Aggregate: {len(times)} queries, "
           f"{tot_sync / max(tot_ms, 1e-9) * 100:.1f}% of summed wall "
           "time blocked on device->host reads."]
    if streamed:
        n_comp = sum(1 for e in streamed if e["path"] == "compiled")
        out.append(f"Streamed >HBM scans: {len(streamed)} "
                   f"({n_comp} compiled chunk pipeline, "
                   f"{len(streamed) - n_comp} eager fallback).")
    out.append("")
    out.append("| query | wall ms | warm s | compile s | host syncs | "
               "sync wait ms | scan MB | scan GB/s |")
    out.append("|---|---|---|---|---|---|---|---|")
    for q in rows:
        p = perf.get(q, {})
        out.append(f"| {q} | {times[q]:.0f} | {p.get('warmS', '-')} | "
                   f"{p.get('compileS', '-')} | "
                   f"{p.get('hostSyncs', '-')} | "
                   f"{p.get('syncWaitMs', '-')} | "
                   f"{p.get('scanBytes', 0) / 1e6:.1f} | "
                   f"{p.get('scanGBps', '-')} |")
    return "\n".join(out) + "\n"


def bench_perf_path():
    """Where a campaign's roofline table goes unless the caller names a
    path: ``<repo>/chiprun_out/BENCH_PERF.md``, beside the run's other
    outputs. Never ``<repo>/PERF.md``: that file is the builders' account
    of the benchmark, and a campaign run used to overwrite it."""
    return os.path.join(REPO, "chiprun_out", "BENCH_PERF.md")


def write_perf(times, perf, platform="unknown", path=None):
    """The campaign's per-query roofline table (wall, host-sync count and
    blocked time, bytes scanned, effective bandwidth) the geomean headline
    decomposes into, written to ``path`` (default
    :func:`bench_perf_path`) so 'is it fast?' is answerable from
    artifacts (device vs host split per query).
    ``platform`` is the serving child's ``jax.devices()[0].platform`` —
    real provenance, not an assumed "attached chip"."""
    if not perf:
        return
    path = path or bench_perf_path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(perf_text(times, perf, platform))


_emitted = False


def emit(times, n_total, aborted=None):
    """Print the one JSON metric line (idempotent; also the signal path).
    ``aborted`` labels a fail-fast partial artifact (circuit breaker) so a
    collector can tell "measured everything" from "gave up early"."""
    global _emitted
    if _emitted:
        return
    _emitted = True
    if not times:
        out = {"metric": "power_geomean_ms", "value": None,
               "unit": "ms", "vs_baseline": 0.0, "n_queries": 0}
        if aborted:
            out["aborted"] = aborted
        print(json.dumps(out))
        return
    geomean = _geomean(list(times.values()))
    try:
        vs = resolve_baseline(os.path.join(REPO, "BASELINE_TIMES.json"),
                              times, n_total)
    except Exception as exc:
        # the metric line must survive a baseline-write failure — this
        # path also runs from the SIGTERM handler of an externally
        # timed-out campaign, where losing the partial geomean repeats
        # the run that ended at rc 124 with no value
        print(f"# baseline update failed: {exc}", file=sys.stderr)
        vs = 0.0
    out = {
        "metric": "power_geomean_ms",
        "value": round(geomean, 3),
        "unit": "ms",
        "vs_baseline": round(vs, 4),
        "n_queries": len(times),
    }
    if aborted:
        out["aborted"] = aborted
    print(json.dumps(out), flush=True)


def finalize(times, perf, n_total, platform="unknown", aborted=None,
             ledger=None, wall_s=None, end_reason=None):
    """Flush everything the campaign measured so far: the PERF.md
    roofline table, the one JSON metric line, and the ledger's terminal
    ``end`` record (``completed``/``aborted``, queries done, wall
    seconds) — the self-describing close every campaign artifact now
    carries. Runs at normal end AND from the SIGTERM/SIGINT handler, so
    an external ``timeout`` kill (rc=124) still records the partial
    geomean of every completed query instead of
    ``{"value": null, "n_queries": 0}``. Each step is isolated: a
    PERF.md write failure must not eat the metric line, and neither may
    eat the terminal record."""
    try:
        write_perf(times, perf, platform)
    except Exception as exc:
        print(f"# PERF.md write failed: {exc}", file=sys.stderr)
    emit(times, n_total, aborted)
    if ledger is not None:
        reason = end_reason or aborted
        status = "aborted" if reason else "completed"
        fields = {"queries": len(times), "total": n_total,
                  "platform": platform}
        if wall_s is not None:
            fields["wallS"] = round(wall_s, 1)
        if reason:
            fields["reason"] = reason
        try:
            ledger.close(status, **fields)
        except Exception as exc:
            print(f"# ledger terminal write failed: {exc}", file=sys.stderr)


def load_resume(path, times, perf):
    """Pre-populate times/perf from a previous campaign's ledger so an
    at-scale run (SF10: minutes/query) is resumable across invocations —
    measured queries are never re-paid (round-4 verdict: the first SF10
    campaign stopped at 30/103 and the partial work was lost). Ported
    onto the ledger loader: records are schema-validated (an
    unknown-version ledger refuses loudly instead of misreading), legacy
    pre-ledger resume lines still load, a torn final line from a kill is
    absorbed, and only status-``ok`` records resume — a ``timeout`` or
    ``error`` query is re-attempted, never trusted. Returns the platform
    the original campaign stamped (meta record), or None: a rerun
    satisfied entirely from the resume file starts no child and would
    otherwise overwrite PERF.md's real provenance with "unknown"."""
    if not path or not os.path.exists(path):
        return None
    data = ledger_mod().load_ledger(path)
    # mixed-arm refusal: a ledger stamped under different knobs must not
    # be resumed — the merged artifact would silently blend two
    # experiments (CampaignResumeError names both fingerprints)
    C = campaign_mod()
    C.check_resume_fingerprint(data.meta.get("envFingerprint"),
                               C.env_fingerprint(), path)
    if data.torn:
        print("# resume ledger: torn final line (in-flight statement of "
              "a kill) dropped", file=sys.stderr)
    for name, rec in data.queries.items():
        if rec["status"] == "ok" and "ms" in rec:
            times[name] = rec["ms"]
            perf[name] = {k: rec[k] for k in PERF_KEYS if k in rec}
    return data.platform


def run_parent(t_entry):
    budget_s = float(os.environ.get("NDS_BENCH_BUDGET_S", "3000"))
    # margin so the final JSON + baseline write always beat an external kill
    margin_s = 20.0
    times = {}
    perf = {}
    names = []
    child = ChildServer()
    resume_path = os.environ.get("NDS_BENCH_RESULTS_JSONL")
    resume_platform = load_resume(resume_path, times, perf)
    ledger = None
    if resume_path:
        # the stamp rides EVERY record (arm name + env fingerprint):
        # cross-arm merges key on recorded provenance, and load_resume's
        # fingerprint refusal has something to check on the next rerun
        ledger = ledger_mod().Ledger(resume_path, driver="bench",
                                     scale=SCALE,
                                     stamp=campaign_mod().campaign_stamp())
    # defined BEFORE the handlers register: a kill during data
    # generation must find every name the handler reads
    platform = resume_platform or "unknown"
    # heartbeat status snapshot, updated by the main loop and read by the
    # heartbeat thread (plain dict: GIL-atomic single-key writes)
    live = {"query": None, "done": len(times), "total": 0}
    # live-metrics registry (nds_tpu/obs/metrics.py): fed as results
    # arrive in THIS loop (the parent's existing evidence point), read
    # by the heartbeat for rolling queries/min + EWMA wall and exported
    # to NDS_TPU_METRICS_FILE on the heartbeat cadence
    metrics_reg = metrics_mod().default()
    metrics_reg.reset()

    def on_signal(signum, frame):
        # an external `timeout` kill lands here: flush the completed
        # per-query results (PERF.md + partial-geomean metric line +
        # terminal ledger record) before the -k SIGKILL grace runs out
        finalize(times, perf, len(names), platform, ledger=ledger,
                 wall_s=time.perf_counter() - t_entry, end_reason="signal")
        child.stop()          # free the chip before exiting
        os._exit(0)

    signal.signal(signal.SIGTERM, on_signal)
    signal.signal(signal.SIGINT, on_signal)

    ensure_data()                                    # once, before the child
    names = [n for n, _ in bench_queries()]
    baseline_file = os.path.join(REPO, "BASELINE_TIMES.json")
    ordered = order_by_history(names, baseline_file)
    budgets = derive_budgets(names, baseline_file, scale=SCALE)
    restarts = 0

    def left():
        return budget_s - margin_s - (time.perf_counter() - t_entry)

    pending = [n for n in ordered if n not in times]
    live["total"] = len(names)
    if times:
        print(f"# resume: {len(times)} queries pre-loaded from "
              f"{os.path.basename(resume_path)}", file=sys.stderr)
    # liveness: a hung child is visible within seconds (progress record +
    # stderr line), not at the rc=124 autopsy; 0 disables
    hb_interval = float(os.environ.get("NDS_BENCH_HEARTBEAT_S", "15"))
    heartbeat = None
    if hb_interval > 0:
        # progress context plus the registry's rolling throughput
        # (queries/min, EWMA query wall) — the rolling numbers replace
        # the static counters as the liveness throughput signal in both
        # the ledger progress record and the stderr line
        heartbeat = ledger_mod().Heartbeat(
            hb_interval, ledger=ledger,
            status=lambda: {**{k: v for k, v in live.items()
                               if v is not None},
                            **metrics_reg.heartbeat_rollup()}).start()
    attempts = {}
    aborted = None
    setup_fails = 0
    try:
        while pending and left() > 0:
            if not child.alive():
                if restarts > 6:                      # crash-looping backend
                    break
                restarts += 1
                # jittered backoff BETWEEN restarts (2nd start onwards):
                # a crashing backend gets breathing room instead of an
                # immediate hammer, before the 2-strike breaker trips
                back = min(restart_backoff_s(restarts), max(left(), 0.0))
                if back > 0:
                    print(f"# child restart {restarts}: backing off "
                          f"{back:.1f}s", file=sys.stderr)
                    time.sleep(back)
                ready = child.start(left())
                # bench-child seam evidence (an injected or real start
                # fault) lands in the parent's own ring — ledger it now
                drain_parent_faults(ledger)
                if ready is None:
                    # circuit breaker: a run once burned its whole 3000s
                    # budget on six consecutive 300s setup timeouts against
                    # a backend that never came up — after 2 in a row, stop
                    # paying and emit the labeled partial artifact instead
                    setup_fails += 1
                    if setup_fails >= 2:
                        aborted = "child-setup-failure"
                        print(f"# {setup_fails} consecutive child-setup "
                              "failures: backend is not coming up; "
                              "failing fast with a partial artifact",
                              file=sys.stderr)
                        break
                    continue                          # dead child -> retry
                setup_fails = 0
                new_plat = ready.get("platform", "unknown")
                if new_plat != "unknown" and new_plat != platform:
                    platform = new_plat
                    if ledger is not None:
                        # provenance meta record: lets a later rerun that
                        # never starts a child still stamp the real platform
                        ledger.meta(driver="bench", platform=platform)
            name = pending.pop(0)
            attempts[name] = attempts.get(name, 0) + 1
            live["query"] = name
            # per-query budget: baseline wall x headroom, so one
            # pathological query costs its budget, not the round
            per_q = budgets.get(name, PER_QUERY_TIMEOUT_S)
            deadline = min(per_q, left())
            msg = child.run_query(name, deadline)
            if msg is None:                           # wedged or crashed
                # the abort cause drives at-scale diagnosis: a dead child
                # is a crash (OOM, device fault — its exit code says
                # which); a live one blew a deadline — named truthfully:
                # its own derived budget, or the ROUND's remaining budget
                # (a healthy query killed by round exhaustion must not be
                # blamed on a per-query budget that never limited it)
                if child.alive():
                    status = "timeout"
                    limiter = "budget" if deadline >= per_q \
                        else "round-budget"
                    cause = f"timeout after {deadline:.0f}s ({limiter})"
                else:
                    status = "error"
                    cause = f"child crashed (exit {child.proc.returncode})"
                print(f"# {name} aborted ({cause}); restarting child",
                      file=sys.stderr)
                metrics_reg.inc("queries.total")
                metrics_reg.inc(f"queries.{status}")
                child.stop()
                if ledger is not None:
                    rec = {"error": cause, "budgetS": round(deadline, 1),
                           "attempt": attempts[name]}
                    if status == "timeout":
                        # machine-readable limiter: bench_compare must
                        # not count a round-budget kill as a query that
                        # "stopped completing" (it was never given its
                        # own budget)
                        rec["limiter"] = limiter
                    ledger.query(name, status=status, **rec)
                if attempts[name] < 2:                # one retry, at the end
                    pending.append(name)
                continue
            if "ms" in msg:
                times[msg["name"]] = msg["ms"]
                perf[msg["name"]] = {k: msg[k]
                                     for k in PERF_KEYS if k in msg}
                live["done"] = len(times)
                M = metrics_mod()
                metrics_reg.inc("queries.total")
                metrics_reg.inc("queries.ok")
                metrics_reg.observe(M.QUERY_WALL, msg["ms"])
                if msg.get("syncWaitMs"):
                    metrics_reg.observe(M.SYNC_WAIT, msg["syncWaitMs"])
                if msg.get("faultEvents"):
                    metrics_reg.inc("faults.total",
                                    len(msg["faultEvents"]))
                if ledger is not None:
                    ledger.query(msg["name"], status="ok",
                                 **{k: v for k, v in msg.items()
                                    if k != "name"})
                    # the rolling rollup as of this query: queries/min,
                    # rolling wall quantiles, EWMA — the per-query
                    # metrics record (same vocabulary as power.py's)
                    ledger.metrics(scope="query", query=msg["name"],
                                   **metrics_reg.query_rollup())
            else:
                print(f"# {name} failed: {msg.get('error')}",
                      file=sys.stderr)
                metrics_reg.inc("queries.total")
                metrics_reg.inc("queries.timeout" if msg.get("timeout")
                                else "queries.error")
                if ledger is not None:
                    # an in-process watchdog expiry (StatementTimeout)
                    # is a classified `timeout`, not an `error`: the
                    # statement was marked, the child kept serving
                    status = "timeout" if msg.get("timeout") else "error"
                    rec = {"error": str(msg.get("error"))[:300],
                           "attempt": attempts[name]}
                    if msg.get("faultEvents"):
                        rec["faultEvents"] = msg["faultEvents"]
                    ledger.query(name, status=status, **rec)
    finally:
        child.stop()
        if heartbeat is not None:
            heartbeat.stop()

    if times and len(times) < len(names):
        print(f"# measured {len(times)}/{len(names)} queries",
              file=sys.stderr)
    # a loop that exits with work pending and no abort label ran out of
    # budget (or crash-looped): the terminal record must say so
    end_reason = None if aborted else ("incomplete" if pending else None)
    finalize(times, perf, len(names), platform, aborted, ledger=ledger,
             wall_s=time.perf_counter() - t_entry, end_reason=end_reason)
    if not times:
        sys.exit(1)


def main():
    t_entry = time.perf_counter()
    ap = argparse.ArgumentParser()
    ap.add_argument("--serve", action="store_true",
                    help="persistent child: serve queries over stdin/stdout")
    args = ap.parse_args()
    if args.serve:
        run_server()
    else:
        run_parent(t_entry)


if __name__ == "__main__":
    main()
