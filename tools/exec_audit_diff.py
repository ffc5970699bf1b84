# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Differential validation of the static execution auditor.

The exec auditor (``nds_tpu/analysis/exec_audit.py``) is a *model* of the
streaming executor's routing and of the engine's sync effects; a model
nobody checks drifts. This harness replays the ``tests/test_synccount.py``
A/B templates — the same four statements whose runtime behavior tier-1
pins — through the real engine on a chunked toy session, drains the
``StreamEvent`` listener evidence, and fails when the static prediction
disagrees with what actually ran:

* **path** — a template the auditor classifies ``compiled-stream`` must
  produce a ``compiled`` StreamEvent (and ``eager-fallback`` an ``eager``
  one), on the cold sight and the warm (pipeline-cached) sight;
* **sync count** — for compiled templates, the runtime's warm host-sync
  total must fit the static ``sync_bound``, the cold total must fit
  ``sync_bound + first_sight``, and every compiled scan's ``gate_bound``
  must respect the streamed-path budget (:data:`exec_audit.SYNC_BUDGET`);
* **trace-layer parity** — the obs span tracer (``nds_tpu/obs``) is
  sync-free by contract, and its per-scan ``stream`` span bridges the
  same ``ops.sync_count()`` window the ``StreamEvent`` charges. Each
  drained span's sync delta must EQUAL its StreamEvent's ``syncs`` on
  every sight — if the trace layer ever started paying for its own
  metrics (or drifted off the event window), span > event and this
  harness fails before the budget tests would;
* **partition pass** — the whole A/B set executes under
  ``NDS_TPU_STREAM_PARTITIONS=2``, so the fan-out templates
  (``_STREAM_AB_PARTITIONED``) must take the grace-style PARTITIONED
  compiled pipeline (StreamEvent ``partitions`` > 1), every drained
  ``stream.partition`` span must carry a ZERO sync delta (the radix pass
  is device-only by construction), and the sync/budget checks above hold
  unchanged — the partition pass is sync-free, so no bound moves.

* **collective budget** — a SECOND mini-sweep drives the sharded subset
  (``_STREAM_AB_SHARDED``: star join, psum'd grouped aggregate, fan-out
  partitioned join) through the shard_map'd pipeline under a forced
  2-shard mesh (``NDS_TPU_STREAM_SHARDS``, the shared
  ``_forced_stream_shards`` context; the harness forces a multi-device
  virtual CPU mesh via XLA_FLAGS below). Every event must report the
  forced shard count, its measured ``StreamEvent.collectives`` (the
  trace-time explicit-collective accounting of
  ``parallel.exchange.collective_trace``) must fit the static budget
  ``a2a_chunk x chunks + coll_final``, and the exchange/partition spans
  must charge ZERO host syncs. The partitioned template must actually
  exchange (nonzero collectives), so ``--inject-drift`` — which zeroes
  the static collective budget on this sweep — must fail.

``--inject-drift`` flips every predicted path (and zeroes the collective
budget) before comparing — a model-drift fixture that MUST fail, proving
the harness can catch a stale model (``tests/test_analysis.py`` asserts
both directions). Run it after any change to
``Planner._stream_join_parts``, ``engine/stream.py`` routing, or the
sync behavior of ``engine/ops.py``: the static model and the executor
are kept in lockstep the same way ``plan_audit`` tracks
``Planner._resolve_name``.
"""

import argparse
import importlib.util
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the sharded sweep needs a multi-device mesh: force the virtual CPU
# devices BEFORE jax initializes (no-op when the caller already did —
# tests/conftest.py forces 8)
if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()


def _load_ab_module():
    path = os.path.join(REPO, "tests", "test_synccount.py")
    spec = importlib.util.spec_from_file_location("_synccount_fixtures",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_ab_templates():
    """The canonical A/B statements + the chunked toy session builder, from
    tests/test_synccount.py — importing the pinned definitions keeps the
    harness and the tier-1 budget tests on the same fixtures by
    construction."""
    mod = _load_ab_module()
    return mod._STREAM_AB_QUERIES, mod._chunked_star_session


def collect_runtime_evidence():
    """Execute each A/B template twice (cold: record+compile; warm:
    pipeline-cache hit) under NDS_TPU_STREAM_PARTITIONS=2 and return
    per-template evidence dicts."""
    import numpy as np

    from nds_tpu.engine import ops as E
    from nds_tpu.listener import drain_stream_events
    from nds_tpu.obs import trace as obs_trace

    mod = _load_ab_module()
    queries = mod._STREAM_AB_QUERIES
    partitioned = set(getattr(mod, "_STREAM_AB_PARTITIONED", ()))
    # forced partition count: the ONE context manager the fixture module
    # ships, so the fixtures and every checker force the same count
    with mod._forced_stream_partitions():
        session = mod._chunked_star_session(np.random.default_rng(42))
        drain_stream_events()
        traced = obs_trace.on()
        obs_trace.drain_spans()
        evidence = []
        for i, (sql, _must_stream) in enumerate(queries):
            runs = []
            for sight in ("cold", "warm"):
                before = E.sync_count()
                rows = session.sql(sql).collect()
                used = E.sync_count() - before
                events = drain_stream_events()
                records = obs_trace.drain_spans()
                # per-scan spans from the trace layer, execution order:
                # each must carry the same sync delta its StreamEvent
                # recorded
                spans = [r for r in records
                         if getattr(r, "name", "") == "stream"
                         and r.attrs.get("path")]
                part_spans = [r for r in records
                              if getattr(r, "name", "")
                              == "stream.partition"]
                runs.append({
                    "sight": sight, "syncs": used,
                    "paths": [e.path for e in events],
                    "reasons": [e.reason for e in events if e.reason],
                    "event_syncs": [e.syncs for e in events],
                    "partitions": [e.partitions for e in events],
                    "span_paths": [s.attrs.get("path") for s in spans],
                    "span_syncs": [s.syncs for s in spans],
                    "part_span_count": len(part_spans),
                    "part_span_syncs": sum(s.syncs for s in part_spans),
                    "rows": len(rows),
                })
            evidence.append({"sql": sql, "cold": runs[0], "warm": runs[1],
                             "traced": traced,
                             "must_partition": i in partitioned})
    return evidence


def predict(queries):
    from nds_tpu.analysis.exec_audit import ExecAuditor
    auditor = ExecAuditor(streamed={"store_sales"})
    return [auditor.audit_sql(sql, query=f"ab{i + 1}")
            for i, (sql, _must) in enumerate(queries)]


def collect_sharded_evidence():
    """Drive the sharded subset through the shard_map'd pipeline (forced
    shard count + forced partitions, both via the fixture module's shared
    contexts) and return (per-template evidence, forced shard count).
    Empty evidence when this process lacks a multi-device mesh."""
    import jax
    import numpy as np

    from nds_tpu.engine import ops as E
    from nds_tpu.listener import drain_stream_events
    from nds_tpu.obs import trace as obs_trace

    mod = _load_ab_module()
    queries = mod._STREAM_AB_QUERIES
    out = []
    with mod._forced_stream_partitions():
        with mod._forced_stream_shards() as n_shards:
            if len(jax.local_devices()) < n_shards:
                return [], n_shards
            session = mod._chunked_star_session(np.random.default_rng(42))
            drain_stream_events()
            obs_trace.drain_spans()
            for i in getattr(mod, "_STREAM_AB_SHARDED", ()):
                sql, _must = queries[i]
                runs = []
                for sight in ("cold", "warm"):
                    before = E.sync_count()
                    rows = session.sql(sql).collect()
                    used = E.sync_count() - before
                    events = drain_stream_events()
                    records = obs_trace.drain_spans()
                    coll_spans = [r for r in records
                                  if getattr(r, "name", "")
                                  in ("stream.exchange",
                                      "stream.partition")]
                    runs.append({
                        "sight": sight, "syncs": used,
                        "paths": [e.path for e in events],
                        "shards": [e.shards for e in events],
                        "chunks": [e.chunks for e in events],
                        "collectives": [e.collectives for e in events],
                        "bytes_ici": [e.bytes_ici for e in events],
                        "coll_span_syncs": sum(s.syncs
                                               for s in coll_spans),
                        "rows": len(rows),
                    })
                out.append({"idx": i, "sql": sql,
                            "cold": runs[0], "warm": runs[1],
                            "must_partition":
                            i in mod._STREAM_AB_PARTITIONED})
    return out, n_shards


def compare_sharded(reports, shard_ev, n_shards, inject_drift=False):
    """Check the static collective budget against the sharded runtime
    evidence; ``inject_drift`` zeroes the budget first (must fail)."""
    ok = True
    lines = []
    for ev in shard_ev:
        rep = reports[ev["idx"]]
        scan = next((s for s in rep.scans if s.compiled), None)
        head = f"[{rep.query}] sharded S={n_shards}"
        problems = []
        if scan is None or scan.shards != n_shards:
            problems.append(
                f"model predicts shards="
                f"{getattr(scan, 'shards', None)}, the sweep forced "
                f"{n_shards} (model drift)")
            a2a = fin = 0
        else:
            a2a, fin = scan.a2a_chunk, scan.coll_final
        if inject_drift:
            a2a = fin = 0
        for sight in ("cold", "warm"):
            r = ev[sight]
            if set(r["paths"]) != {"compiled"}:
                problems.append(f"{sight} path {r['paths']} != compiled")
            if set(r["shards"]) - {n_shards}:
                problems.append(f"{sight} ran shards {r['shards']}, "
                                f"forced {n_shards}")
            for coll, chunks in zip(r["collectives"], r["chunks"]):
                bound = a2a * chunks + fin
                if coll > bound:
                    problems.append(
                        f"{sight} issued {coll} collectives > static "
                        f"budget {a2a}/chunk x {chunks} + {fin} = {bound}")
            if ev["must_partition"] and not inject_drift and \
                    any(c <= 0 for c in r["collectives"]):
                problems.append(
                    f"{sight} partitioned sharded run reported "
                    f"collectives {r['collectives']}: the exchange pass "
                    "never crossed shards")
            if r["coll_span_syncs"]:
                problems.append(
                    f"{sight} exchange/partition spans charged "
                    f"{r['coll_span_syncs']} host syncs; the exchange "
                    "pass must be device-only (0)")
        if not ev["warm"]["rows"]:
            problems.append("sharded A/B template returned no rows")
        if problems:
            ok = False
            lines.append(f"MISMATCH {head}")
            lines.extend(f"    {p}" for p in problems)
        else:
            lines.append(
                f"ok {head} :: warm collectives "
                f"{ev['warm']['collectives']} <= {a2a}/chunk + {fin}")
    return ok, lines


# Which runtime fallback-reason texts each static reason code explains.
# The runtime reports the *mechanism* (which exception broke the trace,
# now tagged with the exception CLASS — "trace diverged [X]: ..."); the
# model reports the *plan feature* that guarantees that mechanism — this
# table is the bridge, checked below so a new routing cause in the
# executor (a reason text no static code explains) fails the harness.
# The whole sweep additionally runs under NDS_TPU_STREAM_STRICT=1 (via
# the shared _forced_stream_partitions context): a fallback caused by
# anything other than StreamSyncError/ReplayMismatch re-raises outright,
# so a genuine engine bug can never masquerade as a routing reason here.
# subquery-residual survives as a code for foreign corpora; the shipped
# corpus pre-plans every subquery residual (multi-pass streaming).
_REASON_EVIDENCE = {
    "subquery-residual": ("trace diverged",),
    "chunk-dependent-host-read": ("not chunk-invariant", "trace diverged"),
    "non-invariant-graph": ("not chunk-invariant", "trace diverged"),
    "outer-join-extras": ("bound-bucket overflow",),
    "accumulator-overflow": ("bound-bucket overflow",),
}


def compare(reports, evidence, inject_drift=False):
    """Check static predictions against runtime evidence; returns
    (ok, lines). ``inject_drift`` flips each predicted path first — the
    self-test fixture that must produce mismatches."""
    from nds_tpu.analysis.exec_audit import (CLASS_COMPILED, CLASS_EAGER,
                                             SYNC_BUDGET)
    ok = True
    lines = []
    for rep, ev in zip(reports, evidence):
        klass = rep.classification
        if inject_drift:
            klass = CLASS_EAGER if klass == CLASS_COMPILED \
                else CLASS_COMPILED
        if klass == CLASS_COMPILED:
            want = "compiled"
        elif klass == CLASS_EAGER:
            want = "eager"
        else:
            # device-resident / unknown: no streamed scan runs, so the
            # listener must record NO StreamEvents at all
            want = "<none>"
        head = f"[{rep.query}] static={klass} bound={rep.sync_bound}"
        problems = []
        for sight in ("cold", "warm"):
            paths = set(ev[sight]["paths"]) or {"<none>"}
            if paths != {want}:
                problems.append(f"{sight} path {sorted(paths)} != "
                                f"predicted {want!r}")
        if klass == CLASS_COMPILED:
            if rep.sync_bound is None:
                problems.append("compiled classification with an unbounded "
                                "sync model")
            else:
                if ev["warm"]["syncs"] > rep.sync_bound:
                    problems.append(
                        f"warm used {ev['warm']['syncs']} syncs > static "
                        f"bound {rep.sync_bound}")
                if ev["cold"]["syncs"] > rep.sync_bound + rep.first_sight:
                    problems.append(
                        f"cold used {ev['cold']['syncs']} syncs > bound "
                        f"{rep.sync_bound} + first-sight {rep.first_sight}")
            for s in rep.scans:
                if s.compiled and s.gate_bound > SYNC_BUDGET:
                    problems.append(f"scan {s.table} gate bound "
                                    f"{s.gate_bound} > budget {SYNC_BUDGET}")
        elif klass == CLASS_EAGER:
            # the runtime's fallback reason must be one the model names:
            # an eager event whose reason text no static reason code
            # explains means the executor grew a routing cause the model
            # does not know about
            if not rep.reasons and not inject_drift:
                problems.append("eager classification with no reason code")
            explained = tuple(pat for code in rep.reasons
                              for pat in _REASON_EVIDENCE.get(code, ()))
            for sight in ("cold", "warm"):
                for rt_reason in ev[sight]["reasons"]:
                    if rt_reason == "NDS_TPU_STREAM_EXEC=eager":
                        continue        # env escape hatch, not plan-driven
                    if inject_drift:
                        continue        # paths already mismatch loudly
                    if not any(pat in rt_reason for pat in explained):
                        problems.append(
                            f"{sight} runtime reason {rt_reason!r} is not "
                            f"explained by static codes {rep.reasons}")
        # partitioned pipeline (the sweep forces NDS_TPU_STREAM_PARTITIONS):
        # the fan-out templates must have taken the grace-style path, and
        # the radix partition pass must be SYNC-FREE — a stream.partition
        # span with a nonzero sync delta means the partition pass started
        # paying host round trips the static model prices at zero
        if ev.get("must_partition") and not inject_drift:
            for sight in ("cold", "warm"):
                r = ev[sight]
                if not r["partitions"] or \
                        any(p <= 1 for p in r["partitions"]):
                    problems.append(
                        f"{sight} expected the partitioned pipeline "
                        f"(forced count), got partitions {r['partitions']}")
                if not r["part_span_count"]:
                    problems.append(
                        f"{sight} partitioned run drained no "
                        "stream.partition spans")
        for sight in ("cold", "warm"):
            if ev[sight].get("part_span_syncs"):
                problems.append(
                    f"{sight} stream.partition spans charged "
                    f"{ev[sight]['part_span_syncs']} host syncs; the "
                    "partition pass must be device-only (0)")
        # trace-layer parity (independent of the drift injection: it is
        # runtime-vs-runtime): every streamed scan's span must report the
        # exact syncs its StreamEvent charged — zero-added-sync tracing,
        # measured, not assumed
        if ev.get("traced"):
            for sight in ("cold", "warm"):
                r = ev[sight]
                if r["span_paths"] != r["paths"] or \
                        r["span_syncs"] != r["event_syncs"]:
                    problems.append(
                        f"{sight} trace spans "
                        f"{list(zip(r['span_paths'], r['span_syncs']))} != "
                        f"StreamEvents "
                        f"{list(zip(r['paths'], r['event_syncs']))}: the "
                        "trace layer is paying for (or mis-windowing) its "
                        "own metrics")
        if not ev["warm"]["rows"]:
            problems.append("A/B template unexpectedly returned no rows")
        if problems:
            ok = False
            lines.append(f"MISMATCH {head}")
            lines.extend(f"    {p}" for p in problems)
        else:
            lines.append(
                f"ok {head} :: cold {ev['cold']['syncs']} syncs / warm "
                f"{ev['warm']['syncs']} syncs via {ev['warm']['paths']}")
    return ok, lines


def run_diff(inject_drift=False):
    """Full harness: predict, execute, compare — the single-device sweep
    plus the sharded collective-budget sweep. Returns (ok, lines)."""
    queries, _ = _load_ab_templates()
    reports = predict(queries)
    evidence = collect_runtime_evidence()
    ok, lines = compare(reports, evidence, inject_drift=inject_drift)
    shard_ev, n_shards = collect_sharded_evidence()
    if shard_ev:
        # sharded predictions run under the forced mesh env, so the
        # model's collective budget is live (stream_shards_env)
        mod = _load_ab_module()
        with mod._forced_stream_partitions():
            with mod._forced_stream_shards():
                shard_reports = predict(queries)
        ok2, lines2 = compare_sharded(shard_reports, shard_ev, n_shards,
                                      inject_drift=inject_drift)
        ok = ok and ok2
        lines.extend(lines2)
    else:
        lines.append("# sharded sweep skipped: no multi-device mesh")
    return ok, lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="differential validation: static exec-audit "
        "predictions vs runtime StreamEvent evidence")
    ap.add_argument("--inject-drift", action="store_true",
                    help="flip every predicted path before comparing: the "
                    "harness must FAIL (model-drift self-test)")
    args = ap.parse_args(argv)
    ok, lines = run_diff(inject_drift=args.inject_drift)
    for ln in lines:
        print(ln)
    if args.inject_drift:
        if ok:
            print("# DRIFT FIXTURE FAILED TO FAIL: the harness cannot "
                  "detect model drift")
            return 1
        print("# drift fixture correctly rejected (harness is live)")
        return 0
    if ok:
        print("# exec-audit differential: static model matches runtime "
              "evidence")
        return 0
    print("# exec-audit differential FAILED: update the static model in "
          "nds_tpu/analysis/exec_audit.py in lockstep with the executor")
    return 1


if __name__ == "__main__":
    sys.exit(main())
