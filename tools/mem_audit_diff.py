# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Differential validation of the static memory auditor (soundness).

The mem auditor (``nds_tpu/analysis/mem_audit.py``) proves per-statement
row/byte bounds that the streaming executor now SIZES ITS SURVIVOR
ACCUMULATORS from — an unsound bound would silently drop rows on device
(the overflow flag only fires past the allocated capacity, so the
capacity itself must dominate the true survivor count). This harness is
the checked contract, mirroring ``tools/exec_audit_diff.py``:

* replay the ``tests/test_synccount.py`` A/B templates — the same
  statements whose runtime behavior tier-1 pins — through the real
  engine on the chunked toy session, cold and warm;
* build the static predictions from a :class:`MemModel` parameterized
  with the toy session's REAL row counts (the audit's SF10 table is a
  stand-in for exactly this knowledge);
* fail when runtime evidence ever exceeds a static bound:

  - a compiled streamed scan's measured survivor count
    (``StreamEvent.rows``, the accumulator's final total) must be
    <= the scan's proven accumulator row bound;
  - the whole sweep runs under ``NDS_TPU_STREAM_PARTITIONS=2``, so the
    fan-out templates take the grace-style PARTITIONED pipeline: the
    runtime partition count must equal the model's static choice, and
    EVERY per-partition survivor count (``StreamEvent.part_rows``) must
    fit the proven per-partition bound
    (``mem_audit.partition_row_bound`` — the skew-conditional bound the
    per-partition overflow flag enforces);
  - a statement's materialized output row count must be <= the
    statement's ``out_rows`` bound (joins bounded by schema key
    uniqueness, group-bys by key domains — the rules DESIGN.md's
    "Static memory model" table documents);
  - every statement must carry a finite bound, and every scan the
    model calls *provable* must actually have taken the compiled path
    (a provable bound that the executor rejects means the model and
    ``stream_graph_fanout`` drifted apart).

The whole sweep runs under ``NDS_TPU_STREAM_STRICT=1`` (set by the
shared ``_forced_stream_partitions`` context from tests/test_synccount):
a record/trace failure that is not a legitimate routing exception
re-raises and fails the harness outright, so an engine bug can never
pose as an eager fallback while the bounds quietly stop being checked.

A SECOND mini-sweep drives the sharded subset (``_STREAM_AB_SHARDED``)
through the shard_map'd pipeline under a forced 2-shard mesh (the
shared ``_forced_stream_shards`` context): the runtime shard count must
equal the model's (``MemModel.shards``), and EVERY per-shard survivor
count (``StreamEvent.shard_rows``) must fit the proven per-shard bound
(``mem_audit.shard_row_bound`` — rows/shards × skew through the
fan-out, the bound the per-shard overflow flags enforce).

``--inject-drift`` zeroes every predicted bound — the per-partition and
per-SHARD bounds INCLUDED — before comparing: a model-drift fixture
that MUST fail in the whole-scan, partition and shard directions,
proving the harness can catch an under-bounding model
(``tests/test_analysis.py`` asserts both directions). Run it after any change to the planner's join
bounds, ``ChunkedTable`` chunk shapes, ``engine/stream.py`` accumulator
sizing or partition plan, or the schema widths: the static model and
the executor are kept in lockstep the same way ``exec_audit`` tracks
the stream routing.
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
    """The canonical A/B statements + the chunked toy session builder,
    imported by path from tests/test_synccount.py so the harness and the
    tier-1 budget tests share one set of fixtures by construction."""
    mod = _load_ab_module()
    return mod._STREAM_AB_QUERIES, mod._chunked_star_session


def _session_row_bounds(session) -> dict:
    """The toy session's real per-table row counts — the cardinality
    knowledge a live audit would read off the arrow metadata."""
    bounds = {}
    for name, t in session.catalog.items():
        bounds[name.lower()] = int(t.nrows) if isinstance(t.nrows, int) \
            else int(t.arrow.num_rows)
    return bounds


def predict(queries, row_bounds):
    # predictions run under the SAME forced partition count as the
    # evidence sweep (MemModel reads the env at construction, so the
    # static partition choice and the runtime's agree by construction)
    with _load_ab_module()._forced_stream_partitions():
        from nds_tpu.analysis.mem_audit import MemAuditor, MemModel
        model = MemModel(row_bounds=row_bounds)
        auditor = MemAuditor(streamed={"store_sales"}, model=model)
        return [auditor.audit_sql(sql, query=f"ab{i + 1}")
                for i, (sql, _must) in enumerate(queries)]


def collect_runtime_evidence():
    """Execute each A/B template twice (cold: record+compile; warm:
    pipeline-cache hit) under the forced partition count and return
    per-template evidence plus the toy session's row bounds."""
    import numpy as np

    from nds_tpu.listener import drain_stream_events

    mod = _load_ab_module()
    queries = mod._STREAM_AB_QUERIES
    partitioned = set(getattr(mod, "_STREAM_AB_PARTITIONED", ()))
    evidence = []
    with mod._forced_stream_partitions():
        session = mod._chunked_star_session(np.random.default_rng(42))
        bounds = _session_row_bounds(session)
        drain_stream_events()
        for i, (sql, _must) in enumerate(queries):
            runs = []
            for sight in ("cold", "warm"):
                rows = session.sql(sql).collect()
                events = drain_stream_events()
                runs.append({
                    "sight": sight,
                    "out_rows": len(rows),
                    "paths": [e.path for e in events],
                    "survivors": [e.rows for e in events
                                  if e.path == "compiled" and e.rows >= 0],
                    "partitions": [e.partitions for e in events
                                   if e.path == "compiled"],
                    "part_rows": [list(e.part_rows) for e in events
                                  if e.path == "compiled"],
                })
            evidence.append({"sql": sql, "cold": runs[0], "warm": runs[1],
                             "must_partition": i in partitioned})
    return evidence, bounds


def compare(reports, evidence, inject_drift=False):
    """Check static bounds against runtime evidence; returns (ok, lines).
    ``inject_drift`` zeroes every predicted bound first — the self-test
    fixture that must produce violations."""
    ok = True
    lines = []
    for rep, ev in zip(reports, evidence):
        provable = [s for s in rep.scans if s.provable]
        acc_bounds = [s.acc_rows for s in provable]
        part_preds = [(s.partitions, s.part_rows) for s in provable]
        out_bound = rep.out_rows
        if inject_drift:
            acc_bounds = [0 for _ in acc_bounds]
            part_preds = [(p, 0 if pr is not None else None)
                          for (p, pr) in part_preds]
            out_bound = 0
        head = (f"[{rep.query}] mode={rep.mode} "
                f"peak={rep.peak_bytes:,}B out<={out_bound:,}")
        problems = []
        if rep.mode == "unknown":
            problems.append(f"no finite bound: {rep.detail}")
        if rep.peak_bytes <= 0:
            problems.append("peak bound is not positive")
        if ev.get("must_partition") and not inject_drift and \
                not any(p > 1 for (p, _pr) in part_preds):
            problems.append(
                "fan-out template: the model chose no partition "
                "decomposition under the forced partition count "
                "(model drift)")
        for sight in ("cold", "warm"):
            r = ev[sight]
            if r["out_rows"] > max(out_bound, 0):
                problems.append(
                    f"{sight} materialized {r['out_rows']} output rows > "
                    f"static out_rows bound {out_bound} (UNSOUND)")
            if not inject_drift and \
                    len(r["survivors"]) < len(acc_bounds):
                # the model proved a bound the executor did not use: a
                # provable scan fell back eager (or its StreamEvent lost
                # the survivor count) — routing and proof drifted apart
                problems.append(
                    f"{sight} ran {len(r['survivors'])} compiled scans "
                    f"with survivor evidence, but the model proved "
                    f"{len(acc_bounds)} accumulator bounds (model drift)")
            for i, got in enumerate(r["survivors"]):
                bound = acc_bounds[i] if i < len(acc_bounds) else None
                if bound is None:
                    # the executor streamed a scan the model calls
                    # unprovable: the proof is stale vs the routing
                    problems.append(
                        f"{sight} compiled scan #{i} has no provable "
                        "static accumulator bound (model drift)")
                elif got > bound:
                    problems.append(
                        f"{sight} accumulator kept {got} survivor rows > "
                        f"static bound {bound} (UNSOUND: the proof-sized "
                        "accumulator would have dropped rows)")
            # partitioned runs: static partition count must match the
            # runtime's (both derive from the same forced env + shared
            # choose_partitions), and every per-partition survivor count
            # must fit the proven per-partition bound — the allocation
            # unit the per-partition overflow flag enforces
            for i, got_p in enumerate(r.get("partitions", [])):
                pred_p, pred_rows = part_preds[i] \
                    if i < len(part_preds) else (None, None)
                if pred_p is None:
                    continue             # already reported as model drift
                if not inject_drift and got_p != pred_p:
                    problems.append(
                        f"{sight} compiled scan #{i} ran {got_p} "
                        f"partitions, the model chose {pred_p} "
                        "(partition plan drift)")
                if got_p > 1 and pred_rows is not None:
                    for j, n in enumerate(r["part_rows"][i]):
                        if n > pred_rows:
                            problems.append(
                                f"{sight} partition {j} kept {n} "
                                f"survivor rows > per-partition bound "
                                f"{pred_rows} (UNSOUND: the proof-sized "
                                "partition accumulator would have "
                                "dropped rows)")
        if not ev["warm"]["out_rows"]:
            problems.append("A/B template unexpectedly returned no rows")
        if problems:
            ok = False
            lines.append(f"MISMATCH {head}")
            lines.extend(f"    {p}" for p in problems)
        else:
            survivors = ev["warm"]["survivors"]
            parts = [p for p in ev["warm"].get("partitions", []) if p > 1]
            extra = f", partitions {parts}" if parts else ""
            lines.append(
                f"ok {head} :: warm survivors {survivors} <= "
                f"{acc_bounds} acc bound{extra}, {ev['warm']['out_rows']} "
                f"rows out via {ev['warm']['paths']}")
    return ok, lines


def collect_sharded_evidence():
    """Drive the sharded subset through the shard_map'd pipeline (forced
    shard count + partitions) and return (evidence, row bounds, forced
    shard count); empty evidence without a multi-device mesh."""
    import jax
    import numpy as np

    from nds_tpu.listener import drain_stream_events

    mod = _load_ab_module()
    queries = mod._STREAM_AB_QUERIES
    out = []
    with mod._forced_stream_partitions():
        with mod._forced_stream_shards() as n_shards:
            if len(jax.local_devices()) < n_shards:
                return [], {}, n_shards
            session = mod._chunked_star_session(np.random.default_rng(42))
            bounds = _session_row_bounds(session)
            drain_stream_events()
            for i in getattr(mod, "_STREAM_AB_SHARDED", ()):
                sql, _must = queries[i]
                runs = []
                for sight in ("cold", "warm"):
                    rows = session.sql(sql).collect()
                    events = drain_stream_events()
                    runs.append({
                        "sight": sight, "out_rows": len(rows),
                        "paths": [e.path for e in events],
                        "shards": [e.shards for e in events
                                   if e.path == "compiled"],
                        "shard_rows": [list(e.shard_rows) for e in events
                                       if e.path == "compiled"],
                    })
                out.append({"idx": i, "sql": sql,
                            "cold": runs[0], "warm": runs[1]})
    return out, bounds, n_shards


def compare_sharded(reports, shard_ev, n_shards, inject_drift=False):
    """Check the static per-shard bounds against the sharded runtime
    evidence; ``inject_drift`` zeroes them first (must fail)."""
    ok = True
    lines = []
    for ev in shard_ev:
        rep = reports[ev["idx"]]
        provable = [s for s in rep.scans if s.provable]
        shard_bounds = [(s.shards, s.shard_rows) for s in provable]
        if inject_drift:
            shard_bounds = [(p, 0 if b is not None else None)
                            for (p, b) in shard_bounds]
        head = f"[{rep.query}] sharded S={n_shards}"
        problems = []
        for sight in ("cold", "warm"):
            r = ev[sight]
            for i, got_s in enumerate(r["shards"]):
                pred_s, bound = shard_bounds[i] \
                    if i < len(shard_bounds) else (None, None)
                if pred_s is None:
                    problems.append(
                        f"{sight} compiled scan #{i} has no provable "
                        "static shard plan (model drift)")
                    continue
                if not inject_drift and got_s != pred_s:
                    problems.append(
                        f"{sight} ran {got_s} shards, the model chose "
                        f"{pred_s} (shard plan drift)")
                if bound is None:
                    continue
                for j, n in enumerate(r["shard_rows"][i]):
                    if n > bound:
                        problems.append(
                            f"{sight} shard {j} kept {n} survivor rows "
                            f"> per-shard bound {bound} (UNSOUND: the "
                            "proof-sized shard accumulator would have "
                            "dropped rows)")
        if not ev["warm"]["out_rows"]:
            problems.append("sharded A/B template returned no rows")
        if problems:
            ok = False
            lines.append(f"MISMATCH {head}")
            lines.extend(f"    {p}" for p in problems)
        else:
            lines.append(
                f"ok {head} :: warm shard rows "
                f"{ev['warm']['shard_rows']} <= "
                f"{[b for (_p, b) in shard_bounds]}")
    return ok, lines


def run_diff(inject_drift=False):
    """Full harness: execute, predict from real counts, compare — the
    single-device sweep plus the sharded per-shard-bound sweep."""
    queries, _ = _load_ab_templates()
    evidence, bounds = collect_runtime_evidence()
    reports = predict(queries, bounds)
    ok, lines = compare(reports, evidence, inject_drift=inject_drift)
    shard_ev, sh_bounds, n_shards = collect_sharded_evidence()
    if shard_ev:
        mod = _load_ab_module()
        with mod._forced_stream_partitions():
            with mod._forced_stream_shards():
                # model built under the forced mesh env: MemModel.shards
                # and the per-shard bounds are live
                shard_reports = predict(queries, sh_bounds)
        ok2, lines2 = compare_sharded(shard_reports, shard_ev, n_shards,
                                      inject_drift=inject_drift)
        ok = ok and ok2
        lines.extend(lines2)
    else:
        lines.append("# sharded sweep skipped: no multi-device mesh")
    return ok, lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="differential validation: static mem-audit bounds vs "
        "runtime survivor/output evidence (soundness)")
    ap.add_argument("--inject-drift", action="store_true",
                    help="zero every predicted bound before comparing: "
                    "the harness must FAIL (model-drift self-test)")
    args = ap.parse_args(argv)
    ok, lines = run_diff(inject_drift=args.inject_drift)
    for ln in lines:
        print(ln)
    if args.inject_drift:
        if ok:
            print("# DRIFT FIXTURE FAILED TO FAIL: the harness cannot "
                  "detect an under-bounding model")
            return 1
        print("# drift fixture correctly rejected (harness is live)")
        return 0
    if ok:
        print("# mem-audit differential: every measured survivor/output "
              "count fits its static bound")
        return 0
    print("# mem-audit differential FAILED: update the static model in "
          "nds_tpu/analysis/mem_audit.py in lockstep with the engine")
    return 1


if __name__ == "__main__":
    sys.exit(main())
