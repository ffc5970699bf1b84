# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Cross-round campaign comparison: diff two evidence ledgers, gate on
regressions, regenerate PERF.md, and cross-validate ledger evidence
against the static audits.

Four tentpole claims (streamed conversion, partitioned accumulation,
encoded upload, sharded collectives) landed with static proofs but no
re-measured number — and the previous round artifact (a run that ended
at rc 124 with no value) was a null geomean nobody diffed. This tool makes rounds COMPARABLE and the
comparison ENFORCEABLE:

* **diff** (two rounds): per-query wall deltas, geomean ratio, and the
  evidence deltas — host syncs, streamed-scan syncs, h2d upload bytes,
  ICI wire bytes, collective counts, eager-fallback counts — the same
  quantities the exec/mem audits bound statically, now compared
  run-over-run so a regression names its mechanism, not just its
  milliseconds;
* **--gate**: exit nonzero when the geomean regresses past
  ``--threshold``, any query regresses past ``--per-query-threshold``,
  or deterministic evidence regresses at all (sync count up, a compiled
  statement newly eager) — the CI face of the evidence era;
* **--inject-drift**: self-test — synthetically regress round B before
  gating and REQUIRE the gate to fail, proving the gate can fail (the
  same discipline as exec/mem_audit_diff);
* **--emit-perf**: regenerate PERF.md deterministically from a ledger
  (bench.py's own renderer), ending hand-edited perf claims: PERF.md is
  a derived artifact of a named, committed round;
* **--record-ab / --audit-ab**: run the pinned A/B template set
  (tests/test_synccount.py fixtures) into a ledger, then cross-validate
  that ledger's recorded syncs/rows/bytes/collectives against the
  exec_audit and mem_audit predictions — the differential-harness
  contract, applied to the DURABLE artifact instead of a live process
  (so any completed campaign's evidence can be re-audited post hoc);
* **--audit-perf**: re-check the same recorded ledger against the
  static COST model (nds_tpu/analysis/perf_audit.py): recorded per-scan
  ``bytesH2d`` must EQUAL the padded-chunk closed form at the live wire
  widths, and the sharded records' ``bytesIci`` must match the
  exchange+reduce collective arithmetic — so a completed campaign's
  byte evidence carries its static denominator, not just its bounds;
* **--audit-num**: re-check the same recorded ledger against the
  numeric-safety proofs (nds_tpu/analysis/num_audit.py): a statement
  the auditor proves must carry NO recorded ``bound-bucket overflow``
  rerun, and a clean record must never sit under an unproven verdict —
  the static/runtime overflow-flag agreement of tools/num_audit_diff.py
  applied to the durable artifact.

Round inputs: a campaign ledger JSONL (nds_tpu/obs/ledger.py — bench.py
resume files and power.py --ledger files alike, legacy pre-ledger
resume lines included), or a JSON dict with a ``"times"`` map
(BASELINE_TIMES.json / a merged BENCH baseline).

With MORE than two rounds the tool renders the cross-arm table instead
(every round vs the first, labeled by the arm name recorded in each
ledger) — the campaign driver's merge view. ``--gate`` stays strictly
two-round.

Usage:
    python tools/bench_compare.py A.jsonl B.jsonl            # diff report
    python tools/bench_compare.py base.jsonl arm1.jsonl arm2.jsonl
                                                             # cross-arm table
    python tools/bench_compare.py A.jsonl B.jsonl --gate     # CI gate
    python tools/bench_compare.py A.jsonl B.jsonl --gate --inject-drift
    python tools/bench_compare.py B.jsonl --emit-perf PERF.md
    python tools/bench_compare.py --record-ab ab.jsonl       # CPU mini-sweep
    python tools/bench_compare.py --audit-ab ab.jsonl [--inject-drift]
    python tools/bench_compare.py --audit-perf ab.jsonl [--inject-drift]
    python tools/bench_compare.py --audit-num ab.jsonl [--inject-drift]
"""

import argparse
import importlib.util
import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the sharded A/B mini-sweep needs a multi-device mesh (same forcing as
# the other differential harnesses; no-op when the caller already did)
if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()


def _load_by_path(name, relpath):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ledger_mod():
    """Stdlib-only module, loaded by path (shared helper): diffing two
    ledgers must not pay (or risk) a jax import."""
    from tools._ledger_load import ledger_mod
    return ledger_mod()


def _geomean(vals):
    return math.exp(sum(math.log(max(v, 1e-3)) for v in vals) / len(vals))


# evidence keys diffed per query (the statically-bounded quantities),
# in report column order. 'syncs' is SCAN-level (streamed-scan charged
# syncs); 'hostSyncs' is the STATEMENT-level counter — kept as separate
# keys so the gate never compares one against the other (a query that
# stops streaming must not read as a sync regression).
EVIDENCE_KEYS = ("syncs", "hostSyncs", "bytesH2d", "bytesIci",
                 "collectives", "eager")


def load_round(path):
    """Normalize one round artifact into
    ``{times, perf, evidence, meta, end, torn, path}``.

    ``evidence[q]`` is the per-query aggregate (ledger ``evidence``
    field, derived from ``streamedScans`` when a record predates the
    field), plus the statement-level ``hostSyncs`` counter under its
    own key (never conflated with the scan-level ``syncs``)."""
    L = _ledger_mod()
    times, perf, evidence, meta, end, torn = {}, {}, {}, {}, None, False
    failed = {}
    metrics = []
    if path.endswith(".json"):
        with open(path) as f:
            doc = json.load(f)
        if not isinstance(doc, dict) or "times" not in doc:
            raise L.LedgerError(
                f"{path}: JSON round must carry a 'times' map "
                "(BASELINE_TIMES.json shape)")
        times = dict(doc["times"])
        meta = {k: v for k, v in doc.items() if k != "times"}
    else:
        data = L.load_ledger(path)
        torn = data.torn
        meta = data.meta
        end = data.end
        # live-metrics rollup records (nds_tpu/obs/metrics.py) when the
        # round carried them; [] on legacy ledgers — every consumer of
        # this key must degrade to "no live metrics" silently
        metrics = data.metrics
        for name, rec in data.queries.items():
            if rec["status"] != "ok" or "ms" not in rec:
                continue
            times[name] = rec["ms"]
            perf[name] = rec
            ev = rec.get("evidence")
            if ev is None and "streamedScans" in rec:
                ev = L.evidence_from_scans(rec["streamedScans"])
            ev = dict(ev or {})
            if "hostSyncs" in rec:
                ev["hostSyncs"] = rec["hostSyncs"]
            evidence[name] = ev
        # failed = attempted under its OWN budget and did not complete.
        # Walk the full attempt history, not just the best record: a
        # round-budget retry of a genuinely hung query must not shadow
        # its budget-limited timeout (a round-budget kill alone means
        # the ROUND ran out — that is coverage loss, not a regression)
        for rec in data.attempts:
            name = rec["name"]
            if name in times:
                continue                       # an ok record wins
            if rec["status"] != "ok" and \
                    rec.get("limiter") != "round-budget":
                failed[name] = rec["status"]
    return {"path": path, "times": times, "perf": perf,
            "evidence": evidence, "meta": meta, "end": end, "torn": torn,
            "failed": failed, "metrics": metrics}


def compare(a, b):
    """Per-query and aggregate deltas between two loaded rounds."""
    common = sorted(set(a["times"]) & set(b["times"]))
    rows = []
    for q in common:
        ta, tb = a["times"][q], b["times"][q]
        row = {"query": q, "a_ms": ta, "b_ms": tb,
               "ratio": tb / max(ta, 1e-9)}
        ea, eb = a["evidence"].get(q), b["evidence"].get(q)
        if ea is not None and eb is not None:
            row["evidence"] = {k: (ea.get(k, 0), eb.get(k, 0))
                               for k in EVIDENCE_KEYS
                               if ea.get(k, 0) or eb.get(k, 0)}
        rows.append(row)
    out = {"common": common, "rows": rows,
           "only_a": sorted(set(a["times"]) - set(b["times"])),
           "only_b": sorted(set(b["times"]) - set(a["times"])),
           # ok in A, error/timeout in B: the worst regression there is —
           # these must never vanish into the 'only in A' footnote
           "now_failing": {q: b.get("failed", {})[q]
                           for q in sorted(set(a["times"])
                                           & set(b.get("failed", {})))}}
    if common:
        ga = _geomean([a["times"][q] for q in common])
        gb = _geomean([b["times"][q] for q in common])
        out.update(geomean_a=ga, geomean_b=gb,
                   geomean_ratio=gb / max(ga, 1e-9))
    return out


def format_compare(cmp, a, b, top=15):
    lines = [f"# bench_compare: {os.path.basename(a['path'])} (A) vs "
             f"{os.path.basename(b['path'])} (B)"]
    for label, r in (("A", a), ("B", b)):
        endrec = r["end"]
        state = (f"{endrec['status']} ({endrec.get('reason', 'clean')})"
                 if endrec else
                 ("json-times" if r["path"].endswith(".json")
                  else "NO terminal record (killed campaign)"))
        torn = " torn-tail" if r["torn"] else ""
        lines.append(f"#   {label}: {len(r['times'])} queries, "
                     f"platform {r['meta'].get('platform', '?')}, "
                     f"end: {state}{torn}")
    if not cmp["common"]:
        lines.append("# no common queries — nothing comparable")
        return lines
    lines.append(f"# geomean: A {cmp['geomean_a']:.1f} ms -> "
                 f"B {cmp['geomean_b']:.1f} ms "
                 f"(ratio {cmp['geomean_ratio']:.4f} over "
                 f"{len(cmp['common'])} common; <1 = B faster)")
    if cmp["only_a"] or cmp["only_b"]:
        lines.append(f"# only in A: {len(cmp['only_a'])}; "
                     f"only in B: {len(cmp['only_b'])}")
    for q, status in cmp.get("now_failing", {}).items():
        lines.append(f"# NOW FAILING: {q} was ok in A, {status} in B")
    ranked = sorted(cmp["rows"], key=lambda r: r["ratio"], reverse=True)
    lines.append("")
    lines.append("| query | A ms | B ms | ratio | evidence delta |")
    lines.append("|---|---|---|---|---|")
    for r in ranked[:top]:
        ev = r.get("evidence") or {}
        delta = ", ".join(f"{k} {va}->{vb}" for k, (va, vb) in ev.items()
                          if va != vb) or "-"
        lines.append(f"| {r['query']} | {r['a_ms']:.0f} | {r['b_ms']:.0f} "
                     f"| {r['ratio']:.2f} | {delta} |")
    if len(ranked) > top:
        lines.append(f"# ... {len(ranked) - top} more queries "
                     "(sorted by ratio, worst first)")
    return lines


def metrics_note(r, label):
    """One-line live-metrics summary per round when the ledger carried
    ``metrics`` records (nds_tpu/obs/metrics.py rollups); [] on legacy
    ledgers, so pre-metrics comparisons print byte-identically."""
    streams = [m for m in r.get("metrics") or ()
               if m.get("scope") == "stream"]
    if not streams:
        return []
    s = streams[-1]
    parts = [f"queries={s.get('queries')}"]
    for key in ("qps", "wallP50Ms", "wallP99Ms", "queueWaitP99Ms",
                "timeoutShed", "faults"):
        if s.get(key) is not None:
            parts.append(f"{key}={s[key]}")
    return [f"# live metrics {label} ({round_label(r)}): "
            + " ".join(parts)]


def round_label(r, fallback=None):
    """How a round is named in cross-arm output: the arm name RECORDED
    in its ledger (bench.py's campaign stamp) when present — provenance
    the artifact carries, not the path it happens to sit at — else the
    file basename."""
    return r["meta"].get("arm") or fallback or os.path.basename(r["path"])


def format_multi(rounds, top=8):
    """Cross-arm table over >2 rounds: every round diffed against
    rounds[0] (the primary arm) with :func:`compare`'s math — one row
    per arm, plus each arm's worst per-query regressions vs primary.
    Rows are keyed by :func:`round_label` (recorded arm name first)."""
    primary = rounds[0]
    plabel = round_label(primary)
    lines = [f"# bench_compare cross-arm: {len(rounds)} rounds, "
             f"primary = {plabel}"]
    lines.append("")
    lines.append("| arm | queries | geomean ms | vs primary | hostSyncs "
                 "| h2d MB | ici MB | end |")
    lines.append("|---|---|---|---|---|---|---|---|")
    details = []
    for r in rounds:
        label = round_label(r)
        cmp = compare(primary, r)
        geo = (_geomean(list(r["times"].values()))
               if r["times"] else float("nan"))
        ratio = (f"{cmp['geomean_ratio']:.3f}"
                 if cmp.get("geomean_ratio") and r is not primary else
                 ("1.000" if r is primary else "-"))
        syncs = sum(e.get("hostSyncs", 0) for e in r["evidence"].values())
        h2d = sum(e.get("bytesH2d", 0)
                  for e in r["evidence"].values()) / 1e6
        ici = sum(e.get("bytesIci", 0)
                  for e in r["evidence"].values()) / 1e6
        endrec = r["end"]
        state = (endrec["status"] if endrec else
                 ("json" if r["path"].endswith(".json") else "KILLED"))
        lines.append(f"| {label} | {len(r['times'])} | {geo:.1f} "
                     f"| {ratio} | {syncs} | {h2d:.1f} | {ici:.1f} "
                     f"| {state} |")
        if r is primary:
            continue
        worst = sorted(cmp["rows"], key=lambda x: x["ratio"],
                       reverse=True)[:top]
        moved = [w for w in worst if abs(w["ratio"] - 1.0) >= 0.05]
        if moved:
            details.append(f"# {label} vs {plabel} (worst movers):")
            for w in moved:
                details.append(
                    f"#   {w['query']}: {w['a_ms']:.0f} -> "
                    f"{w['b_ms']:.0f} ms (x{w['ratio']:.2f})")
        for q, status in cmp.get("now_failing", {}).items():
            details.append(f"# {label}: {q} ok in {plabel}, {status} here")
    lines.append("")
    lines.extend(details)
    return lines


def gate(cmp, threshold=1.10, per_query_threshold=1.50,
         bytes_threshold=1.20, b_round=None, allow_missing=False):
    """Regression verdicts. Wall-clock regressions gate with headroom
    (device weather is real); DETERMINISTIC evidence regresses at zero
    tolerance — a sync-count increase or a compiled statement going
    eager is an engine change, not weather. COVERAGE also gates: a
    killed round B (no terminal record) or queries measured in A but
    absent from B fail unless ``allow_missing`` explicitly blesses a
    partial comparison — CI must never go green on a campaign that died
    (the silent death of a run that ended at rc 124 with no value). Returns violation lines (empty =
    pass)."""
    v = []
    for q, status in cmp.get("now_failing", {}).items():
        v.append(f"{q}: ok in A, {status} in B (query stopped completing)")
    if not allow_missing:
        if b_round is not None and not b_round["path"].endswith(".json") \
                and b_round["end"] is None:
            v.append("round B has no terminal record: the campaign was "
                     "killed mid-flight (pass --allow-missing to gate a "
                     "partial round on purpose)")
        if cmp["only_a"]:
            head = ", ".join(cmp["only_a"][:5])
            more = len(cmp["only_a"]) - 5
            v.append(f"{len(cmp['only_a'])} queries measured in A are "
                     f"missing from B ({head}"
                     + (f", +{more} more" if more > 0 else "")
                     + "): incomplete round (pass --allow-missing to "
                     "gate a partial round on purpose)")
    if not cmp["common"]:
        v.append("no common queries between rounds: nothing was compared "
                 "(a gate that compares nothing must not pass)")
        return v
    if cmp["geomean_ratio"] > threshold:
        v.append(f"geomean regressed {cmp['geomean_ratio']:.3f}x > "
                 f"threshold {threshold}x")
    for r in cmp["rows"]:
        if r["ratio"] > per_query_threshold:
            v.append(f"{r['query']}: wall {r['a_ms']:.0f} -> "
                     f"{r['b_ms']:.0f} ms ({r['ratio']:.2f}x > "
                     f"{per_query_threshold}x)")
        ev = r.get("evidence") or {}
        for key, label, tol in (("syncs", "streamed-scan syncs", 0),
                                ("hostSyncs", "host syncs", 0),
                                ("eager", "eager fallbacks", 0),
                                ("collectives", "collectives", 0)):
            if key in ev:
                va, vb = ev[key]
                if vb > va + tol:
                    v.append(f"{r['query']}: {label} {va} -> {vb} "
                             "(deterministic evidence regression)")
        if "bytesH2d" in ev:
            va, vb = ev["bytesH2d"]
            if va > 0 and vb > va * bytes_threshold:
                v.append(f"{r['query']}: h2d upload {va} -> {vb} bytes "
                         f"(> {bytes_threshold}x: encoding win lost)")
    return v


def inject_drift(b, threshold):
    """Synthetically regress round B (walls past both thresholds, +2
    syncs and +1 eager fallback per query): the gate MUST reject this,
    or the gate cannot catch a real regression."""
    out = {"path": b["path"] + "<drift>", "meta": b["meta"],
           "end": b["end"], "torn": b["torn"], "perf": b["perf"]}
    out["times"] = {q: t * max(threshold * 2, 4.0)
                    for q, t in b["times"].items()}
    out["evidence"] = {}
    for q in b["times"]:
        ev = dict(b["evidence"].get(q) or {})
        ev["syncs"] = ev.get("syncs", 0) + 2
        ev["eager"] = ev.get("eager", 0) + 1
        out["evidence"][q] = ev
    return out


def emit_perf(b, out_path):
    """The campaign roofline table as a derived artifact: render round B
    through bench.py's own deterministic renderer (one renderer, whether
    the table comes from a live campaign or a committed ledger) into
    ``out_path`` (empty: ``bench.bench_perf_path()``)."""
    bench = _load_by_path("_bench_for_perf", "bench.py")
    perf = {q: {k: rec[k] for k in bench.PERF_KEYS if k in rec}
            for q, rec in b["perf"].items()}
    platform = (b["meta"].get("platform")
                or (b["end"] or {}).get("platform") or "unknown")
    # scale must come FROM the ledger: falling into the reader's env
    # default would stamp a wrong provenance line into a document whose
    # whole point is being derived, not assumed
    scale = b["meta"].get("scale", "unknown")
    text = bench.perf_text(b["times"], perf, platform=platform,
                           scale=scale)
    out_path = out_path or bench.bench_perf_path()
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(out_path, "w") as f:
        f.write(text)
    return text


# ---------------------------------------------------------------------------
# A/B evidence cross-validation (ledger vs exec/mem audit predictions)
# ---------------------------------------------------------------------------


def _load_ab_module():
    return _load_by_path("_synccount_fixtures_cmp", "tests/test_synccount.py")


def _session_row_bounds(session):
    bounds = {}
    for name, t in session.catalog.items():
        bounds[name.lower()] = int(t.nrows) if isinstance(t.nrows, int) \
            else int(t.arrow.num_rows)
    return bounds


def record_ab(path):
    """Drive the pinned A/B template set (plus the sharded subset on a
    forced 2-shard mesh) through the real engine on the chunked toy
    session and ledger the WARM sight of each — the steady state the
    static bounds gate. The toy session's real row counts land in the
    meta record so ``--audit-ab`` can rebuild the same MemModel."""
    import numpy as np

    from nds_tpu.obs import evidence as obs_evidence
    from nds_tpu.obs.ledger import Ledger

    mod = _load_ab_module()
    queries = mod._STREAM_AB_QUERIES
    with mod._forced_stream_partitions():
        session = mod._chunked_star_session(np.random.default_rng(42))
        ledger = Ledger(path, driver="bench-compare-ab", platform="cpu",
                        rowBounds=_session_row_bounds(session))
        for i, (sql, _must) in enumerate(queries):
            session.sql(sql).collect()       # cold: record+compile
            evidence = obs_evidence.begin()
            t0 = time.perf_counter()
            rows = session.sql(sql).collect()
            ms = (time.perf_counter() - t0) * 1e3
            ev = evidence.end()
            ledger.query(f"ab{i + 1}", status="ok", ms=round(ms, 3),
                         hostSyncs=ev["hostSyncs"], outRows=len(rows),
                         sight="warm",
                         syncWaitMs=round(ev["syncWaitMs"], 3),
                         tracePhases=ev["rollup"],
                         streamedScans=ev["streamedScans"])
    # sharded mini-sweep: the collective evidence
    import jax
    with mod._forced_stream_partitions():
        with mod._forced_stream_shards() as n_shards:
            if len(jax.local_devices()) >= n_shards:
                session = mod._chunked_star_session(
                    np.random.default_rng(42))
                for i in getattr(mod, "_STREAM_AB_SHARDED", ()):
                    sql, _must = queries[i]
                    session.sql(sql).collect()
                    evidence = obs_evidence.begin()
                    t0 = time.perf_counter()
                    rows = session.sql(sql).collect()
                    ms = (time.perf_counter() - t0) * 1e3
                    ev = evidence.end()
                    ledger.query(f"ab{i + 1}@sharded", status="ok",
                                 ms=round(ms, 3),
                                 hostSyncs=ev["hostSyncs"],
                                 outRows=len(rows), sight="warm",
                                 shardsForced=n_shards,
                                 streamedScans=ev["streamedScans"])
    ledger.close("completed", queries=len(queries))
    return path


def audit_ab(path, inject=False):
    """Cross-validate a recorded A/B ledger against the static audits:
    recorded warm host syncs vs exec_audit's statement bound, recorded
    paths vs the routing classification, recorded survivor rows and h2d
    bytes vs mem_audit's accumulator/chunk bounds, recorded collectives
    vs the a2a-per-chunk collective budget. ``inject`` flips paths and
    zeroes every bound first — the self-test that MUST fail. Returns
    (ok, lines)."""
    from nds_tpu.obs.ledger import load_ledger

    data = load_ledger(path)
    mod = _load_ab_module()
    queries = mod._STREAM_AB_QUERIES
    row_bounds = {str(k): int(v) for k, v in
                  (data.meta.get("rowBounds") or {}).items()}
    with mod._forced_stream_partitions():
        from nds_tpu.analysis.exec_audit import (CLASS_COMPILED,
                                                 CLASS_EAGER, ExecAuditor)
        from nds_tpu.analysis.mem_audit import MemAuditor, MemModel
        exec_reports = [ExecAuditor(streamed={"store_sales"})
                        .audit_sql(sql, query=f"ab{i + 1}")
                        for i, (sql, _m) in enumerate(queries)]
        mem_reports = [MemAuditor(streamed={"store_sales"},
                                  model=MemModel(row_bounds=row_bounds))
                       .audit_sql(sql, query=f"ab{i + 1}")
                       for i, (sql, _m) in enumerate(queries)]
        with mod._forced_stream_shards():
            exec_sharded = [ExecAuditor(streamed={"store_sales"})
                            .audit_sql(sql, query=f"ab{i + 1}")
                            for i, (sql, _m) in enumerate(queries)]
    ok = True
    lines = []
    for i, (sql, _must) in enumerate(queries):
        name = f"ab{i + 1}"
        rec = data.queries.get(name)
        rep = exec_reports[i]
        problems = []
        if rec is None:
            ok = False
            lines.append(f"MISMATCH [{name}] no ledger record")
            continue
        ev = rec.get("evidence") or {}
        scans = rec.get("streamedScans") or []
        klass = rep.classification
        if inject:
            klass = CLASS_EAGER if klass == CLASS_COMPILED \
                else CLASS_COMPILED
        if klass == CLASS_COMPILED:
            if ev.get("eager", 0) or not ev.get("compiled", 0):
                problems.append(
                    f"predicted compiled-stream, ledger evidence "
                    f"compiled={ev.get('compiled', 0)} "
                    f"eager={ev.get('eager', 0)}")
            bound = 0 if inject else rep.sync_bound
            if bound is not None and rec.get("hostSyncs", 0) > bound:
                problems.append(
                    f"warm hostSyncs {rec['hostSyncs']} > static "
                    f"sync bound {bound}")
        elif klass == CLASS_EAGER:
            if ev.get("compiled", 0) or not ev.get("eager", 0):
                problems.append(
                    f"predicted eager-fallback, ledger evidence "
                    f"compiled={ev.get('compiled', 0)} "
                    f"eager={ev.get('eager', 0)}")
        # mem bounds: recorded survivor rows and upload bytes vs the
        # accumulator / padded-chunk bounds
        mem_scans = {s.table: s for s in mem_reports[i].scans}
        for s in scans:
            if s.get("path") != "compiled":
                continue
            ms_bound = mem_scans.get(s.get("table"))
            if ms_bound is None or ms_bound.acc_rows is None:
                continue
            acc = 0 if inject else ms_bound.acc_rows
            if s.get("rows", -1) >= 0 and s["rows"] > acc:
                problems.append(
                    f"scan {s['table']} survivors {s['rows']} > proven "
                    f"accumulator bound {acc}")
            chunk_b = 0 if inject else ms_bound.chunk_bytes
            if chunk_b and s.get("bytesH2d", -1) >= 0 and \
                    s["bytesH2d"] > chunk_b * max(s.get("chunks", 1), 1):
                problems.append(
                    f"scan {s['table']} uploaded {s['bytesH2d']} bytes > "
                    f"padded-chunk bound {chunk_b} x "
                    f"{s.get('chunks', 1)} chunks")
        # sharded record: collective budget
        srec = data.queries.get(f"{name}@sharded")
        if srec is not None:
            srep = exec_sharded[i]
            scan = next((s for s in srep.scans if s.compiled), None)
            a2a = 0 if inject else getattr(scan, "a2a_chunk", 0)
            fin = 0 if inject else getattr(scan, "coll_final", 0)
            for s in srec.get("streamedScans") or []:
                coll = s.get("collectives", -1)
                if coll < 0:
                    continue
                bound = a2a * s.get("chunks", 0) + fin
                if coll > bound:
                    problems.append(
                        f"sharded scan {s.get('table')} issued {coll} "
                        f"collectives > budget {a2a}/chunk x "
                        f"{s.get('chunks', 0)} + {fin} = {bound}")
        if problems:
            ok = False
            lines.append(f"MISMATCH [{name}]")
            lines.extend(f"    {p}" for p in problems)
        else:
            lines.append(f"ok [{name}] hostSyncs {rec.get('hostSyncs')} "
                         f"<= bound {rep.sync_bound}, evidence {ev}")
    return ok, lines


def audit_perf(path, inject=False):
    """Cross-validate a recorded A/B ledger against the static COST
    model: recorded per-scan ``bytesH2d`` (warm sight — but the closed
    form is sight-invariant) must EQUAL the perf_audit prediction built
    from the ledger's own ``rowBounds`` meta plus the toy session's live
    wire widths, per statement as a sorted multiset; the sharded
    records' ``bytesIci`` must equal the exchange+reduce arithmetic for
    ici-exact scans and dominate it otherwise. ``inject`` zeroes every
    prediction first — the self-test that MUST fail. Returns
    (ok, lines)."""
    import numpy as np

    from nds_tpu.obs.ledger import load_ledger

    data = load_ledger(path)
    mod = _load_ab_module()
    queries = mod._STREAM_AB_QUERIES
    row_bounds = {str(k): int(v) for k, v in
                  (data.meta.get("rowBounds") or {}).items()}
    with mod._forced_stream_partitions():
        from nds_tpu.analysis.mem_audit import MemModel
        from nds_tpu.analysis.perf_audit import (PerfAuditor,
                                                 wire_column_widths)
        # the chunk geometry and wire widths are STRUCTURE, not
        # measurements: rebuild the deterministic toy session to read
        # them (the row counts stay the ledger's own meta record)
        session = mod._chunked_star_session(np.random.default_rng(42))
        store = session.catalog["store_sales"]
        wire = {"store_sales": wire_column_widths(store)}
        chunk_rows = getattr(store, "chunk_rows", None)

        def build_reports():
            model = MemModel(row_bounds=row_bounds, chunk_rows=chunk_rows)
            auditor = PerfAuditor(streamed={"store_sales"}, model=model,
                                  wire_cols=wire)
            return [auditor.audit_sql(sql, query=f"ab{i + 1}")
                    for i, (sql, _m) in enumerate(queries)]

        reports = build_reports()
        with mod._forced_stream_shards():
            sharded_reports = build_reports()
    ok = True
    lines = []
    for i, (sql, _must) in enumerate(queries):
        name = f"ab{i + 1}"
        rec = data.queries.get(name)
        rep = reports[i]
        problems = []
        if rec is None:
            ok = False
            lines.append(f"MISMATCH [{name}] no ledger record")
            continue
        preds = sorted((c.bytes_h2d for c in rep.scans if c.compiled),
                       reverse=True)
        if inject:
            preds = [0 for _ in preds]
        got = sorted((s["bytesH2d"] for s in rec.get("streamedScans") or []
                      if s.get("path") == "compiled"
                      and s.get("bytesH2d", -1) >= 0), reverse=True)
        if not inject and len(got) != len(preds):
            problems.append(
                f"ledger carries {len(got)} compiled byte records, the "
                f"cost model priced {len(preds)} scans (model drift)")
        else:
            for p, g in zip(preds, got):
                if rep.h2d_exact and g != p:
                    problems.append(
                        f"recorded upload {g} bytes != static prediction "
                        f"{p} (EXACTNESS LOST)")
                elif not rep.h2d_exact and not inject \
                        and not (rep.bytes_h2d_min <= g <= p):
                    problems.append(
                        f"recorded upload {g} bytes outside static band")
        srec = data.queries.get(f"{name}@sharded")
        if srec is not None:
            srep = sharded_reports[i]
            ici_preds = sorted(((c.bytes_ici, c.ici_exact)
                                for c in srep.scans
                                if c.compiled and c.shards > 1),
                               reverse=True)
            if inject:
                ici_preds = [(0, True) for _ in ici_preds]
            got_ici = sorted(
                (s["bytesIci"] for s in srec.get("streamedScans") or []
                 if s.get("bytesIci", -1) >= 0), reverse=True)
            if not inject and len(got_ici) != len(ici_preds):
                problems.append(
                    f"sharded record carries {len(got_ici)} ICI byte "
                    f"records, the cost model priced {len(ici_preds)} "
                    "sharded scans (model drift)")
            else:
                for (p, exact), g in zip(ici_preds, got_ici):
                    if exact and g != p:
                        problems.append(
                            f"recorded ICI {g} bytes != static "
                            f"prediction {p} (EXACTNESS LOST)")
                    elif not exact and g < p:
                        problems.append(
                            f"recorded ICI {g} bytes < static lower "
                            f"bound {p}")
        if problems:
            ok = False
            lines.append(f"MISMATCH [{name}]")
            lines.extend(f"    {p}" for p in problems)
        else:
            lines.append(f"ok [{name}] recorded h2d {got} == static, "
                         f"roofline {rep.roofline_ms:.2f} ms ({rep.bound})")
    return ok, lines


def audit_num(path, inject=None):
    """Cross-validate a recorded A/B ledger against the static NUMERIC
    safety proofs: a statement num_audit proves (every codec/rebase/
    accumulator/hash-bit check) must carry NO recorded overflow-flag
    evidence — no streamed scan that took the ``bound-bucket overflow``
    eager rerun — and a clean record must never sit under an unproven
    verdict. ``inject`` is the two-direction drift self-test that MUST
    fail: ``"runtime"`` stamps the overflow reason onto every recorded
    scan (proven verdicts contradicted), ``"static"`` inflates the
    ledger's own row bounds x10^9 so the accumulator proofs fail against
    the clean record. Returns (ok, lines)."""
    from nds_tpu.obs.ledger import load_ledger

    data = load_ledger(path)
    mod = _load_ab_module()
    queries = mod._STREAM_AB_QUERIES
    row_bounds = {str(k): int(v) for k, v in
                  (data.meta.get("rowBounds") or {}).items()}
    if inject == "static":
        row_bounds = {k: v * 10 ** 9 for k, v in row_bounds.items()}
    with mod._forced_stream_partitions():
        from nds_tpu.analysis.mem_audit import MemModel
        from nds_tpu.analysis.num_audit import NumAuditor
        auditor = NumAuditor(streamed={"store_sales"},
                             model=MemModel(row_bounds=row_bounds))
        reports = [auditor.audit_sql(sql, query=f"ab{i + 1}")
                   for i, (sql, _m) in enumerate(queries)]
    ok = True
    lines = []
    for i, (sql, _must) in enumerate(queries):
        name = f"ab{i + 1}"
        rec = data.queries.get(name)
        rep = reports[i]
        if rec is None:
            ok = False
            lines.append(f"MISMATCH [{name}] no ledger record")
            continue
        reasons = [s.get("reason", "") for s in
                   (rec.get("streamedScans") or [])]
        if inject == "runtime":
            reasons = ["bound-bucket overflow" for _ in reasons] or \
                ["bound-bucket overflow"]
        over = any(r == "bound-bucket overflow" for r in reasons)
        if rep.proven and over:
            ok = False
            lines.append(f"MISMATCH [{name}] statically proven but the "
                         "ledger records a bound-bucket overflow rerun")
        elif not rep.proven and not over:
            bad = [c for c in rep.checks if not c.proven]
            what = f"{bad[0].kind} {bad[0].subject}" if bad else "?"
            ok = False
            lines.append(f"MISMATCH [{name}] statically unproven "
                         f"({what}) against a clean ledger record")
        else:
            lines.append(f"ok [{name}] {len(rep.checks)} checks proven, "
                         "no overflow evidence recorded")
    return ok, lines


def audit_param(path, inject=None):
    """Cross-validate a recorded A/B ledger against the static literal-
    BINDABILITY proofs: a statement param_audit proves bindable slots
    for must be classified compiled-stream AND carry compiled-path
    streamed-scan evidence in the ledger (bindable literals only ride
    as jit operands of a compiled chunk pipeline — eager evidence means
    there is no one-compile program to re-serve), and conversely a
    record whose scans all took the compiled path must not sit under a
    statement the param audit classifies as non-streamed (bindability
    proofs standing on a misclassified statement are unproven).
    ``inject`` is the two-direction drift self-test that MUST fail:
    ``"runtime"`` rewrites every recorded scan path to eager (proven
    slots contradicted), ``"static"`` audits with an EMPTY streamed set
    so the compiled evidence contradicts the classifications."""
    from nds_tpu.obs.ledger import load_ledger

    data = load_ledger(path)
    mod = _load_ab_module()
    queries = mod._STREAM_AB_QUERIES
    with mod._forced_stream_partitions():
        from nds_tpu.analysis.exec_audit import CLASS_COMPILED
        from nds_tpu.analysis.param_audit import ParamAuditor
        auditor = ParamAuditor(
            streamed=frozenset() if inject == "static" else None)
        reports = [auditor.audit_sql(sql, query=f"ab{i + 1}")
                   for i, (sql, _m) in enumerate(queries)]
    ok = True
    lines = []
    n_slots = 0
    for i, (sql, _must) in enumerate(queries):
        name = f"ab{i + 1}"
        rec = data.queries.get(name)
        rep = reports[i]
        if rec is None:
            ok = False
            lines.append(f"MISMATCH [{name}] no ledger record")
            continue
        paths = [s.get("path", "") for s in
                 (rec.get("streamedScans") or [])]
        if inject == "runtime":
            paths = ["eager" for _ in paths] or ["eager"]
        compiled_evidence = bool(paths) and \
            all(p == "compiled" for p in paths)
        if rep.n_bindable and not (rep.classification == CLASS_COMPILED
                                   and compiled_evidence):
            ok = False
            lines.append(
                f"MISMATCH [{name}] {rep.n_bindable} bindable slots "
                f"proven but the evidence is {rep.classification} / "
                f"paths {sorted(set(paths))} — no compiled program for "
                "the parameter operands to re-serve")
        elif compiled_evidence and rep.classification != CLASS_COMPILED:
            ok = False
            lines.append(
                f"MISMATCH [{name}] ledger records the compiled stream "
                f"path but the param audit classifies the statement "
                f"{rep.classification} — its bindability verdicts stand "
                "on a misclassified statement")
        else:
            n_slots += rep.n_bindable
            sig = f" [{rep.signature()}]" if rep.n_bindable else ""
            lines.append(f"ok [{name}] {rep.n_bindable} bindable "
                         f"slots{sig} on {rep.classification} evidence")
    if ok and inject is None and n_slots == 0:
        ok = False
        lines.append("MISMATCH: the A/B corpus yielded ZERO bindable "
                     "slots — the bindability rule went dark")
    return ok, lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="diff two campaign evidence ledgers / bench rounds; "
        "gate on regressions; regenerate PERF.md; cross-validate ledger "
        "evidence against the static audits")
    ap.add_argument("rounds", nargs="*",
                    help="round artifacts: ledger JSONL (bench resume / "
                    "power --ledger) or JSON with a 'times' map")
    ap.add_argument("--gate", action="store_true",
                    help="exit nonzero on regressions past the thresholds")
    ap.add_argument("--threshold", type=float, default=1.10,
                    help="geomean regression gate (default 1.10x)")
    ap.add_argument("--per-query-threshold", type=float, default=1.50,
                    help="per-query wall regression gate (default 1.50x)")
    ap.add_argument("--allow-missing", action="store_true",
                    help="gate a PARTIAL round on purpose: skip the "
                    "killed-campaign (no terminal record) and "
                    "missing-coverage violations")
    ap.add_argument("--inject-drift", action="store_true",
                    help="self-test: synthetically regress round B (or "
                    "zero the audit bounds under --audit-ab) and REQUIRE "
                    "the gate to fail")
    ap.add_argument("--emit-perf", metavar="PATH", nargs="?", const="",
                    help="regenerate the campaign roofline table from "
                    "the (single) given ledger — deterministic, same "
                    "renderer as bench.py (default: "
                    "chiprun_out/BENCH_PERF.md; never the repo's PERF.md "
                    "unless named)")
    ap.add_argument("--record-ab", metavar="PATH",
                    help="run the pinned A/B template mini-sweep (CPU) "
                    "and write its evidence ledger to PATH")
    ap.add_argument("--audit-ab", metavar="PATH",
                    help="cross-validate a recorded A/B ledger against "
                    "exec_audit/mem_audit predictions")
    ap.add_argument("--audit-perf", metavar="PATH",
                    help="cross-validate a recorded A/B ledger's byte "
                    "evidence against the perf_audit static cost model "
                    "(h2d equality, ICI exchange+reduce arithmetic)")
    ap.add_argument("--audit-num", metavar="PATH",
                    help="cross-validate a recorded A/B ledger's "
                    "overflow-flag evidence against the num_audit "
                    "value-range proofs (proven <=> no overflow rerun)")
    ap.add_argument("--audit-param", metavar="PATH",
                    help="cross-validate a recorded A/B ledger's "
                    "compiled-path evidence against the param_audit "
                    "bindability proofs (bindable slots <=> compiled "
                    "stream evidence)")
    args = ap.parse_args(argv)

    if args.record_ab:
        record_ab(args.record_ab)
        print(f"# A/B evidence ledger recorded: {args.record_ab}")
        return 0

    if args.audit_ab:
        ok, lines = audit_ab(args.audit_ab, inject=args.inject_drift)
        for ln in lines:
            print(ln)
        if args.inject_drift:
            if ok:
                print("# DRIFT FIXTURE FAILED TO FAIL: the evidence "
                      "check cannot catch a stale audit")
                return 1
            print("# drift fixture correctly rejected (evidence check "
                  "is live)")
            return 0
        if ok:
            print("# ledger evidence matches exec/mem audit predictions")
            return 0
        print("# evidence check FAILED: ledger evidence exceeds a "
              "static audit bound (model drift or engine regression)")
        return 1

    if args.audit_perf:
        ok, lines = audit_perf(args.audit_perf, inject=args.inject_drift)
        for ln in lines:
            print(ln)
        if args.inject_drift:
            if ok:
                print("# DRIFT FIXTURE FAILED TO FAIL: the cost-model "
                      "check cannot catch a drifted model")
                return 1
            print("# drift fixture correctly rejected (cost-model check "
                  "is live)")
            return 0
        if ok:
            print("# ledger byte evidence matches the perf_audit static "
                  "cost model")
            return 0
        print("# cost-model check FAILED: ledger byte evidence differs "
              "from the static predictions (model drift or engine "
              "regression)")
        return 1

    if args.audit_num:
        if args.inject_drift:
            # both drift directions must be rejected for exit 0
            ok_r, lines_r = audit_num(args.audit_num, inject="runtime")
            ok_s, lines_s = audit_num(args.audit_num, inject="static")
            for ln in lines_r + lines_s:
                print(ln)
            if ok_r or ok_s:
                print("# DRIFT FIXTURE FAILED TO FAIL: the numeric "
                      "evidence check cannot catch a drifted verdict")
                return 1
            print("# both drift directions correctly rejected (numeric "
                  "evidence check is live)")
            return 0
        ok, lines = audit_num(args.audit_num)
        for ln in lines:
            print(ln)
        if ok:
            print("# ledger overflow evidence agrees with the num_audit "
                  "static verdicts")
            return 0
        print("# numeric evidence check FAILED: a static verdict "
              "contradicts the recorded overflow evidence (model drift "
              "or engine regression)")
        return 1

    if args.audit_param:
        if args.inject_drift:
            # both drift directions must be rejected for exit 0
            ok_r, lines_r = audit_param(args.audit_param,
                                        inject="runtime")
            ok_s, lines_s = audit_param(args.audit_param,
                                        inject="static")
            for ln in lines_r + lines_s:
                print(ln)
            if ok_r or ok_s:
                print("# DRIFT FIXTURE FAILED TO FAIL: the bindability "
                      "evidence check cannot catch a drifted proof")
                return 1
            print("# both drift directions correctly rejected "
                  "(bindability evidence check is live)")
            return 0
        ok, lines = audit_param(args.audit_param)
        for ln in lines:
            print(ln)
        if ok:
            print("# ledger compiled-path evidence agrees with the "
                  "param_audit bindability proofs")
            return 0
        print("# bindability evidence check FAILED: a bindability "
              "verdict contradicts the recorded stream-path evidence "
              "(model drift or engine regression)")
        return 1

    if args.emit_perf is not None:
        if len(args.rounds) != 1:
            ap.error("--emit-perf takes exactly one ledger round")
        b = load_round(args.rounds[0])
        emit_perf(b, args.emit_perf)
        print(f"# roofline table regenerated from {args.rounds[0]} -> "
              f"{args.emit_perf or 'chiprun_out/BENCH_PERF.md'} "
              f"({len(b['times'])} queries)")
        return 0

    if len(args.rounds) > 2:
        # cross-arm table: every round vs the first (primary). The GATE
        # contract stays strictly two-round — regression thresholds are
        # a pairwise judgment, and widening them silently would let a
        # multi-arm invocation skip the real A/B gate.
        if args.gate or args.inject_drift:
            ap.error("--gate/--inject-drift take exactly two rounds "
                     "(A B); the cross-arm table is report-only")
        for ln in format_multi([load_round(p) for p in args.rounds]):
            print(ln)
        return 0

    if len(args.rounds) != 2:
        ap.error("diff mode takes exactly two rounds (A B)")
    a = load_round(args.rounds[0])
    b = load_round(args.rounds[1])
    if args.inject_drift:
        b = inject_drift(b, args.threshold)
    cmp = compare(a, b)
    for ln in format_compare(cmp, a, b):
        print(ln)
    for ln in metrics_note(a, "A") + metrics_note(b, "B"):
        print(ln)
    violations = gate(cmp, threshold=args.threshold,
                      per_query_threshold=args.per_query_threshold,
                      b_round=b, allow_missing=args.allow_missing)
    if args.inject_drift:
        if not violations:
            print("# DRIFT FIXTURE FAILED TO FAIL: the gate cannot "
                  "catch a regression")
            return 1
        print(f"# drift fixture correctly rejected "
              f"({len(violations)} violations; gate is live)")
        return 0
    if violations:
        print(f"# gate: {len(violations)} violation(s)")
        for ln in violations:
            print(f"  REGRESSION {ln}")
        return 1 if args.gate else 0
    print("# gate: no regressions past thresholds")
    return 0


if __name__ == "__main__":
    sys.exit(main())
