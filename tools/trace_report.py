# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Aggregate a --trace-dir of per-query Chrome traces into the phase
table PERF.md needs.

Reads every ``*.trace.json`` a driver wrote (``nds_power.py --trace-dir``
/ ``NDS_BENCH_TRACE_DIR``) and prints:

1. the per-query phase breakdown — self-time per phase (the rollup's
   ``selfMs``: a span's time minus its direct children of the same
   thread, from the spans' ``parent`` ids), host-sync count, the
   compile-vs-drive
   split of the streamed chunk pipeline, the collective time of a
   SHARDED pipeline (``stream.exchange`` — the per-chunk hash-exchange
   pass — as its own phase column, with the cross-shard reduce inside
   ``stream.materialize``), and the transfer accounting: logical vs
   actually-uploaded (encoded) bytes per template plus the effective
   scan GB/s, and for sharded runs the ICI MB the explicit collectives
   moved plus the effective ICI GB/s (wire bytes over the collective
   phase wall), and the prefetch-stall column — driver ms BLOCKED on
   the bounded prefetch ring (``StreamEvent.prefetch_stall_ms``), the
   async-ingest overlap evidence — wins measured, not asserted;
2. the top sync-charging host-read sites across the run (the first-class
   ``ops.host_read`` call-site tags — which engine lines pay the round
   trips);
3. the eager-fallback cost ranking by reason — the measured worklist for
   ROADMAP's streamability widening (each line is wall time + syncs a
   query paid because the compiled pipeline rejected it);
4. ROOFLINE columns — each query's effective scan GB/s as a percentage
   of ``NDS_TPU_ROOFLINE_HBM_GBS`` and its ICI GB/s as a percentage of
   ``NDS_TPU_ROOFLINE_ICI_GBS`` (defaults are v5e-class: 819 / 186;
   set them for the attached part) — so "is the scan fast?" reads off
   the table instead of requiring the chip datasheet — plus, for
   queries the STATIC cost model prices (the corpus templates, via
   ``nds_tpu/analysis/perf_audit.py``), a ``static-roofline %`` /
   ``unexplained ms`` pair: the statically-predicted lower-bound wall
   (max of h2d/HBM/ICI byte totals over the same
   ``NDS_TPU_ROOFLINE_*_GBS`` knobs, ``_H2D_GBS`` included) as a
   fraction of the measured wall, and the remainder — measured minus
   explained — which is the named-overhead worklist;
5. a ranked NEXT-BOTTLENECK summary — host-sync blocking, eager
   fallbacks, compile time, HBM-roofline headroom and ICI-roofline
   headroom, each priced in attributable milliseconds across the run —
   ROADMAP's "name the next bottleneck from data" as one command;
6. from a ledger, COMPILE BY PROGRAM — the ``compiles`` block of the
   terminal record (``nds_tpu/obs/compiles.py``): the process's program
   builds in all and by program name, each with its builds, cache hits
   and misses, XLA compile ms, cache-read ms, trace ms and lowering ms.

The input may be a ``--trace-dir`` of per-query Chrome traces OR a
campaign evidence ledger file (``nds_tpu/obs/ledger.py`` — bench.py
resume / ``nds_power.py --ledger``): ledger query records carry the
same ``tracePhases`` rollup and streamed-scan evidence, so post-hoc
analysis works on any completed round without re-running it. Both
inputs price phases from the same recorded rollup (``selfMs``); ledger
rows use uploaded (encoded) bytes as the logical volume.

``--profile DIR`` reads a profiler capture instead (``nds_power.py
--profile DIR``, one ``<query>/`` capture each, or any directory
holding ``*.xplane.pb``): per statement, device busy time by engine
scope (``nds.*``), the top device operations with their scope beside
their HLO name, and every idle gap over 1 ms with the innermost ``nds:``
span open on the host at that instant. See :func:`profile_report`.

Usage: python tools/trace_report.py TRACE_DIR_OR_LEDGER [--top N]
       python tools/trace_report.py --profile PROFILE_DIR [--top N]
"""

import argparse
import glob
import json
import os
import re
import sys
from collections import Counter, defaultdict

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# per-chip roofline knobs for the % columns and the bottleneck ranking;
# defaults are v5e-class numbers — override for the attached part
ROOFLINE_HBM_GBS = float(os.environ.get("NDS_TPU_ROOFLINE_HBM_GBS", "819"))
ROOFLINE_ICI_GBS = float(os.environ.get("NDS_TPU_ROOFLINE_ICI_GBS", "186"))

# phase columns of the breakdown table, in pipeline order; everything
# else (query/stream umbrellas, uncovered wall) folds into "other".
# stream.partition is the grace-style radix pass of a partitioned
# pipeline (per-chunk partition-id hashing + device-resident histogram)
# — priced as its own column so a partitioned statement's partition
# overhead is visible next to compile/drive. stream.overflow-rerun is
# the eager re-execution after a completed compiled run overflowed its
# bound buckets — its cost is priced separately in the fallback ranking
# (the wasted pipeline time is the stream span's remainder).
# stream.exchange is the sharded pipeline's per-chunk hash-exchange pass
# (parallel/exchange.py all-to-alls) — the collective-time column; the
# one cross-shard reduce rides stream.materialize.
# "ops" is every engine-primitive span (op.join, op.sort, ...) folded
# into one column; "stream" is the umbrella's own self time (cache
# lookup, part flattening: what its stream.* children do not cover).
# "compile" is every program build (trace, lowering, XLA compile or
# persistent-cache read: one span each, nds_tpu/obs/compiles.py), taken
# out of the phase that asked for it: stream.compile / replay.compile
# keep their own dispatch and re-trace.
PHASES = ("statement", "parse", "plan", "ops", "compile",
          "replay.record", "replay.compile", "replay.drive",
          "stream", "stream.record", "stream.compile", "stream.partition",
          "stream.exchange", "stream.prefetch", "stream.drive",
          "stream.eager", "stream.overflow-rerun", "stream.materialize",
          "materialize", "collect")
# the prefetch ring's stages: mostly worker-thread time that ran BESIDE
# the driver (their own table row would exceed the wall), so they are
# kept out of the per-query phase sum and "other"
BESIDE = ("prefetch.source", "prefetch.prepare", "prefetch.backpressure")


def phase_self_ms(phases):
    """``{column: self ms}`` and the wall the span tree covers (sum of
    the parentless spans, ``rootMs``) from one rollup's ``phases`` — the
    ONE self-time source of this report, for trace files and ledger
    records alike. A rollup that predates ``selfMs`` prices inclusive
    ``ms`` (its umbrellas then double-count; re-run to get the tree)."""
    cols = defaultdict(float)
    root_ms = 0.0
    for name, p in phases.items():
        root_ms += p.get("rootMs", 0.0)
        if name in BESIDE:
            continue
        col = "ops" if name.startswith("op.") else \
            name if name in PHASES else "other"
        cols[col] += p.get("selfMs", p.get("ms", 0.0))
    return cols, root_ms


def _add_unit_costs(agg, phases):
    """Feed the run's compiled-path unit costs (per-chunk drive, one
    materialize) from one statement's rollup."""
    for name, key in (("stream.drive", "drive"),
                      ("stream.materialize", "mat")):
        p = phases.get(name)
        if p:
            agg[key + "_ms"] += p.get("selfMs", p.get("ms", 0.0))
            agg[key + "_n"] += p.get("count", 0)


def load_trace(path):
    """(query, events, rollup phases) of one Chrome trace file."""
    with open(path) as f:
        doc = json.load(f)
    nds = doc.get("nds") or {}
    query = nds.get("query") or \
        os.path.basename(path).split(".trace.json")[0]
    return (query, doc.get("traceEvents") or [],
            (nds.get("rollup") or {}).get("phases") or {})


def _new_agg():
    return {
        "per_query": {},
        "sites": Counter(),
        "site_tag": {},
        "fallbacks": defaultdict(lambda: {"queries": 0, "ms": 0.0,
                                          "syncs": 0, "rerun_ms": 0.0,
                                          "chunks": 0}),
        # compiled-path unit costs measured from THIS run's streamed
        # statements: the basis of the projected-savings column (what an
        # eager fallback would roughly cost compiled — per-chunk drive
        # time of comparable pipelines plus one materialize)
        "drive_ms": 0.0, "drive_n": 0, "mat_ms": 0.0, "mat_n": 0,
    }


def collect_from_traces(trace_dir):
    """Aggregate a --trace-dir of Chrome traces; None when empty."""
    files = sorted(glob.glob(os.path.join(trace_dir, "*.trace.json")))
    if not files:
        return None
    agg = _new_agg()
    per_query = agg["per_query"]
    sites = agg["sites"]
    site_tag = agg["site_tag"]
    fallbacks = agg["fallbacks"]
    for path in files:
        query, events, phases = load_trace(path)

        def is_sync(e):
            return e.get("cat") == "sync" or e["name"].startswith("sync:")

        query_syncs = 0
        query_sync_ms = 0.0
        for e in events:
            if e.get("ph") == "X" and is_sync(e):
                args = e.get("args") or {}
                site = args.get("site", "?")
                sites[site] += args.get("syncs", 0)
                query_syncs += args.get("syncs", 0)
                query_sync_ms += e.get("dur", 0.0) / 1e3
                site_tag.setdefault(site, e["name"].split("sync:")[-1])
        # sync slices are no spans: their blocked time belongs to the
        # phase span that paid it (its selfMs), not to an "other" row
        spans = [e for e in events
                 if e.get("ph") == "X" and not is_sync(e)]
        cols, root_ms = phase_self_ms(phases)
        row = {"total_ms": root_ms, "syncs": 0, "phases": cols,
               "h2d": 0, "logical": 0, "stream_ms": 0.0, "ici": 0,
               "sync_ms": 0.0, "pf_stall": 0.0}
        _add_unit_costs(agg, phases)
        for e in spans:
            name = e["name"]
            args = e.get("args") or {}
            if name == "stream":
                # driver ms BLOCKED on the prefetch ring, measured per
                # scan (StreamEvent.prefetch_stall_ms riding the stream
                # span annotation) — the async-ingest overlap evidence
                row["pf_stall"] += max(args.get("prefetchStallMs", 0)
                                       or 0, 0)
                # encoded-columnar accounting rides the stream span
                # (engine/stream.py annotates bytesH2d/bytesLogical;
                # the eager loop annotates bytesH2d only; sharded runs
                # add bytesIci — the explicit collectives' wire bytes)
                row["h2d"] += args.get("bytesH2d", 0) or 0
                row["logical"] += args.get("bytesLogical",
                                           args.get("bytesH2d", 0)) or 0
                row["stream_ms"] += e["dur"] / 1e3
                ici = args.get("bytesIci", 0) or 0
                row["ici"] += max(ici, 0)
            if name == "stream" and args.get("path") == "eager":
                fb = fallbacks[args.get("reason", "?")]
                fb["queries"] += 1
                fb["ms"] += e["dur"] / 1e3
                fb["syncs"] += args.get("syncs", 0)
                fb["chunks"] += args.get("chunks", 0)
            if name == "stream.overflow-rerun":
                # an overflow rerun's eager loop: the enclosing stream
                # span's remainder is the WASTED compiled-pipeline work
                fb = fallbacks[args.get("reason", "bound-bucket overflow")]
                fb["rerun_ms"] += e["dur"] / 1e3
        # wall from the parentless spans only (rootMs), so nested phases
        # never double-count into the query total; syncs from the
        # attributed sync-site slices — each charged sync appears on
        # exactly one slice, including syncs paid BETWEEN spans that no
        # root span's delta would cover
        row["syncs"] = query_syncs
        row["sync_ms"] = query_sync_ms
        per_query[query] = row
    return agg


def collect_from_ledger(path):
    """Build the same aggregate from a campaign evidence ledger: query
    records carry the ``tracePhases`` rollup (per-phase inclusive ms /
    counts / syncs, top sync sites, fallbacks) and the streamed-scan
    evidence (bytesH2d/bytesIci) — enough for the phase table, roofline
    columns and bottleneck ranking without the original trace dir.
    Phase times are the rollup's ``selfMs`` (:func:`phase_self_ms`, as
    for a trace dir); uploaded bytes stand in for logical volume."""
    sys.path.insert(0, REPO)
    from tools._ledger_load import ledger_mod   # stdlib-only: no jax
    data = ledger_mod().load_ledger(path)
    if not data.queries:
        return None
    agg = _new_agg()
    per_query = agg["per_query"]
    for name, rec in sorted(data.queries.items()):
        if rec["status"] != "ok":
            continue
        roll = rec.get("tracePhases") or rec.get("trace") or {}
        phases = roll.get("phases") or {}
        row = {"total_ms": rec.get("ms", 0.0), "syncs": 0,
               "phases": defaultdict(float), "h2d": 0, "logical": 0,
               "stream_ms": 0.0, "ici": 0,
               "sync_ms": rec.get("syncWaitMs", 0.0), "pf_stall": 0.0}
        row["phases"], _root_ms = phase_self_ms(phases)
        row["stream_ms"] = (phases.get("stream") or {}).get("ms", 0.0)
        _add_unit_costs(agg, phases)
        # driver-measured XLA compile (the jax monitoring meter): richer
        # than the span phases when the compile happened outside a
        # stream/replay compile span (e.g. eager table-at-a-time ops)
        row["compile_ms"] = rec.get("compileMs",
                                    rec.get("compileS", 0.0) * 1e3)
        ev = rec.get("evidence")
        if ev is None and "streamedScans" in rec:
            # legacy record (pre-evidence field): derive the aggregate
            # from the per-scan evidence, exactly as the ledger writer
            # now does — the byte/roofline/pf-stall columns must render
            # from a ledger identically to the equivalent trace dir
            ev = ledger_mod().evidence_from_scans(rec["streamedScans"])
        ev = ev or {}
        row["h2d"] = max(ev.get("bytesH2d", 0), 0)
        row["logical"] = row["h2d"]
        row["ici"] = max(ev.get("bytesIci", 0), 0)
        row["pf_stall"] = max(ev.get("prefetchStallMs", 0.0), 0.0)
        row["syncs"] = rec.get("hostSyncs",
                               sum(p.get("syncs", 0)
                                   for p in phases.values()))
        for site in roll.get("syncSites") or []:
            agg["sites"][site.get("site", "?")] += site.get("syncs", 0)
            agg["site_tag"].setdefault(site.get("site", "?"),
                                       site.get("tag", "?"))
        for fb_rec in roll.get("fallbacks") or []:
            fb = agg["fallbacks"][fb_rec.get("reason", "?")]
            fb["queries"] += 1
            fb["ms"] += fb_rec.get("ms", 0.0)
            fb["syncs"] += fb_rec.get("syncs", 0)
        per_query[name] = row
    return agg if per_query else None


def _static_walls(per_query):
    """``query -> (roofline_ms, bound)`` from the static cost model
    (``nds_tpu/analysis/perf_audit.py``) for the queries this run
    measured — the denominator of the ``static-roofline %`` /
    ``unexplained ms`` columns. Walls use the SAME
    ``NDS_TPU_ROOFLINE_*_GBS`` knobs as the measured roofline columns.
    Returns {} when the model cannot load (no nds_tpu/jax available) or
    no measured query matches a priced corpus statement — the measured
    columns render regardless."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, REPO)
    try:
        from nds_tpu.analysis.perf_audit import corpus_walls
        walls = corpus_walls()
    except Exception:
        return {}
    return {q: walls[q] for q in per_query if q in walls}


def bottlenecks(agg):
    """Rank the run's improvement levers by ATTRIBUTABLE milliseconds —
    ROADMAP's "name the next bottleneck from data". Candidates: host-sync
    blocking (measured blocked ms), eager fallbacks (measured fallback
    ms), XLA compile (measured compile-phase ms), HBM headroom (streamed
    scan ms x the fraction of the HBM roofline unused), ICI headroom
    (collective ms x the fraction of the ICI roofline unused)."""
    per_query = agg["per_query"].values()
    out = []
    sync_ms = sum(r["sync_ms"] for r in per_query)
    if sync_ms > 0:
        out.append((sync_ms, "host-sync blocking",
                    "reduce round trips (DESIGN.md sync inventory)"))
    fb_ms = sum(fb["ms"] for fb in agg["fallbacks"].values())
    if fb_ms > 0:
        out.append((fb_ms, "eager fallbacks",
                    "widen streamability (fallback ranking below)"))
    # per row, the larger of span-phase compile and the driver's compile
    # meter (ledger rows) — the meter covers compiles no span wraps
    compile_ms = sum(max(r["phases"].get("stream.compile", 0.0)
                         + r["phases"].get("replay.compile", 0.0)
                         + r["phases"].get("compile", 0.0),
                         r.get("compile_ms", 0.0))
                     for r in per_query)
    if compile_ms > 0:
        out.append((compile_ms, "XLA compile",
                    "persistent cache / template bank (ROADMAP item 5)"))
    stream_ms = sum(r["stream_ms"] for r in per_query)
    logical = sum(r["logical"] for r in per_query)
    if stream_ms > 0 and logical > 0:
        gbs = logical / (stream_ms / 1e3) / 1e9
        frac = min(gbs / ROOFLINE_HBM_GBS, 1.0)
        out.append((stream_ms * (1.0 - frac),
                    f"HBM roofline headroom (scans at {gbs:.1f} GB/s = "
                    f"{frac * 100:.1f}% of {ROOFLINE_HBM_GBS:.0f})",
                    "fuse the chunk hot path (ROADMAP item 3)"))
    coll_ms = sum(r["phases"].get("stream.exchange", 0.0)
                  + r["phases"].get("stream.materialize", 0.0)
                  for r in per_query if r["ici"])
    ici = sum(r["ici"] for r in per_query)
    if coll_ms > 0 and ici > 0:
        igbs = ici / (coll_ms / 1e3) / 1e9
        frac = min(igbs / ROOFLINE_ICI_GBS, 1.0)
        out.append((coll_ms * (1.0 - frac),
                    f"ICI roofline headroom (collectives at {igbs:.1f} "
                    f"GB/s = {frac * 100:.1f}% of {ROOFLINE_ICI_GBS:.0f})",
                    "batch/widen exchanges (ROADMAP item 4)"))
    return sorted(out, key=lambda t: t[0], reverse=True)


def render(agg, source, top=10):
    """The printable report from one collected aggregate."""
    per_query = agg["per_query"]
    sites = agg["sites"]
    site_tag = agg["site_tag"]
    fallbacks = agg["fallbacks"]
    drive_ms, drive_n = agg["drive_ms"], agg["drive_n"]
    mat_ms, mat_n = agg["mat_ms"], agg["mat_n"]
    used = [p for p in PHASES
            if any(r["phases"].get(p) for r in per_query.values())]
    if any(r["phases"].get("other") for r in per_query.values()):
        used.append("other")
    any_bytes = any(r["logical"] for r in per_query.values())
    any_ici = any(r["ici"] for r in per_query.values())
    # prefetch-stall column (StreamEvent.prefetch_stall_ms evidence):
    # driver ms blocked on the bounded prefetch ring — present whenever
    # any query carried the measurement (>= 0 means measured; the
    # collectors clamp unknown/-1 to absent)
    any_stall = any(r.get("pf_stall", 0.0) > 0.0
                    for r in per_query.values())
    # static cost-model columns: only for queries the corpus pricing
    # covers (same knobs as the measured roofline columns)
    walls = _static_walls(per_query)
    byte_heads = (" logical MB | h2d MB | eff GB/s | %HBM roof |"
                  if any_bytes else "")
    ici_heads = " ici MB | ici GB/s | %ICI roof |" if any_ici else ""
    stall_heads = " pf-stall ms |" if any_stall else ""
    static_heads = " static-roofline % | unexplained ms |" if walls else ""
    n_cols = (len(used) + 3 + (4 if any_bytes else 0)
              + (3 if any_ici else 0) + (1 if any_stall else 0)
              + (2 if walls else 0))
    lines = [f"# trace report: {len(per_query)} queries from {source}",
             "",
             "| query | total ms | " + " | ".join(used) +
             " | host syncs |" + byte_heads + ici_heads + stall_heads
             + static_heads,
             "|---" * n_cols + "|"]
    for q in sorted(per_query):
        r = per_query[q]
        cells = " | ".join(f"{r['phases'].get(p, 0.0):.1f}" for p in used)
        tail = ""
        if any_bytes:
            # effective GB/s: LOGICAL bytes served per second of streamed
            # scan wall time — what the scan achieves in uncompressed
            # terms (uploaded h2d bytes below logical = compression win)
            gbs = (r["logical"] / (r["stream_ms"] / 1e3) / 1e9) \
                if r["stream_ms"] else 0.0
            tail = (f" {r['logical'] / 1e6:.1f} | {r['h2d'] / 1e6:.1f} | "
                    f"{gbs:.2f} | {gbs / ROOFLINE_HBM_GBS * 100:.1f} |")
        if any_ici:
            # effective ICI GB/s: the explicit collectives' wire bytes
            # over the collective phase wall (the exchange pass + the
            # materialize-time cross-shard reduce)
            coll_ms = (r["phases"].get("stream.exchange", 0.0)
                       + r["phases"].get("stream.materialize", 0.0))
            igbs = (r["ici"] / (coll_ms / 1e3) / 1e9) if coll_ms else 0.0
            tail += (f" {r['ici'] / 1e6:.1f} | {igbs:.2f} | "
                     f"{igbs / ROOFLINE_ICI_GBS * 100:.1f} |")
        if any_stall:
            tail += f" {r.get('pf_stall', 0.0):.1f} |"
        if walls:
            # static-roofline %: how much of the measured wall the
            # byte-movement lower bound explains; unexplained ms is the
            # remainder — the named-overhead worklist (a negative
            # remainder would mean the "lower bound" isn't one: clamped
            # to zero, and the % then reads > 100 as the tell)
            w = walls.get(q)
            if w is not None and r["total_ms"] > 0:
                tail += (f" {w[0] / r['total_ms'] * 100:.1f} | "
                         f"{max(r['total_ms'] - w[0], 0.0):.1f} |")
            else:
                tail += " - | - |"
        lines.append(f"| {q} | {r['total_ms']:.1f} | {cells} | "
                     f"{r['syncs']} |" + tail)
    # the first dispatch of each chunk program and, since builds are
    # spans of their own, the programs the streamed statements built
    comp = sum(r["phases"].get("stream.compile", 0.0)
               + r["phases"].get("compile", 0.0)
               for r in per_query.values() if "stream.compile" in r["phases"])
    drive = sum(r["phases"].get("stream.drive", 0.0)
                for r in per_query.values())
    if comp or drive:
        ratio = f"{comp / drive:.2f}" if drive else "inf"
        lines.append(f"# streamed pipeline compile/drive ratio: {ratio} "
                     f"({comp:.1f} ms compile / {drive:.1f} ms drive)")
    lines.append("")
    lines.append(f"# top host-sync sites (of {sum(sites.values())} "
                 "attributed syncs)")
    for site, n in sites.most_common(top):
        lines.append(f"  {n:4d}  {site_tag.get(site, '?'):<12} {site}")
    lines.append("")
    if fallbacks:
        lines.append("# eager-fallback cost by reason (the streamability "
                     "widening worklist; projected = measured eager ms "
                     "minus a compiled-path estimate from this run's "
                     "per-chunk drive cost)")
        ranked = sorted(fallbacks.items(),
                        key=lambda kv: kv[1]["ms"], reverse=True)
        per_drive = drive_ms / drive_n if drive_n else None
        per_mat = mat_ms / mat_n if mat_n else 0.0
        for reason, fb in ranked:
            extra = ""
            if fb["rerun_ms"]:
                wasted = max(fb["ms"] - fb["rerun_ms"], 0.0)
                extra = (f"  (overflow rerun: {fb['rerun_ms']:.1f} ms "
                         f"eager + {wasted:.1f} ms wasted pipeline)")
            if per_drive is not None and fb["chunks"]:
                est = fb["chunks"] * per_drive + fb["queries"] * per_mat
                proj = f"{max(fb['ms'] - est, 0.0):9.1f} ms saved"
            else:
                # no compiled pipeline ran (no drive-cost basis) or the
                # span carried no chunk count: the projection is unpriced
                # (width-matched to the priced format above)
                proj = f"{'n/a':>12} saved"
            lines.append(f"  {fb['ms']:9.1f} ms  {proj}  "
                         f"{fb['syncs']:4d} syncs  "
                         f"{fb['queries']:3d} scans  {reason}{extra}")
    else:
        lines.append("# no eager-fallback streamed scans in this run")
    ranked = bottlenecks(agg)
    lines.append("")
    if ranked:
        lines.append("# next bottleneck (ranked by attributable ms)")
        for ms, what, action in ranked:
            lines.append(f"  {ms:9.1f} ms  {what} -> {action}")
    else:
        lines.append("# next bottleneck: no attributable costs in "
                     "this run")
    return lines


def metrics_report_lines(path):
    """Render a ledger's live-metrics records (``kind == "metrics"``,
    nds_tpu/obs/metrics.py rollups) as an APPEND-ONLY section: legacy
    ledgers without them return [] and the report is byte-identical to
    the pre-metrics output (pinned by tests/test_obs.py)."""
    sys.path.insert(0, REPO)
    from tools._ledger_load import ledger_mod   # stdlib-only: no jax
    recs = ledger_mod().load_ledger(path).metrics
    if not recs:
        return []

    def fmt(rec, keys):
        parts = []
        for key, label in keys:
            v = rec.get(key)
            if v is not None:
                parts.append(f"{label}={v}")
        return " ".join(parts)

    lines = ["", "# live metrics records (nds_tpu/obs/metrics.py "
             "rollups carried in the ledger)"]
    streams = [r for r in recs if r.get("scope") == "stream"]
    queries = [r for r in recs if r.get("scope") == "query"]
    for rec in streams:
        lines.append("  stream  " + fmt(rec, (
            ("app", "app"), ("phase", "phase"), ("queries", "queries"),
            ("okCount", "ok"), ("errorCount", "err"),
            ("timeoutShed", "timeoutShed"), ("faults", "faults"),
            ("qps", "qps"), ("wallP50Ms", "wallP50Ms"),
            ("wallP99Ms", "wallP99Ms"), ("wallMeanMs", "wallMeanMs"),
            ("queueWaitP50Ms", "queueWaitP50Ms"),
            ("queueWaitP99Ms", "queueWaitP99Ms"),
            ("stallMs", "stallMs"))))
    if queries:
        last = queries[-1]
        lines.append(f"  query rollups: {len(queries)} records; "
                     "last " + fmt(last, (
                         ("query", "query"), ("queries", "queries"),
                         ("qpm", "qpm"), ("wallP50Ms", "wallP50Ms"),
                         ("wallP99Ms", "wallP99Ms"),
                         ("ewmaWallMs", "ewmaWallMs"),
                         ("stallPct", "stallPct"),
                         ("queueWaitP99Ms", "queueWaitP99Ms"))))
    return lines



# ---------------------------------------------------------------------------
# --profile: the program's reader of a profiler capture
# ---------------------------------------------------------------------------
#
# What a v5e's capture holds (found on the chip, PR 26): the device plane
# ``/device:TPU:n`` has a line ``XLA Ops`` (one event per executed HLO
# operation, named by its whole HLO text) and a line ``XLA Modules`` (one
# event per program run, named ``jit_<function>(<program id>)``). The
# operation's ``op_name`` — the ``jax.named_scope`` path, where the
# engine's ``nds.*`` scopes live — IS recorded, as the stat ``tf_op``
# beside ``program_id``, but on the event's METADATA entry, which
# ``jax.profiler.ProfileData`` does not hand out (it gives the per-event
# stats only: offsets and durations). So events, lines and the host
# plane's ``nds:`` annotations (with their ``sid`` / ``parent`` / ``qid``
# stats) are read through ``ProfileData``, and the metadata stats through
# ``_event_metadata``, a reader of just those fields of the file.

ANNOTATION_PREFIX = "nds:"
SCOPE_PREFIX = "nds."
OP_LINE, MODULE_LINE = "XLA Ops", "XLA Modules"
GAP_MIN_NS = 1_000_000


def _wire_fields(buf):
    """(field number, wire type, value) of one protobuf message: varints
    as ints, length-delimited fields as memoryviews; nothing else is
    decoded."""
    i, n = 0, len(buf)

    def varint(i):
        out = shift = 0
        while True:
            b = buf[i]
            i += 1
            out |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                return out, i
    while i < n:
        key, i = varint(i)
        no, wt = key >> 3, key & 7
        if wt == 0:
            v, i = varint(i)
        elif wt == 2:
            ln, i = varint(i)
            v, i = buf[i:i + ln], i + ln
        elif wt == 1:
            v, i = buf[i:i + 8], i + 8
        elif wt == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wt}")
        yield no, wt, v


def _event_metadata(path):
    """``{plane name: {event name: {program id: op_name}}}`` from the
    XEventMetadata stats ``tf_op`` / ``program_id`` of an ``.xplane.pb``
    (XSpace.planes=1; XPlane.name=2, event_metadata=4, stat_metadata=5;
    map entries key=1 value=2; XEventMetadata.name=2, stats=5;
    XStatMetadata.name=2; XStat.metadata_id=1, uint64=3, int64=4,
    str=5, ref=7)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for no, _wt, plane in _wire_fields(space):
        if no != 1:
            continue
        pname, metas, stat_names = "", [], {}
        for f1, _w, v in _wire_fields(plane):
            if f1 == 2:
                pname = bytes(v).decode()
            elif f1 == 4:
                metas += [v2 for f2, _w2, v2 in _wire_fields(v) if f2 == 2]
            elif f1 == 5:
                key = name = None
                for f2, _w2, v2 in _wire_fields(v):
                    if f2 == 1:
                        key = v2
                    elif f2 == 2:
                        for f3, _w3, v3 in _wire_fields(v2):
                            if f3 == 2:
                                name = bytes(v3).decode()
                stat_names[key] = name
        by_name = {}
        for em in metas:
            ename, tf_op, program = "", None, None
            for f1, _w, v in _wire_fields(em):
                if f1 == 2:
                    ename = bytes(v).decode(errors="replace")
                elif f1 == 5:
                    sid = sval = None
                    for f2, _w2, v2 in _wire_fields(v):
                        if f2 == 1:
                            sid = v2
                        elif f2 in (3, 4):
                            sval = v2
                        elif f2 == 5:
                            sval = bytes(v2).decode(errors="replace")
                        elif f2 == 7:
                            sval = stat_names.get(v2, "")
                    if stat_names.get(sid) == "tf_op":
                        tf_op = sval
                    elif stat_names.get(sid) == "program_id":
                        program = sval
            if tf_op:
                by_name.setdefault(ename, {})[program] = tf_op
        if by_name:
            out[pname] = by_name
    return out


def scope_of(op_name):
    """The engine scopes of an operation's ``op_name``, outermost first
    (``jit(traced)/nds.stream.chunk/nds.join/jit(_key_hash_impl)/
    nds.join.key_hash/xor`` -> ``["stream.chunk", "join",
    "join.key_hash"]``)."""
    return [c[len(SCOPE_PREFIX):] for c in (op_name or "").split("/")
            if c.startswith(SCOPE_PREFIX)]


_HLO_HEAD = re.compile(r"^(%[\w.\-]+) = (.*?[\}\)\]]) ([\w\-]+)\(")


def short_hlo(name, limit=96):
    """``%while.4 while (u32[], s32[4194304], ...)`` from a whole HLO
    line: result name, opcode, result type without layouts."""
    m = _HLO_HEAD.match(name)
    if not m:
        return name[:limit]
    lhs, rtype, opcode = m.groups()
    rtype = re.sub(r"\{[^{}]*\}", "", rtype)
    return f"{lhs} {opcode} {rtype}"[:limit]


def read_profile(path):
    """One ``.xplane.pb`` as plain data:

    ``{"ops": [(plane, name, start_ns, dur_ns, scopes, module)],
    "notes": [{"name", "start", "end", "sid", "parent", "qid", "line"}]}``

    ``scopes`` from the operation's ``op_name`` (see the note above);
    ``module`` is the ``jit_<function>`` of the program run that holds
    the operation. Where a capture has no device plane (the CPU
    backend) the host lines' events that carry an ``hlo_module`` stat
    stand in for the operations, named by module alone."""
    import bisect
    import warnings

    from jax.profiler import ProfileData
    # iterating an event's stats warns about the binding's own type
    warnings.filterwarnings("ignore", category=DeprecationWarning,
                            message=".*event_stats.*")
    data = ProfileData.from_file(path)
    meta = _event_metadata(path)
    dev = re.compile(r"^/device:(TPU|GPU):\d+$")
    ops, notes, host_ops = [], [], []
    for plane in data.planes:
        if dev.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            mods = sorted((int(e.start_ns), int(e.start_ns + e.duration_ns),
                           e.name) for e in lines[MODULE_LINE].events) \
                if MODULE_LINE in lines else []
            starts = [m[0] for m in mods]
            by_name = meta.get(plane.name, {})
            for e in (lines[OP_LINE].events if OP_LINE in lines else ()):
                start, dur = int(e.start_ns), int(e.duration_ns)
                k = bisect.bisect_right(starts, start) - 1
                module, program = "", None
                if k >= 0 and start < mods[k][1]:
                    m = re.match(r"^(.*)\((-?\d+)\)$", mods[k][2])
                    module = m.group(1) if m else mods[k][2]
                    program = int(m.group(2)) if m else None
                cands = by_name.get(e.name) or {}
                op_name = cands.get(program)
                if op_name is None and len(cands) == 1:
                    op_name = next(iter(cands.values()))
                ops.append((plane.name, e.name, start, dur,
                            scope_of(op_name), module))
            continue
        for k, ln in enumerate(plane.lines):
            # a host thread is a line; the OS name of a Python thread is
            # "python" for all of them, so a line is known by its place
            thread = (plane.name, k)
            for e in ln.events:
                if e.name.startswith(ANNOTATION_PREFIX):
                    st = {key: val for key, val in e.stats}
                    notes.append({
                        "name": e.name[len(ANNOTATION_PREFIX):],
                        "start": int(e.start_ns),
                        "end": int(e.start_ns + e.duration_ns),
                        "sid": int(st.get("sid", 0)) or None,
                        "parent": int(st.get("parent", 0)) or None,
                        "qid": int(st.get("qid", 0)) or None,
                        "line": thread})
                elif not ops:
                    st = {key: val for key, val in e.stats}
                    if "hlo_module" in st:
                        host_ops.append((plane.name, e.name,
                                         int(e.start_ns),
                                         int(e.duration_ns), [],
                                         str(st["hlo_module"])))
    return {"ops": ops or host_ops, "notes": notes}


def _self_ns(events):
    """Per event, its duration minus the events nested in it on the same
    line (a ``while`` holds its body's operations): what a by-scope sum
    may add up without counting a nanosecond twice."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], events[i][2], -events[i][3]))
    self_ns = [e[3] for e in events]
    stack = []
    for i in order:
        start, end = events[i][2], events[i][2] + events[i][3]
        if stack and events[stack[-1][0]][0] != events[i][0]:
            stack = []                   # the next device plane
        while stack and stack[-1][1] <= start:
            stack.pop()
        if stack and end <= stack[-1][1]:
            self_ns[stack[-1][0]] -= events[i][3]
        stack.append((i, end))
    return self_ns


def _merge(intervals):
    merged = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _host_during(notes, g0, g1):
    """What the host was doing in ``[g0, g1)``: ``(driver, beside)``,
    each ``[(annotation name, ns)]`` longest first. An annotation's share
    is its overlap with the gap minus its children's (by ``parent`` id)
    on the same host thread, so the innermost open span gets the time.
    ``beside``: annotations that ran on another thread than their parent
    (the prefetch ring's worker)."""
    def overlap(n):
        return max(min(n["end"], g1) - max(n["start"], g0), 0)
    by_sid = {n["sid"]: n for n in notes if n["sid"]}
    own = {}
    beside = {}
    for n in notes:
        ov = overlap(n)
        if not ov:
            continue
        parent = by_sid.get(n["parent"])
        if parent is not None and parent["line"] != n["line"]:
            beside[n["name"]] = beside.get(n["name"], 0) + ov
            continue
        own[id(n)] = own.get(id(n), 0) + ov
        if parent is not None:
            own[id(parent)] = own.get(id(parent), 0) - ov
    names = {}
    for n in notes:
        ns = own.get(id(n), 0)
        if ns > 0:
            names[n["name"]] = names.get(n["name"], 0) + ns

    def ranked(d):
        return sorted(d.items(), key=lambda kv: -kv[1])
    return ranked(names), ranked(beside)


def profile_statements(prof):
    """The capture cut into statements: ``[(label, start, end, qid)]``
    from the ``nds:`` annotations of each ``qid`` (statement, then its
    materialize / collect), in time order; the whole capture as one
    window where it holds no statement."""
    spans = {}
    for n in prof["notes"]:
        if n["qid"] and n["sid"]:
            lo, hi = spans.get(n["qid"], (n["start"], n["end"]))
            spans[n["qid"]] = (min(lo, n["start"]), max(hi, n["end"]))
    if spans:
        return [(f"qid {q}", lo, hi, q)
                for q, (lo, hi) in sorted(spans.items(),
                                          key=lambda kv: kv[1][0])]
    if not prof["ops"]:
        return []
    lo = min(o[2] for o in prof["ops"])
    hi = max(o[2] + o[3] for o in prof["ops"])
    return [("capture", lo, hi, None)]


def profile_report_lines(path, label, top=10):
    prof = read_profile(path)
    ops, notes = prof["ops"], prof["notes"]
    lines = [f"# profile {label}: {len(ops)} device operations, "
             f"{len(notes)} nds: annotations"]
    if not ops:
        return lines + ["  no device operation in this capture"]
    self_ns = _self_ns(ops)
    for wlabel, lo, hi, qid in profile_statements(prof):
        idx = [i for i, o in enumerate(ops) if lo <= o[2] < hi]
        planes = sorted({ops[i][0] for i in idx}) or [""]
        busy = sum(e - s for pl in planes for s, e in _merge(
            [[ops[i][2], min(ops[i][2] + ops[i][3], hi)]
             for i in idx if ops[i][0] == pl])) / len(planes)
        window = hi - lo
        lines.append(f"## {wlabel}: window {window / 1e6:.1f} ms, device "
                     f"busy {busy / 1e6:.1f} ms "
                     f"({100.0 * busy / max(window, 1):.1f}%)")
        by_scope = {}
        by_op = {}
        for i in idx:
            _pl, name, _s, dur, scopes, module = ops[i]
            key = scopes[-1] if scopes else (module or "?")
            by_scope[key] = by_scope.get(key, 0) + self_ns[i]
            where = " > ".join(scopes) if scopes else ""
            okey = (short_hlo(name), where, module)
            agg = by_op.setdefault(okey, [0, 0])
            agg[0] += dur
            agg[1] += 1
        lines.append("  device time by scope (self time of each "
                     "operation; a scope, else the jitted function):")
        for key, ns in sorted(by_scope.items(),
                              key=lambda kv: -kv[1])[:top]:
            lines.append(f"    {ns / 1e6:10.2f} ms  {key}")
        lines.append(f"  top {top} device operations (inclusive):")
        for (hlo, where, module), (ns, n) in sorted(
                by_op.items(), key=lambda kv: -kv[1][0])[:top]:
            named = where or "-"
            lines.append(f"    {ns / 1e6:10.2f} ms  x{n:<5d} "
                         f"{named}  [{module or '?'}]  {hlo}")
        gaps = []
        for pl in planes:
            merged = _merge([[max(ops[i][2], lo),
                              min(ops[i][2] + ops[i][3], hi)]
                             for i in idx if ops[i][0] == pl])
            edges = [lo] + [x for se in merged for x in se] + [hi]
            gaps += [(g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2])
                     if g1 - g0 >= GAP_MIN_NS]
        mine = [n for n in notes if qid is None or n["qid"] == qid]
        lines.append(f"  idle gaps over {GAP_MIN_NS / 1e6:.0f} ms: "
                     f"{len(gaps)}, "
                     f"{sum(g1 - g0 for g0, g1 in gaps) / 1e6:.1f} ms")
        for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
            driver, beside = _host_during(mine, g0, g1)
            what = ", ".join(f"{n} {ns / 1e6:.1f}" for n, ns in driver[:4]) \
                or "no nds: span open"
            if beside:
                what += "; beside: " + ", ".join(
                    f"{n} {ns / 1e6:.1f}" for n, ns in beside[:3])
            lines.append(f"    {(g1 - g0) / 1e6:8.2f} ms at "
                         f"+{(g0 - lo) / 1e6:.1f} ms  host: {what}")
    return lines


def profile_report(profile_dir, top=10):
    """Lines of the ``--profile`` report over every ``*.xplane.pb`` under
    ``profile_dir`` (``nds_power.py --profile`` writes one capture
    per query under ``<dir>/<query>/``)."""
    files = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        return [f"# no *.xplane.pb under {profile_dir}"]
    lines = []
    for path in files:
        rel = os.path.relpath(path, profile_dir)
        label = rel.split(os.sep)[0] if os.sep in rel else rel
        lines += profile_report_lines(path, label, top=top)
    return lines


def compile_report_lines(path, top=10):
    """"compile by program" from a ledger's terminal record: the
    process's program builds (``compiles`` = the totals of
    ``nds_tpu/obs/compiles.py`` and its twenty programs dearest to
    compile). [] for a ledger without the block (an older one, a killed
    run)."""
    sys.path.insert(0, REPO)
    from tools._ledger_load import ledger_mod   # stdlib-only: no jax
    comp = (ledger_mod().load_ledger(path).end or {}).get("compiles")
    if not comp:
        return []

    def row(r):
        return (f"{r.get('builds', 0):6d} {r.get('hits', 0):5d} "
                f"{r.get('misses', 0):6d} {r.get('backendMs', 0.0):11.1f} "
                f"{r.get('readMs', 0.0):9.1f} {r.get('traceMs', 0.0):9.1f} "
                f"{r.get('lowerMs', 0.0):9.1f}")
    lines = ["", "# compile by program (the process's builds; backend = "
             "XLA compiles on cache misses, read = persistent-cache "
             "hits, trace + lower = host Python no cache saves)",
             "  builds  hits misses  backend ms   read ms  trace ms  "
             "lower ms  program",
             "  " + row(comp) + "  (all)"]
    for r in (comp.get("programs") or [])[:top]:
        lines.append("  " + row(r) + f"  {r.get('program', '?')}")
    return lines


def report(source, top=10):
    """Aggregate a --trace-dir (directory) or a campaign evidence ledger
    (file); returns the printable lines."""
    if os.path.isdir(source):
        agg = collect_from_traces(source)
        if agg is None:
            return [f"# no *.trace.json files under {source}"]
    elif not os.path.exists(source):
        return [f"# {source}: no such trace dir or ledger file"]
    else:
        agg = collect_from_ledger(source)
        if agg is None:
            return [f"# no completed query records in ledger {source}"]
        # the live-metrics section stays last (append-only: a ledger
        # without those records reads exactly as the lines before it)
        return (render(agg, source, top=top)
                + compile_report_lines(source, top=top)
                + metrics_report_lines(source))
    return render(agg, source, top=top)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="aggregate a --trace-dir (or a campaign evidence "
        "ledger file) into the per-phase breakdown table (PERF.md), "
        "roofline columns, top sync sites, fallback costs and the "
        "ranked next-bottleneck summary")
    ap.add_argument("trace_dir", nargs="?",
                    help="directory of *.trace.json files "
                    "written by nds_power.py --trace-dir, OR a campaign "
                    "evidence ledger file (bench.py resume JSONL / "
                    "nds_power.py --ledger)")
    ap.add_argument("--profile", metavar="DIR",
                    help="read a profiler capture instead (nds_power.py "
                    "--profile DIR): device time by engine scope, "
                    "top operations with their scope, idle gaps with the "
                    "host span open in them")
    ap.add_argument("--top", type=int, default=10,
                    help="sync sites / scopes / operations / gaps to "
                    "list (default 10)")
    args = ap.parse_args(argv)
    if args.profile:
        lines = profile_report(args.profile, top=args.top)
    elif args.trace_dir:
        lines = report(args.trace_dir, top=args.top)
    else:
        ap.error("give a trace dir / ledger, or --profile DIR")
    for ln in lines:
        print(ln)
    return 0


if __name__ == "__main__":
    sys.exit(main())
