# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Trace exporters: Chrome ``trace_event`` files and per-query rollups.

One file per query, loadable in ``chrome://tracing`` / Perfetto: spans
become ``"ph": "X"`` complete events (microsecond ts/dur from the span's
host clock), sync-site events become thin ``"X"`` slices whose width is
the time the host spent BLOCKED on that read — the stall is visible at a
glance. The whole document stays plain JSON, so ``tools/trace_report.py``
aggregates the same files the browser loads.
"""

from __future__ import annotations

import json
from collections import Counter

from nds_tpu.obs.trace import COMPILE, STATEMENT, SpanRecord, SyncSite


def to_chrome(records, query: str = "", pid: int = 0,
              tid: int = 0, roll: dict | None = None) -> dict:
    """Chrome trace_event document (object form) for one drained record
    list. Extra top-level keys are legal in the format; ``nds`` carries
    the query name and the rollup so readers need not re-aggregate.
    Callers that already computed :func:`rollup` (the drivers stamp it
    into the query summary too) pass it as ``roll`` to skip the rewalk."""
    events = []
    for r in records:
        if isinstance(r, SpanRecord):
            args = {"syncs": r.syncs,
                    "syncWaitMs": round(r.sync_wait_ns / 1e6, 3),
                    "compileMs": round(r.compile_ns / 1e6, 3),
                    "sid": r.sid, "parent": r.parent, "qid": r.qid}
            args.update(r.attrs)
            # a re-recorded ring-worker stage ran beside the driver: its
            # own row in the viewer
            events.append({
                "name": r.name, "cat": "query", "ph": "X",
                "ts": r.ts_ns / 1e3, "dur": r.dur_ns / 1e3,
                "pid": pid,
                "tid": tid + 1 if r.thread == "worker" else tid,
                "args": args})
        elif isinstance(r, SyncSite):
            events.append({
                "name": f"sync:{r.tag}", "cat": "sync", "ph": "X",
                "ts": r.ts_ns / 1e3 - r.wait_ns / 1e3,
                "dur": max(r.wait_ns / 1e3, 1.0),
                "pid": pid, "tid": tid,
                "args": {"site": r.site, "syncs": r.syncs, "sid": r.sid,
                         "parent": r.parent, "qid": r.qid}})
    return {"traceEvents": events,
            "displayTimeUnit": "ms",
            "nds": {"query": query,
                    "rollup": rollup(records) if roll is None else roll}}


def write_chrome_trace(path: str, records, query: str = "",
                       roll: dict | None = None) -> None:
    with open(path, "w") as f:
        # compact: the consumers (chrome://tracing, Perfetto,
        # tools/trace_report.py) are all programmatic, and a ~2500-chunk
        # streamed scan emits thousands of events per file
        json.dump(to_chrome(records, query=query, roll=roll), f,
                  separators=(",", ":"))


# the spans that dispatch a streamed scan's chunk program: the first of
# them (chunk 0) ends a statement's lead-in
DISPATCH_SPANS = ("stream.compile", "stream.drive")


def _lead_in_ns(spans) -> int:
    """Per statement (``qid``): from the ``statement`` span's start to
    the start of the first span that dispatches chunk 0 of the
    statement's first streamed scan; summed over the statements in
    ``spans``, 0 where none streamed."""
    starts = {r.qid: r.ts_ns for r in spans if r.name == STATEMENT}
    first: dict = {}
    for r in spans:
        if r.name in DISPATCH_SPANS and r.attrs.get("chunk") == 0 \
                and r.qid in starts:
            first[r.qid] = min(first.get(r.qid, r.ts_ns), r.ts_ns)
    return sum(t - starts[q] for q, t in first.items())


def rollup(records, top_sites: int = 5) -> dict:
    """Per-query aggregate the drivers merge into their JSON summaries:
    per-phase totals by span name, the top sync-charging host-read sites,
    and any eager-fallback streamed scans with their reason — the
    phase-attribution slice of the full trace.

    Per phase: ``ms`` / ``count`` / ``syncs`` (inclusive, children
    counted), ``selfMs`` (duration minus what the direct children OF THE
    SAME THREAD cover, from ``parent``; a re-recorded worker stage ran
    beside the driver and is taken out of nobody), ``syncWaitMs`` /
    ``compileMs`` (the same self share of the span's counter deltas) and
    ``rootMs`` (durations of the phase's parentless spans: what the tree
    covers of a call's wall, by no span's name). ``phases["stream"]``
    also carries ``leadInMs`` (:func:`_lead_in_ns`), and a phase whose
    spans state the ``cells`` they move (``op.gather``: index width x
    arrays gathered; ``op.join`` / ``op.semi_join``: both sides' key
    arrays and the indices or mask out) carries their sum, as does
    ``probeRows`` of ``op.join``: the bucket each join's two binary
    searches ran at (under the probe side's bucket: narrowed to its
    candidates), and ``buildRows``, the bucket its build side's hash was
    sorted at (neither inside a stream-bounds region; on the chunked arm
    ``op.join`` also states ``spans``, the spans of probe rows whose pairs
    it made), and ``deferredArrays`` of
    ``op.gather``: the columns' arrays read through a composed index, never
    gathered at the width of the PK-gather join that brought them, and
    every array read through a pair table's index. ``op.setop`` states the key
    arrays its DISTINCT reads, ``op.concat`` the arrays it appends at the
    output's bucket, ``op.window`` rows sorted x arrays scanned. ``plan``
    states ``scanColumns``: the columns its catalog scans kept after the
    projection pushdown, summed over the statement's scans. ``op.subquery``
    states the key arrays its decorrelation reads as ``cells`` and, each
    0 / 1 a span and so a count a phase, ``planned`` (this evaluator
    planned the inner query), ``correlated``, ``residual``, ``negated``.
    ``sortedRows``: the input buckets the compaction's sort arm sorted
    (``ops.compact_indices``), stated on whichever span is innermost:
    ``op.compact``, ``op.join``, ``op.pk_gather`` and the like.
    ``op.group_ids`` states ``keys`` (its arity), ``rows`` (the bucket it
    grouped at), ``groups`` (the groups it found) and ``arm`` (``packed``
    | ``fold``; on the span alone); ``plan`` states ``cteReads``, the
    reads of a ``WITH`` body by name; ``op.join`` states ``stringKey``
    (True, a count here) where its key columns are strings.
    ``phases["compile"]`` (one span a program build,
    :mod:`nds_tpu.obs.compiles`) carries the sums of its spans'
    ``backendMs`` / ``readMs`` / ``traceMs`` / ``lowerMs`` and counts
    their ``cache`` outcomes as ``hits`` / ``misses``; a build is its
    parent's child, so the parent's ``selfMs`` and self ``compileMs`` no
    longer hold it.

    ``syncSites``: the ``top_sites`` sites by ``syncs``, each with the
    time its reads waited (``waitMs``) and the longest one (``maxWaitMs``)."""
    phases: dict = {}
    sites: Counter = Counter()
    site_tag: dict = {}
    site_wait: dict = {}
    fallbacks = []
    spans = [r for r in records if isinstance(r, SpanRecord)]
    # what each span's same-thread direct children cover
    child: dict = {}
    for r in spans:
        if r.parent is not None and r.thread == "driver":
            c = child.setdefault(r.parent, [0, 0, 0])
            c[0] += r.dur_ns
            c[1] += r.sync_wait_ns
            c[2] += r.compile_ns
    for r in records:
        if isinstance(r, SpanRecord):
            p = phases.setdefault(r.name, {
                "ms": 0.0, "count": 0, "syncs": 0, "selfMs": 0.0,
                "syncWaitMs": 0.0, "compileMs": 0.0, "rootMs": 0.0})
            p["ms"] = round(p["ms"] + r.dur_ns / 1e6, 3)
            p["count"] += 1
            p["syncs"] += r.syncs
            dur, wait, comp = child.get(r.sid, (0, 0, 0))
            p["selfMs"] = round(
                p["selfMs"] + max(r.dur_ns - dur, 0) / 1e6, 3)
            p["syncWaitMs"] = round(
                p["syncWaitMs"] + max(r.sync_wait_ns - wait, 0) / 1e6, 3)
            p["compileMs"] = round(
                p["compileMs"] + max(r.compile_ns - comp, 0) / 1e6, 3)
            if r.parent is None:
                p["rootMs"] = round(p["rootMs"] + r.dur_ns / 1e6, 3)
            for k in ("cells", "probeRows", "buildRows", "deferredArrays",
                      "spans", "scanColumns", "planned", "correlated",
                      "residual", "negated", "sortedRows", "keys", "rows",
                      "groups", "cteReads", "stringKey"):
                if k in r.attrs:
                    p[k] = p.get(k, 0) + r.attrs[k]
            if r.name == COMPILE:
                for k in ("backendMs", "readMs", "traceMs", "lowerMs"):
                    p[k] = round(p.get(k, 0.0) + r.attrs.get(k, 0.0), 3)
                for k, outcome in (("hits", "hit"), ("misses", "miss")):
                    p[k] = p.get(k, 0) + (r.attrs.get("cache") == outcome)
            if r.name == "stream" and r.attrs.get("path") == "eager":
                fallbacks.append({
                    "table": r.attrs.get("table", "?"),
                    "reason": r.attrs.get("reason", ""),
                    "ms": round(r.dur_ns / 1e6, 3), "syncs": r.syncs})
        elif isinstance(r, SyncSite):
            sites[r.site] += r.syncs
            site_tag.setdefault(r.site, r.tag)
            wait = site_wait.setdefault(r.site, [0, 0])
            wait[0] += r.wait_ns
            wait[1] = max(wait[1], r.wait_ns)
    if "stream" in phases:
        phases["stream"]["leadInMs"] = round(_lead_in_ns(spans) / 1e6, 3)
    out = {"phases": phases,
           "syncSites": [{"site": s, "tag": site_tag[s], "syncs": n,
                          "waitMs": round(site_wait[s][0] / 1e6, 3),
                          "maxWaitMs": round(site_wait[s][1] / 1e6, 3)}
                         for s, n in sites.most_common(top_sites)]}
    if fallbacks:
        out["fallbacks"] = fallbacks
    return out
