# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""From a profiler trace to device busy time, idle share, the operations
that took most time and the longest idle gaps.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/*.xplane.pb``;
``read_planes`` turns it into plain lists (``jax.profiler.ProfileData``
needs nothing but JAX), and everything after that is arithmetic on

    [{"name": plane, "lines": [{"name": line, "events": [[name, start_ns,
                                                          duration_ns]]}]}]

so the reduction is checked on a small recorded trace kept as JSON.

Busy is the union of the intervals in which an operation ran on a device
plane, clipped to the window; idle share is 1 - busy / window. The window is
the span of the harness's own annotations (one per statement) on the host
plane, which the profiler writes on the same clock as the device planes.

There is one yardstick and no second choice: operations come from the device
planes' ``XLA Ops`` line alone and the window from the annotations alone. A
trace that lacks either gives nothing to read (``reduce_trace`` returns None)
rather than a number from whole programs, overlapping lines or the devices'
own extent, which would read busier and less idle than the same run.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
# the device line that holds single operations
OP_LINE = "XLA Ops"


_HLO = re.compile(r"^(%[\w.\-]+) = (.*?[\}\)\]]) ([\w\-]+)\(")


def short_name(event_name: str, limit: int = 120) -> str:
    """An operation's trace name cut to a readable length. The TPU's trace
    names an operation by its whole HLO line; keep its result name, its
    opcode and its result type without layouts: ``%while.4 while (u32[],
    s32[4194304], ...)``. Other names pass through, cut to ``limit``."""
    m = _HLO.match(event_name)
    if not m:
        return event_name[:limit]
    lhs, result_type, opcode = m.groups()
    result_type = re.sub(r"\{[^{}]*\}", "", result_type)
    result_type = re.sub(r"/\*[^*]*\*/", "", result_type)
    return f"{lhs} {opcode} {result_type}"[:limit]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_planes(path: str) -> list:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                      for e in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def device_planes(planes: list) -> list:
    return [p for p in planes if DEVICE_PLANE.match(p["name"])]


def op_events(plane: dict) -> list:
    """The plane's single-operation events: its OP_LINE, or none."""
    return [e for ln in plane["lines"] if ln["name"] == OP_LINE
            for e in ln["events"]]


def annotations(planes: list, names) -> list:
    """[(name, start_ns, end_ns)] of the host-plane events whose name is one
    of ``names`` (the harness's per-statement TraceAnnotations), by start."""
    want = set(names)
    out = []
    for p in planes:
        if DEVICE_PLANE.match(p["name"]):
            continue
        for ln in p["lines"]:
            for name, start, dur in ln["events"]:
                if name in want:
                    out.append((name, start, start + dur))
    return sorted(out, key=lambda a: a[1])


def merge(intervals: list) -> list:
    """Union of [start, end) intervals as a sorted list of disjoint ones."""
    merged = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def clip(intervals: list, lo: int, hi: int) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def reduce_trace(planes: list, annotation_names, top: int = 10) -> dict | None:
    """{"window_s", "busy_s", "idle_share", "devices", "device_ops",
    "idle_gaps", "op_line_events"}; None where the trace holds no operation
    on a device plane's OP_LINE, or none of the annotations (nothing to
    read)."""
    devs = device_planes(planes)
    notes = annotations(planes, annotation_names)
    per_dev = []
    op_time: dict = {}
    n_events = 0
    for p in devs:
        events = op_events(p)
        n_events += len(events)
        per_dev.append(merge([[s, s + d] for _n, s, d in events]))
        for name, _s, d in events:
            op_time[name] = op_time.get(name, 0) + d
    if not n_events or not notes:
        return None
    lo, hi = notes[0][1], max(a[2] for a in notes)
    window_ns = hi - lo
    if window_ns <= 0:
        return None
    busy_ns = []
    gaps = []
    for merged in per_dev:
        inside = clip(merged, lo, hi)
        busy_ns.append(sum(e - s for s, e in inside))
        edges = [lo] + [x for s, e in inside for x in (s, e)] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                gaps.append((g0, g1))
    busy_s = sum(busy_ns) / len(busy_ns) / 1e9
    window_s = window_ns / 1e9

    def label(t):
        for name, s, e in notes:
            if s <= t < e:
                return name
        return "between_statements"

    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": window_s, "busy_s": busy_s,
            "idle_share": 1.0 - busy_s / window_s,
            "devices": len(devs), "op_line_events": n_events,
            "device_ops": [[short_name(n), d / 1e9] for n, d in ops],
            "idle_gaps": [[label(g0), (g1 - g0) / 1e9] for g0, g1 in longest],
            "busy_by_annotation": _busy_by_annotation(per_dev, notes)}


def _busy_by_annotation(per_dev: list, notes: list) -> dict:
    """{annotation name: mean-over-devices busy seconds inside it}."""
    out: dict = {}
    for name, s, e in notes:
        busy = [sum(b - a for a, b in clip(m, s, e)) for m in per_dev]
        out[name] = out.get(name, 0.0) + sum(busy) / max(len(busy), 1) / 1e9
    return out


def summary(planes: list, max_lines: int = 12) -> list:
    """What a trace holds, for a first look by hand: plane names, and the
    busiest lines of each with their event counts and first event name."""
    out = []
    for p in planes:
        lines = sorted(p["lines"], key=lambda ln: -len(ln["events"]))
        out.append({"plane": p["name"], "lines": [
            [ln["name"], len(ln["events"]),
             short_name(ln["events"][0][0]) if ln["events"] else None]
            for ln in lines[:max_lines]]})
    return out
