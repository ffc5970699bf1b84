# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""One record a program build, made where the build happens.

JAX's ``monitoring`` stream reports every part of a build synchronously
on the thread that asked for the program, in this order (JAX 0.9):

* ``/jax/core/compile/jaxpr_trace_duration`` (``fun_name`` ``cumsum``):
  the Python trace; traces nested inside it end, and arrive, before it;
* ``/jax/core/compile/jaxpr_to_mlir_module_duration`` (``jit(cumsum)``):
  the lowering;
* ``/jax/compilation_cache/cache_hits`` or ``cache_misses``: what the
  persistent cache did (neither where it is off, or took no part: a
  program with a host callback is never written);
* ``/jax/core/compile/backend_compile_duration`` (``jit(cumsum)``): closes
  the build. The event wraps ``compile_or_get_cached``, so on a hit its
  whole duration is the READ (key hashing, the file, deserialisation: JAX's
  own ``cache_retrieval_time_sec`` is the middle part of it) and no XLA
  compile ran.

``ops.enable_compile_meter()`` registers :func:`listeners` once. At the
closing event the pending parts become one record: ``program``, ``cache``
(``hit`` | ``miss`` | ``off``), ``backendMs`` (0 on a hit), ``readMs`` (0
unless a hit), ``traceMs`` (the last trace whose name the program's name
wraps, nested traces inside it not added again), ``lowerMs``. The record
goes two places:

* a ``compile`` span in the calling thread's ring
  (:func:`nds_tpu.obs.trace.note_compile`): a driver-thread child of the
  innermost open span, from the start of the trace to the end of the
  backend step, the record's fields as attributes. No span with
  ``NDS_TPU_TRACE=off``;
* this module's process-lifetime table, keyed by ``program``: always on,
  lock-guarded, bounded by the number of distinct program names. Read by
  :func:`totals` and :func:`table`.

The pending parts and the per-thread sums (:func:`thread_sums`, what a
statement's evidence reads around one call) live in ``ops._sync_tls``
beside the sync counters; ``ops.compile_ns()`` is the closing events'
durations summed, as before: ``backendMs + readMs`` to the nanosecond.

A trace made ahead of its build (``Jitted.trace``: ``replay.compile``)
is claimed only if no other build closes in between; otherwise it stays
in the self time of the span it ran under, as before. Listeners fire only
when JAX builds a program: a warm process pays nothing.
"""

from __future__ import annotations

import threading
import time

from nds_tpu.obs import trace as _trace

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}
# traces kept pending on a thread: a build claims one of the last few
# (its own arrives last but for what its lowering traces); traces that
# no build follows (eval_shape, Jitted.trace) must not pile up
_MAX_PENDING = 16

# a row of the table, and a thread's sums: three counts, four sums in ns
_COUNTS = ("builds", "hits", "misses")
_PARTS = ("backend", "read", "trace", "lower")

_tls = None               # ops._sync_tls, handed over by listeners()
_lock = threading.Lock()
_table: dict = {}         # program -> row


def listeners(tls) -> tuple:
    """The event listener and the duration listener
    ``ops.enable_compile_meter()`` registers with ``jax.monitoring``,
    bound to ops' thread-local."""
    global _tls
    _tls = tls
    return _on_event, _on_duration


def _on_event(event: str, **_kw) -> None:
    outcome = _CACHE_EVENTS.get(event)
    if outcome is not None:
        _tls.build_cache = outcome


def _on_duration(event: str, secs: float, fun_name: str = "", **_kw) -> None:
    if event == BACKEND_EVENT:
        _close(fun_name, int(secs * 1e9))
    elif event == TRACE_EVENT:
        ns = int(secs * 1e9)
        pending = getattr(_tls, "build_traces", None)
        if pending is None:
            pending = _tls.build_traces = []
        pending.append((fun_name, time.perf_counter_ns() - ns, ns))
        del pending[:-_MAX_PENDING]
    elif event == LOWER_EVENT:
        ns = int(secs * 1e9)
        _tls.build_lower = (fun_name, time.perf_counter_ns() - ns, ns)


def _new_row() -> dict:
    return dict.fromkeys(_COUNTS + _PARTS, 0)


def _add(row: dict, rec: dict) -> None:
    for k, v in rec.items():
        row[k] += v


def _close(program: str, ns: int) -> None:
    """The backend step of ``program`` ended after ``ns``: assemble the
    thread's pending parts into one record, clear them, hand it on."""
    tls = _tls
    end = time.perf_counter_ns()
    tls.compile_ns = getattr(tls, "compile_ns", 0) + ns
    cache = getattr(tls, "build_cache", "off")
    tls.build_cache = "off"
    hit = cache == "hit"
    rec = {"builds": 1, "hits": int(hit), "misses": int(cache == "miss"),
           "backend": 0 if hit else ns, "read": ns if hit else 0,
           "trace": 0, "lower": 0}
    start = end - ns
    lower = getattr(tls, "build_lower", None)
    if lower is not None and lower[0] == program:
        start, rec["lower"] = min(start, lower[1]), lower[2]
    pending = getattr(tls, "build_traces", None) or []
    for name, t0, t_ns in reversed(pending):
        if program.endswith(f"({name})"):
            start, rec["trace"] = min(start, t0), t_ns
            break
    tls.build_lower = None
    del pending[:]
    # builds of one thread never overlap in the ring: a compile inside a
    # trace (rare: compile-time evaluation) closed first and keeps its time
    start = max(start, getattr(tls, "build_end", 0))
    tls.build_end = end
    sums = getattr(tls, "build_sums", None)
    if sums is None:
        sums = tls.build_sums = _new_row()
    _add(sums, rec)
    with _lock:
        _add(_table.setdefault(program, _new_row()), rec)
    _trace.note_compile(
        start, end - start, ns, program=program, cache=cache,
        **{f"{k}Ms": round(rec[k] / 1e6, 3) for k in _PARTS})


def _row_json(row: dict) -> dict:
    out = {k: row[k] for k in _COUNTS}
    out.update({f"{k}Ms": row[k] / 1e6 for k in _PARTS})
    return out


def thread_sums() -> dict:
    """The calling thread's own builds since it started, in the keys of
    :func:`totals` (zeros before the meter is on): what a statement's
    evidence reads around one call."""
    return _row_json(getattr(_tls, "build_sums", None) or _new_row())


def totals() -> dict:
    """The process's builds so far, all threads: ``builds``, ``hits``,
    ``misses`` and the sums ``backendMs`` (XLA compiles), ``readMs``
    (persistent-cache reads), ``traceMs``, ``lowerMs``."""
    total = _new_row()
    with _lock:
        for row in _table.values():
            _add(total, row)
    return _row_json(total)


def table(top: int | None = None) -> list:
    """One row a program name (``program`` + the keys of
    :func:`totals`), the dearest to compile first (``backendMs``, then
    ``readMs``); the first ``top`` rows where given."""
    with _lock:
        rows = [dict(program=name, **_row_json(row))
                for name, row in _table.items()]
    rows.sort(key=lambda r: (-r["backendMs"], -r["readMs"], r["program"]))
    return rows[:top]
