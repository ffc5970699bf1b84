# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Zero-sync span tracing: the engine-side half of the obs layer.

A span is a host-clock interval (``time.perf_counter_ns`` at enter/exit)
with the thread's sync accounting deltas attached: host syncs charged,
nanoseconds blocked on device->host reads, and XLA backend-compile
nanoseconds — all read from the counters :mod:`nds_tpu.engine.ops`
already maintains, so opening a span never touches the device. Sync-site
events (:class:`SyncSite`) are emitted by ``ops.host_read`` itself when a
fetch actually charged syncs, carrying the first-class call-site tag that
``tools/sync_profile.py`` used to recover by monkeypatching.

One statement is one tree. Every record carries ``sid`` (a per-process
id), ``parent`` (the ``sid`` of the innermost span open on the thread at
enter) and ``qid`` (the statement's id: set by the root ``statement``
span, inherited by everything under it, kept by the ``Result`` so the
``materialize`` / ``collect`` spans that run after ``Session.sql``
returned carry it too). Readers take self time from ``parent``
(:func:`nds_tpu.obs.export.rollup`), never from interval containment.

Every live span is also a ``jax.profiler.TraceAnnotation`` named
``nds:<span name>`` (stats ``sid`` / ``parent`` / ``qid``): with a profile being
taken the program's tree lies in the host plane on the device planes'
clock; with none a ``TraceMe`` is a flag test.

Scoping mirrors :class:`nds_tpu.listener.Manager`: records land in the
ring of the thread that produced them (concurrent Throughput streams each
drain only their own), and a span finished on a thread that never
attached a ring (e.g. a shared device-runtime callback thread) lands in
the module-level :data:`unattributed` diagnostics deque instead of
leaking or cross-charging a stream.

Hazard guards:

* a span opened while ``ops.replay_mode() == "replay"`` is a no-op — the
  replay/stream compilers re-run planner code under ``jax.jit``, and a
  host clock read there would measure trace time, not run time (the
  ``span-in-jit`` lint rule enforces the static side of this);
* disabled tracing (``NDS_TPU_TRACE=off`` or :func:`set_enabled`) makes
  ``span()`` return a shared null context: no clock reads at all.
"""

from __future__ import annotations

import functools
import itertools
import os
import threading
import time
from collections import deque

import jax
from jax.profiler import TraceAnnotation

# ring capacity per thread: diagnostics, never unbounded. A >HBM scan
# emits ~6 records per chunk (dispatch, stall, three ring-worker stages),
# so the default keeps a full per-query pipeline of ~1300 chunks; drivers
# drain per query. Read at ring-ATTACH
# time (not import): a Throughput child that sets NDS_TPU_TRACE_RING
# after import sizes its threads' rings from the live value.
def _ring_max() -> int:
    return int(os.environ.get("NDS_TPU_TRACE_RING", "8192"))

# NDS_TPU_TRACE is only the import DEFAULT of this runtime flag;
# set_enabled() is the post-import control path, so the conc-audit
# env-freeze rule is waived on the next line.
# nds-lint: ignore[env-freeze]
_enabled = os.environ.get("NDS_TPU_TRACE", "on").lower() not in (
    "off", "0", "false")

_tls = threading.local()

# per-process ids: span/sync-site ids and statement ids (next() on an
# itertools.count is atomic under the GIL, so threads never share one)
_sids = itertools.count(1)
_qids = itertools.count(1)

# the one vocabulary: span "op.join", annotation "nds:op.join", device
# scope "nds.join"
ANNOTATION_PREFIX = "nds:"
SCOPE_PREFIX = "nds."
# the root span of a statement's tree (Session.sql): entering it draws
# the statement id everything under it inherits
STATEMENT = "statement"

# spans/sync events from threads with no attached ring (mirrors
# Manager.unattributed: never fanned into another stream's drain)
unattributed: deque = deque(maxlen=1000)

_E = None


def _ops():
    """Late-bound engine.ops (ops imports this module at its top, so the
    reverse import must happen after both modules exist)."""
    global _E
    if _E is None:
        from nds_tpu.engine import ops
        _E = ops
    return _E


def on() -> bool:
    """Is tracing live for new spans/sync events?"""
    return _enabled


def set_enabled(value: bool) -> None:
    """Process-wide switch (tests; ``NDS_TPU_TRACE=off`` sets the import
    default). Open spans finish normally either way."""
    global _enabled
    _enabled = bool(value)


def attach() -> None:
    """Give the calling thread its own span ring (idempotent). Called by
    ``Session.sql`` so every query-executing thread is scoped; a record
    finished on a never-attached thread goes to :data:`unattributed`."""
    if getattr(_tls, "ring", None) is None:
        _tls.ring = deque(maxlen=_ring_max())


def drain_spans() -> list:
    """Return and clear the calling thread's trace records (spans and
    sync-site events, completion order). Attaches the thread."""
    attach()
    out = list(_tls.ring)
    _tls.ring.clear()
    return out


def _emit(rec) -> None:
    ring = getattr(_tls, "ring", None)
    if ring is None:
        unattributed.append(rec)
    else:
        ring.append(rec)


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def attributed() -> tuple:
    """(syncs, wait_ns) already attributed to sync-site events on this
    thread — ``ops.host_read`` subtracts these so a fetch that re-enters
    ``host_read`` (nested reads) charges each site exactly once."""
    return (getattr(_tls, "attr_syncs", 0), getattr(_tls, "attr_wait", 0))


class SyncSite:
    """One host_read fetch that charged host syncs: the first-class form
    of tools/sync_profile.py's call-site attribution."""

    __slots__ = ("tag", "site", "syncs", "wait_ns", "ts_ns", "sid",
                 "parent", "qid")

    def __init__(self, tag, site, syncs, wait_ns, ts_ns, parent, qid):
        self.tag = tag            # host_read tag ("sync", "counts3", ...)
        self.site = site          # "file.py:lineno:function" above ops.py
        self.syncs = syncs
        self.wait_ns = wait_ns
        self.ts_ns = ts_ns
        self.sid = next(_sids)
        self.parent = parent      # sid of the innermost open span
        self.qid = qid

    def __repr__(self):
        return (f"SyncSite({self.tag!r}, {self.site!r}, "
                f"syncs={self.syncs})")


def note_sync(tag: str, syncs: int, wait_ns: int, site: str) -> None:
    """Record one sync-charging host read (called from ``ops.host_read``
    only when ``syncs`` not already attributed by a nested read)."""
    _tls.attr_syncs = getattr(_tls, "attr_syncs", 0) + syncs
    _tls.attr_wait = getattr(_tls, "attr_wait", 0) + wait_ns
    st = _stack()
    top = st[-1] if st else None
    _emit(SyncSite(tag, site, syncs, wait_ns, time.perf_counter_ns(),
                   top.sid if top else None, top.qid if top else None))


class SpanRecord:
    """One finished span. ``syncs``/``sync_wait_ns``/``compile_ns`` are
    deltas of the thread's existing ops counters over the span (children
    included — it is a tree, readers subtract for self-time)."""

    __slots__ = ("name", "attrs", "ts_ns", "dur_ns", "syncs",
                 "sync_wait_ns", "compile_ns", "sid", "parent", "qid",
                 "thread", "dropped", "_s0", "_w0", "_c0", "_note")

    def __init__(self, name: str, attrs: dict, qid=None):
        self.name = name
        self.attrs = attrs
        self.ts_ns = 0
        self.dur_ns = 0
        self.syncs = 0
        self.sync_wait_ns = 0
        self.compile_ns = 0
        self.sid = next(_sids)
        self.parent = None        # sid of the innermost open span at enter
        self.qid = qid            # statement id (explicit, else inherited)
        self.thread = "driver"    # "worker": re-recorded ring-worker stage
        self.dropped = False

    def set(self, **kw) -> None:
        """Attach counters/labels mid-span (chunks=…, cache="hit", …)."""
        self.attrs.update(kw)

    def drop(self) -> None:
        """Discard this span: it still unwinds normally at ``__exit__``
        but is never emitted. For spans whose subject turns out not to
        exist — e.g. the drive loop's ``stream.prefetch`` stall span
        when the ring reports end-of-stream: there was no chunk, so
        there must be no span record for one."""
        self.dropped = True

    def __enter__(self) -> "SpanRecord":
        E = _ops()
        st = _stack()
        if st:
            self.parent = st[-1].sid
            if self.qid is None:
                self.qid = st[-1].qid
        if self.name == STATEMENT:
            # the root of one statement's tree: a new statement id, also
            # given to driver spans already open around the call (power's
            # "query") so the whole drain groups under one id
            self.qid = next(_qids)
            for outer in st:
                if outer.qid is None:
                    outer.qid = self.qid
        st.append(self)
        self._note = TraceAnnotation(ANNOTATION_PREFIX + self.name,
                                     sid=self.sid, parent=self.parent or 0,
                                     qid=self.qid or 0)
        self._note.__enter__()
        self._s0 = E.sync_count()
        self._w0 = E.sync_wait_ns()
        self._c0 = E.compile_ns()
        self.ts_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.dur_ns = time.perf_counter_ns() - self.ts_ns
        self._note.__exit__(None, None, None)
        E = _ops()
        self.syncs = E.sync_count() - self._s0
        self.sync_wait_ns = E.sync_wait_ns() - self._w0
        self.compile_ns = E.compile_ns() - self._c0
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        elif self in st:                  # defensive: mis-nested exits
            st.remove(self)
        if not self.dropped:
            _emit(self)
        return False

    def __repr__(self):
        return (f"SpanRecord({self.name!r}, {self.dur_ns / 1e6:.3f}ms, "
                f"syncs={self.syncs}, attrs={self.attrs})")


class _NullSpan:
    """Shared no-op span: returned when tracing is off or the caller is
    inside a replay re-trace (host clock reads under jit tracing measure
    compile time, not run time)."""

    __slots__ = ()
    qid = None

    def set(self, **kw) -> None:
        pass

    def drop(self) -> None:
        pass

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


NULL_SPAN = _NullSpan()


def span(name: str, *, qid=None, **attrs):
    """Open a nestable span on the calling thread. Usage::

        with obs.span("stream.drive", chunk=i) as sp:
            ...
            sp.set(rows=n)

    ``qid`` names the statement for a span opened outside its tree (the
    ``Result`` of a returned ``Session.sql``); inside one it is inherited.

    Zero host syncs by construction: enter/exit read the host clock and
    the thread's existing sync/wait/compile counters, nothing else."""
    if not _enabled or _ops().replay_mode() == "replay":
        return NULL_SPAN
    return SpanRecord(name, attrs, qid)


def op(primitive: str, **attrs):
    """The engine-primitive boundary, for the Python wrappers of
    ``engine/ops.py`` / ``engine/window.py`` and the planner's top-level
    expression evaluation: span ``op.<primitive>`` when the wrapper runs
    eagerly; under a replay re-trace (where a span is a no-op) the
    device scope ``nds.<primitive>`` instead, so the eager operations a
    wrapper issues between its jitted bodies carry the primitive's name
    inside the replayed / chunk program too. Never inside a jitted body
    (``span-in-jit``)."""
    if _ops().replay_mode() == "replay":
        return jax.named_scope(SCOPE_PREFIX + primitive)
    return span("op." + primitive, **attrs)


def traced(primitive: str, **attrs):
    """Decorator form of :func:`op` for a wrapper that is one primitive
    from its first line to its last."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*a, **k):
            with op(primitive, **attrs):
                return fn(*a, **k)
        return wrapper
    return deco


def scoped(primitive: str):
    """Decorator for the BODY of a jitted implementation (placed under
    the ``jax.jit`` decorator): the body runs under the device scope
    ``nds.<primitive>`` (``jax.named_scope``), so every operation it
    stages carries the name wherever the function is traced — its own
    program when called eagerly, or inlined into a replayed / chunk
    program. Trace-time only: it names operations (``op_name``
    metadata) and adds none."""
    def deco(fn):
        @functools.wraps(fn)
        def body(*a, **k):
            with jax.named_scope(SCOPE_PREFIX + primitive):
                return fn(*a, **k)
        return body
    return deco


def annotation(name: str, parent=None, qid=None):
    """Profiler-clock annotation ``nds:<name>`` alone: no ring record, no
    counter read. For code that may not open a span — the prefetch
    ring's worker thread (its stages come back as records through
    :func:`record_interval`; it names the scan's ``stream`` span as
    ``parent``) and the blocking fetch of ``host_read`` (child of the
    innermost span open on the thread)."""
    if not _enabled:
        return NULL_SPAN
    if parent is None:
        st = getattr(_tls, "stack", None)
        if st:
            parent, qid = st[-1].sid, st[-1].qid
    return TraceAnnotation(ANNOTATION_PREFIX + name, parent=parent or 0,
                           qid=qid or 0)


def enclosing(name: str):
    """``(sid, qid)`` of the innermost OPEN span called ``name`` on this
    thread, else of the innermost open span, else ``(None, None)``."""
    st = getattr(_tls, "stack", None)
    if not st:
        return None, None
    for s in reversed(st):
        if s.name == name:
            return s.sid, s.qid
    return st[-1].sid, st[-1].qid


def record_interval(name: str, ts_ns: int, dur_ns: int, parent=None,
                    qid=None, **attrs) -> None:
    """Record a finished interval another thread timed (a prefetch-ring
    worker stage) into the CALLING thread's ring, under ``parent``: the
    ``_drain_worker_faults`` pattern for spans. Marked
    ``thread="worker"``: it ran beside the driver, so readers count it
    in its own phase and never take it out of its parent's self time."""
    if not _enabled:
        return
    rec = SpanRecord(name, attrs, qid)
    rec.parent = parent
    rec.thread = "worker"
    rec.ts_ns, rec.dur_ns = ts_ns, dur_ns
    _emit(rec)


def annotate(**attrs) -> None:
    """Set attributes on the innermost OPEN span of the calling thread
    (no-op when tracing is off or no span is open) — lets a callee deep
    in the engine label the phase span its caller opened (e.g. the
    streaming executor stamping cache hit/miss on the planner's
    ``stream`` span). Same replay guard as :func:`span`: under a replay
    re-trace the caller's own span was a null context, so the innermost
    open span would be an OUTER compile-phase span — annotating it would
    stamp another scan's attrs onto it at jit-trace time."""
    if not _enabled or _ops().replay_mode() == "replay":
        return
    st = getattr(_tls, "stack", None)
    if st:
        st[-1].attrs.update(attrs)


# one program build (trace, lowering, backend compile or cache read). Kept
# at the end of the file: the lines above sit in the call stack of every
# scoped body, and a Mosaic program is keyed by where those lines are.
COMPILE = "compile"


def note_compile(ts_ns: int, dur_ns: int, compile_ns: int, **attrs) -> None:
    """Record one program build as a finished ``compile`` span of the
    calling thread (called by :mod:`nds_tpu.obs.compiles` when JAX closes
    the build: its time is known only once it is over, like a sync
    site's): a driver-thread child of the innermost open span, cut to
    start inside it, so the parent's self time no longer holds the build.
    ``compile_ns`` is the build's share of ``ops.compile_ns()``."""
    if not _enabled:
        return
    st = getattr(_tls, "stack", None)
    top = st[-1] if st else None
    rec = SpanRecord(COMPILE, attrs, top.qid if top else None)
    if top is not None:
        rec.parent = top.sid
        end_ns = ts_ns + dur_ns
        ts_ns = min(max(ts_ns, top.ts_ns), end_ns)
        dur_ns = end_ns - ts_ns
    rec.ts_ns, rec.dur_ns, rec.compile_ns = ts_ns, dur_ns, compile_ns
    _emit(rec)
