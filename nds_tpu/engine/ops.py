# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Structural relational operators over DeviceTable.

The TPU-native analogs of the physical operators the reference delegates to
Spark+RAPIDS (Parquet scan, filter/project, hash join, hash aggregate, sort,
window; SURVEY.md §2.2 N4). All grouping and joining is sort-based on device:
lexsort + run boundaries + segment reductions — collision-free and
XLA-friendly (fixed dtypes, gathers, segment ops), with searchsorted probes
for the join build/probe phases.

Shape discipline: every materialization pads its row count up to a
power-of-two bucket (:func:`bucket_len`), with valid rows in a prefix
(``DeviceTable.nrows`` logical rows out of ``plen`` physical). Data past the
logical count is garbage that every operator ignores: joins hash pad rows to
unmatchable sentinels, grouping gives them a discardable trailing group, and
sorts order them last. XLA sees a handful of distinct shapes instead of one
per intermediate cardinality, so compiled executables are reused across
queries and across Power Runs via the persistent compilation cache — the
compile-once-run-many analog of the reference's warmed JVM+plugin
(ref: nds/nds_power.py:125-135, SURVEY.md §6 hard parts: bucketed padding).
"""

from __future__ import annotations

import functools
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from nds_tpu.engine import faults as _faults
from nds_tpu.engine.column import Column, encs_equal, is_dec
from nds_tpu.engine.table import DeviceTable
from nds_tpu.obs import trace as _trace

# ---------------------------------------------------------------------------
# bucketed shapes
# ---------------------------------------------------------------------------

# Floor of every physical bucket. Meshes shard buckets row-wise, so a mesh
# wider than the floor needs it raised (NDS_TPU_MIN_BUCKET) at process
# start — it is a process-wide shape contract, never mutated at run time.
# Rounded up to a power of two so every bucket divides any power-of-two
# mesh up to the floor.
def _pow2_ceil(n: int) -> int:
    return 1 << max(int(n) - 1, 1).bit_length() if n > 2 else 2


# deliberate import freeze: the bucket floor is a process-wide shape
# contract (session.py refuses mid-process changes by construction), so
# the conc-audit freeze rule is waived on the next line.
# nds-lint: ignore[env-freeze]
_MIN_BUCKET = _pow2_ceil(int(os.environ.get("NDS_TPU_MIN_BUCKET", "16")))


def bucket_len(n: int) -> int:
    """Smallest power-of-two capacity >= n (floor ``_MIN_BUCKET``)."""
    if n <= _MIN_BUCKET:
        return _MIN_BUCKET
    return 1 << (int(n) - 1).bit_length()


# host-sync accounting: every device->host scalar read blocks the dispatch
# queue (and under GSPMD is a full-mesh barrier through the host), so the
# count per query is THE scalability number to watch (DESIGN.md). Read
# around a query by the drivers. Thread-local, matching the thread-scoped
# listener: concurrent Throughput streams each count their own syncs.
_sync_tls = threading.local()


def add_syncs(n: int = 1) -> None:
    """Charge ``n`` host syncs to the calling thread's stream."""
    _sync_tls.count = getattr(_sync_tls, "count", 0) + n


def sync_count() -> int:
    """Host syncs counted on the calling thread so far."""
    return getattr(_sync_tls, "count", 0)


def add_sync_wait(ns: int) -> None:
    """Charge nanoseconds spent BLOCKED on a device->host read (sync
    stalls + result fetches) to the calling thread — the host side of the
    roofline decomposition (everything else in a query's wall time is
    dispatch + device compute overlap)."""
    _sync_tls.wait_ns = getattr(_sync_tls, "wait_ns", 0) + ns


def sync_wait_ns() -> int:
    return getattr(_sync_tls, "wait_ns", 0)


def add_fetch_bytes(n: int) -> None:
    """Record device->host result bytes (collect()/to_arrow transfers)."""
    _sync_tls.fetch_bytes = getattr(_sync_tls, "fetch_bytes", 0) + n


def fetch_bytes() -> int:
    return getattr(_sync_tls, "fetch_bytes", 0)


# compile-time accounting: XLA compilation is the dominant first-sight cost
# at scale (SF1 Power: 70% of the official wall was shape-universe compile)
# and the reports must split it from execution to be optimizable. JAX's
# monitoring stream reports every part of a program build (trace, lowering,
# persistent-cache hit or miss, backend compile) synchronously on the
# compiling thread: nds_tpu/obs/compiles.py makes one record a build from
# them, with its pending parts and per-thread sums in _sync_tls, so it
# composes with concurrent Throughput streams like the sync counters above.
_compile_meter_on = False


def enable_compile_meter() -> None:
    """Register the compile meter's two listeners (idempotent)."""
    global _compile_meter_on
    if _compile_meter_on:
        return
    from jax import monitoring
    from nds_tpu.obs import compiles
    on_event, on_duration = compiles.listeners(_sync_tls)
    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)
    _compile_meter_on = True


def compile_ns() -> int:
    """Nanoseconds the calling thread spent in JAX's backend-compile step:
    XLA compiles AND persistent-cache reads (obs.compiles splits them)."""
    return getattr(_sync_tls, "compile_ns", 0)


# --------------------------------------------------------------------------
# trace-replay: every host read the engine performs routes through
# host_read(), so a query can be RECORDED once (eager run, log of host
# decisions) and then RE-TRACED under jax.jit with the log answering every
# host read — compiling the entire query pipeline into ONE XLA program
# (the Spark whole-stage-codegen analog; engine/replay.py drives this).
# --------------------------------------------------------------------------


class ReplayMismatch(RuntimeError):
    """The replay trace consumed host reads in a different order than the
    recording — the query is not replay-safe; callers fall back eager."""


# --------------------------------------------------------------------------
# stream-bounds mode: the compiled streaming executor (engine/stream.py)
# traces ONE per-chunk program and replays it for every chunk of a >HBM
# ChunkedTable, so the program must be CHUNK-INVARIANT — no host decision
# may depend on a chunk's data. Inside a stream-bounds region:
#   * host scalar syncs raise StreamSyncError (the executor falls back to
#     the eager chunk loop — correctness never depends on streamability);
#   * joins size their pair buckets from STATIC bounds instead of a
#     data-dependent sizing sync, registering a device-side overflow
#     predicate via stream_overflow() that the executor checks once at the
#     pipeline's single materializing sync (overflow => rerun eager);
#   * lazy compaction never takes the adaptive resolve (counts stay on
#     device for the pipeline's whole life).
# Host reads against NON-streamed inputs (dimension key maps/ranges) stay
# legal: they are chunk-invariant and ride the replay log.
# --------------------------------------------------------------------------


class StreamSyncError(RuntimeError):
    """A chunk-data-dependent host sync was reached inside a stream-bounds
    region; the query's join graph is not streamable through the compiled
    chunk pipeline."""


def stream_bounds_on() -> bool:
    return getattr(_sync_tls, "stream_bounds", False)


class _StreamBoundsSession:
    def __enter__(self):
        self._prev = (stream_bounds_on(),
                      getattr(_sync_tls, "stream_flags", None))
        _sync_tls.stream_bounds = True
        self.flags: list = []
        _sync_tls.stream_flags = self.flags
        return self

    def __exit__(self, *exc):
        _sync_tls.stream_bounds, _sync_tls.stream_flags = self._prev


def stream_bounds():
    """Context: execute with chunk-invariant (bound-derived) shape
    decisions; ``.flags`` collects the device-side overflow predicates the
    region registered."""
    return _StreamBoundsSession()


def stream_overflow(pred) -> None:
    """Register a device bool scalar that is True when a bound-sized
    bucket overflowed (rows silently dropped). The streaming executor ORs
    every flag into its accumulated overflow bit; outside a stream-bounds
    region this is a no-op."""
    flags = getattr(_sync_tls, "stream_flags", None)
    if flags is not None:
        flags.append(pred)


class _OuterMatchCollector:
    """Collects the per-dispatch matched-build-row masks an outer-build
    join registers (multi-pass streaming, engine/stream.py): the streamed
    pipeline ORs them into a device-resident unmatched-key accumulator so
    the outer-extras rows can be emitted once, at materialize time."""

    def __enter__(self):
        self._prev = getattr(_sync_tls, "stream_outer", None)
        self.masks: list = []
        _sync_tls.stream_outer = self.masks
        return self

    def __exit__(self, *exc):
        _sync_tls.stream_outer = self._prev


def outer_match_collector():
    return _OuterMatchCollector()


def stream_outer_matched(mask) -> None:
    """Register the device bool vector of build-side rows the current
    outer-build join dispatch matched. No-op outside a collector region
    (plain device-resident outer joins resolve their extras inline)."""
    lst = getattr(_sync_tls, "stream_outer", None)
    if lst is not None:
        lst.append(mask)


class _SuspendStreamRecord:
    """Escape hatch for CHUNK-INVARIANT inner plans reached from inside a
    streamed pipeline's record phase (subquery residuals): restores plain
    eager execution — replay log detached (inner host reads must never
    interleave with the outer recording, which the trace would then fail
    to consume), stream-bounds off (the inner plan may sync freely; it
    runs ONCE, not per chunk), and a FRESH pending-count/check list so the
    inner's batched resolutions never drain counts the outer record phase
    still owes its log."""

    def __enter__(self):
        t = _sync_tls
        self._saved = (
            replay_mode(), getattr(t, "replay_log", None),
            getattr(t, "replay_cursor", 0),
            getattr(t, "replay_operands", None),
            stream_bounds_on(), getattr(t, "stream_flags", None),
            getattr(t, "stream_outer", None),
            getattr(t, "pending", None), getattr(t, "checks", None))
        t.replay_mode = "off"
        t.replay_log = None
        t.replay_cursor = 0
        t.replay_operands = None
        t.stream_bounds = False
        t.stream_flags = None
        t.stream_outer = None
        t.pending = []
        t.checks = []
        return self

    def __exit__(self, *exc):
        t = _sync_tls
        (t.replay_mode, t.replay_log, t.replay_cursor, t.replay_operands,
         t.stream_bounds, t.stream_flags, t.stream_outer,
         t.pending, t.checks) = self._saved


def suspend_stream_record():
    return _SuspendStreamRecord()


def guarded_scalar_read(tag: str, dev_scalar) -> int:
    """Mechanism for CHUNK-DERIVED host scalars inside the streamed
    pipeline (the `chunk-dependent-host-read` conversion): outside a
    stream-bounds region this is an ordinary counted host read. Inside
    one, the value read on the FIRST chunk is recorded and replayed for
    every later chunk — with a device-side STALENESS GUARD registered on
    the overflow channel, so any chunk for which the recorded value's
    validity predicate fails (the live value differs) flips the pipeline's
    overflow flag and the statement re-runs eagerly, bit-for-bit. The
    guard is what makes replaying a recorded scalar SOUND rather than
    hopeful."""
    import jax.numpy as _jnp

    def fetch():
        add_syncs()
        t0 = time.perf_counter_ns()
        out = int(jax.device_get(dev_scalar))
        add_sync_wait(time.perf_counter_ns() - t0)
        return out

    if not stream_bounds_on():
        return host_read(tag, fetch)
    # replay serves the recorded value without touching fetch; record
    # fetches (one counted sync, first chunk only) and logs it
    val = host_read(tag, fetch)
    stream_overflow(_jnp.asarray(dev_scalar) != val)
    return val


def replay_mode() -> str:
    return getattr(_sync_tls, "replay_mode", "off")


class _ReplaySession:
    def __init__(self, mode: str, log, operands=None):
        self.mode, self.log = mode, log
        self.operands = operands

    def __enter__(self):
        self._prev = (replay_mode(), getattr(_sync_tls, "replay_log", None),
                      getattr(_sync_tls, "replay_cursor", 0))
        # snapshot ENTRIES (not an index): resolve_counts clears the list
        # mid-trace, so positions shift — restoration must be by identity
        self._pend_snapshot = list(_pending_counts())
        self._prev_ops = getattr(_sync_tls, "replay_operands", None)
        _sync_tls.replay_mode = self.mode
        _sync_tls.replay_log = self.log
        _sync_tls.replay_cursor = 0
        _sync_tls.replay_operands = self.operands
        return self.log

    def __exit__(self, *exc):
        if self.mode == "replay":
            # counts created while TRACING hold tracer scalars; they must
            # never reach a later eager device_get — keep only the entries
            # that already existed when the trace began. Same for deferred
            # checks registered against tracer counts: left in place they
            # could never resolve and would force a spurious resolve at
            # every later statement's flush.
            lst = _pending_counts()
            keep = [c for c in lst
                    if any(c is s for s in self._pend_snapshot)]
            lst[:] = keep
            checks = getattr(_sync_tls, "checks", None)
            if checks:
                _sync_tls.checks = [
                    (c, f) for c, f in checks
                    if any(c is s for s in self._pend_snapshot)
                    or c._host is not None]
        (_sync_tls.replay_mode, _sync_tls.replay_log,
         _sync_tls.replay_cursor) = self._prev
        _sync_tls.replay_operands = self._prev_ops


def recording(log=None):
    """Context: run eagerly while logging every host read."""
    return _ReplaySession("record", [] if log is None else log)


def replaying(log, operands=None):
    """Context: serve every host read from ``log`` (device untouched);
    ``operands`` resolves any lifted :class:`ArgRef` entries to traced
    jit arguments."""
    return _ReplaySession("replay", log, operands)


class ArgRef:
    """Placeholder in a replay log for a large array lifted into a jit
    ARGUMENT (baking fact-sized host reads as jaxpr constants bloats the
    compiled program; see replay.py). The replaying context resolves it to
    the corresponding traced operand."""

    __slots__ = ("index",)

    def __init__(self, index: int):
        self.index = index


def _resolve_refs(val):
    ops = getattr(_sync_tls, "replay_operands", None)
    if isinstance(val, ArgRef):
        return ops[val.index]
    if isinstance(val, tuple) and any(isinstance(x, ArgRef) for x in val):
        return tuple(ops[x.index] if isinstance(x, ArgRef) else x
                     for x in val)
    return val


# frames the sync-site walk passes through: this module and the obs
# layer's op()/traced() wrappers around its primitives
_SYNC_SITE_SKIP = ("ops.py", os.path.join("obs", "trace.py"))


def _sync_site() -> str:
    """First non-ops engine frame above the fetch — the call-site tag
    every sync-charging host read carries into the trace layer (the
    first-class form of tools/sync_profile.py's old monkeypatch). Frame
    walk only, no source reads; runs only when a sync was charged."""
    f = sys._getframe(1)
    while f is not None:
        fn = f.f_code.co_filename
        if "nds_tpu" in fn and not fn.endswith(_SYNC_SITE_SKIP):
            return (f"{os.path.basename(fn)}:{f.f_lineno}:"
                    f"{f.f_code.co_name}")
        f = f.f_back
    return "?"


def host_read(tag: str, fetch):
    """The single host-read chokepoint. Off: just fetch. Record: fetch and
    log. Replay: pop the recorded value — no device contact (large arrays
    come back as traced jit operands via :class:`ArgRef`).

    With tracing on (nds_tpu/obs), a fetch that charged host syncs emits
    a sync-site event naming its engine call site. Attribution is
    re-entrancy-exact: a fetch that re-enters host_read (nested reads —
    e.g. a count fallback inside a span fetch) charges each site only its
    OWN syncs, which the old monkeypatch double-counted. Pure counter
    arithmetic — zero additional syncs."""
    mode = replay_mode()
    if mode == "replay":
        log = _sync_tls.replay_log
        i = _sync_tls.replay_cursor
        if i >= len(log) or log[i][0] != tag:
            got = log[i][0] if i < len(log) else "<end>"
            raise ReplayMismatch(f"expected {got!r}, hit {tag!r} at {i}")
        _sync_tls.replay_cursor = i + 1
        return _resolve_refs(log[i][1])
    if not _trace.on():
        val = fetch()
        if mode == "record":
            _sync_tls.replay_log.append((tag, val))
        return val
    s0, w0 = sync_count(), sync_wait_ns()
    a_s0, a_w0 = _trace.attributed()
    # on the profiler's clock too: an idle chip during this interval is
    # the host waiting on (or about to ask for) this read
    with _trace.annotation("sync:" + tag):
        val = fetch()
    a_s1, a_w1 = _trace.attributed()
    own = (sync_count() - s0) - (a_s1 - a_s0)
    if own > 0:
        own_wait = max((sync_wait_ns() - w0) - (a_w1 - a_w0), 0)
        _trace.note_sync(tag, own, own_wait, _sync_site())
    if mode == "record":
        _sync_tls.replay_log.append((tag, val))
    return val


def _guarded_blocking_fetch(tag: str, fetch):
    """The ``sync`` fault seam around one blocking device->host fetch:
    bounded deterministic retry of the idempotent read (transient
    device flakes and injected faults recover in place — the
    retry RE-CHARGES the same sync accounting, never re-budgets it:
    exec_audit's retry-paths row), and the statement watchdog
    (``NDS_TPU_STATEMENT_DEADLINE_S``): a hung fetch raises a classified
    :class:`faults.StatementTimeout` instead of hanging the process.
    Watchdog unset (the default): the fetch runs inline — zero threads,
    bit-for-bit today's path."""
    return _faults.with_retry(
        "sync",
        lambda: _faults.bounded_call(
            "sync",
            lambda: (_faults.fault_point("sync", tag), fetch())[1]))


def timed_read(tag: str, fetch):
    """host_read() with the fetch charged to the thread's sync/wait
    accounting — for blocking device->host reads that are not simple
    scalar syncs (chunk spans, exchange overflow counters, whole-column
    string/date fetches), so PERF.md's roofline sees them too. The raw
    fetch runs behind the ``sync`` fault seam (retry + watchdog); the
    sync counters stay charged on the CALLING thread either way."""

    def timed():
        add_syncs()
        t0 = time.perf_counter_ns()
        out = _guarded_blocking_fetch(tag, fetch)
        add_sync_wait(time.perf_counter_ns() - t0)
        return out

    return host_read(tag, timed)


def host_sync(value) -> int:
    """Read a device scalar on host, counting the sync."""
    if stream_bounds_on():
        raise StreamSyncError(
            "host scalar sync inside a stream-bounds region")

    def fetch():
        add_syncs()
        t0 = time.perf_counter_ns()
        out = _guarded_blocking_fetch("sync", lambda: int(value))
        add_sync_wait(time.perf_counter_ns() - t0)
        return out

    return host_read("sync", fetch)


class DeviceCount:
    """A logical row count that stays on device (DESIGN.md reduction items
    1+3: no-shrink capacity propagation with batched sync points).

    Operators that merely need the count inside a traced computation
    (liveness masks, hash-pad thresholds, segment routing) consume ``dev``
    and never block. ``bound`` is the static upper bound — a filter or
    inner join can never grow its input, so the producer's bucket is a
    valid capacity for every consumer — used for all physical-shape
    choices. Only a consumer that truly needs the host integer (ORDER
    BY+LIMIT output, scalar subqueries, ``collect()``) resolves, and
    resolution drains EVERY pending count of the calling thread in one
    transfer: a join that would have cost three round trips (pairs + two
    outer-extra counts) costs one.
    """

    __slots__ = ("dev", "bound", "_host")

    def __init__(self, dev, bound: int):
        self.dev = dev
        self.bound = int(bound)
        self._host: int | None = None
        _pending_counts().append(self)

    def to_int(self) -> int:
        if self._host is None and stream_bounds_on():
            # a chunk-data-dependent count must never reach host inside
            # the compiled per-chunk program (engine/stream.py)
            raise StreamSyncError(
                "DeviceCount resolution inside a stream-bounds region")
        if self._host is None:
            resolve_counts()
        if self._host is None:
            # not in the calling thread's pending list (created on another
            # stream's thread) or an earlier drain failed mid-transfer:
            # fetch directly rather than returning a poisoned None
            def fetch():
                add_syncs()
                t0 = time.perf_counter_ns()
                out = int(jax.device_get(self.dev))
                add_sync_wait(time.perf_counter_ns() - t0)
                return out

            self._host = host_read("count1", fetch)
            _run_deferred_checks()
        return self._host

    def __repr__(self):
        state = self._host if self._host is not None else "?"
        return f"DeviceCount({state}/{self.bound})"

    # implicit coercions raise so every host consumer is an EXPLICIT,
    # counted choice between count_int (syncs, batched) and count_bound
    # (free): a silent int() here would be an uncounted round trip
    def _no_host(self, *_a, **_k):
        raise TypeError(
            "DeviceCount is not a host value; use ops.count_int (syncs, "
            "batched) or ops.count_bound (free upper bound)")

    __bool__ = __index__ = __int__ = __eq__ = __lt__ = __le__ = __gt__ = \
        __ge__ = __add__ = __radd__ = __mul__ = __rmul__ = _no_host
    __hash__ = None


def _pending_counts() -> list:
    lst = getattr(_sync_tls, "pending", None)
    if lst is None:
        lst = _sync_tls.pending = []
    return lst


def resolve_counts() -> None:
    """Fetch every pending device count of this thread in ONE transfer
    (counted as one host sync — the batching is the point)."""
    lst = _pending_counts()
    pend = [c for c in lst if c._host is None]
    if not pend:
        lst.clear()
        _run_deferred_checks()   # checks on already-resolved counts
        return

    def fetch():
        t0 = time.perf_counter_ns()
        # on a failed transfer (device preemption) the list survives
        # untouched, so a retry drains it instead of stranding counts —
        # the ``sync`` fault seam (bounded retry + statement watchdog)
        # wraps the raw transfer, accounting stays on this thread
        vals = _guarded_blocking_fetch(
            "counts", lambda: jax.device_get([c.dev for c in pend]))
        add_sync_wait(time.perf_counter_ns() - t0)
        add_syncs()
        return [int(v) for v in vals]

    vals = host_read(f"counts{len(pend)}", fetch)
    for c, v in zip(pend, vals):
        c._host = v
    lst.clear()
    _run_deferred_checks()


def defer_check(count: DeviceCount, fn) -> None:
    """Register a validation against a count's eventual host value; it
    runs at whichever batched resolution produces the value. Keeps SQL
    runtime-error semantics (e.g. 'scalar subquery returned more than one
    row') without spending a dedicated sync on the check."""
    lst = getattr(_sync_tls, "checks", None)
    if lst is None:
        lst = _sync_tls.checks = []
    lst.append((count, fn))


def _run_deferred_checks() -> None:
    lst = getattr(_sync_tls, "checks", None)
    if not lst:
        return
    ready = [(c, f) for c, f in lst if c._host is not None]
    _sync_tls.checks = [(c, f) for c, f in lst if c._host is None]
    first_err = None
    for c, f in ready:          # every ready check runs even if one raises
        try:
            f(c._host)
        except Exception as e:
            first_err = first_err or e
    if first_err is not None:
        raise first_err


def flush_deferred_checks() -> None:
    """Statement-end barrier: resolve any counts that deferred checks are
    waiting on so SQL runtime errors surface inside the statement that
    caused them, never attributed to a later one."""
    if getattr(_sync_tls, "checks", None):
        resolve_counts()


def discard_deferred_checks() -> None:
    """Drop pending deferred checks — called when a statement aborts
    with its own exception, so its half-registered checks neither mask
    the real error nor leak into the next statement."""
    _sync_tls.checks = []


def count_int(n) -> int:
    """Host integer of a count (resolves a DeviceCount, batched)."""
    return n.to_int() if isinstance(n, DeviceCount) else int(n)


def count_bound(n) -> int:
    """Static upper bound of a count — valid for capacity decisions, free
    of any sync. Exact when already host-resolved."""
    if isinstance(n, DeviceCount):
        return n.bound if n._host is None else n._host
    return int(n)


def count_arr(n):
    """Traced-use form: the device scalar (or the plain int — both are
    valid jit arguments)."""
    return n.dev if isinstance(n, DeviceCount) else n


def live_mask(plen: int, nrows) -> jnp.ndarray:
    """Bool mask of the logical (non-pad) prefix of a physical array.
    ``nrows`` may be a host int or a :class:`DeviceCount` (no sync)."""
    return jnp.arange(plen) < count_arr(nrows)


def compact_indices(mask: jnp.ndarray, n: int) -> jnp.ndarray:
    """Indices of the first ``n`` True rows of ``mask``, padded to
    ``bucket_len(n)`` with an out-of-range fill (gathers clip, scatters
    drop)."""
    cap = bucket_len(n)
    plen = int(mask.shape[0])
    return jnp.nonzero(mask, size=cap, fill_value=max(plen, 1))[0]


# lazy-compaction bucket ceiling: below it, carrying the un-shrunk bucket
# is cheaper than a device->host round trip (the round trip flushes the
# dispatch queue and is a full-mesh barrier under GSPMD); above it, the
# resolve-and-slice pays for itself in downstream sort width.
# Read at USE time (not import) like stream_fanout(): setting
# NDS_TPU_LAZY_SHRINK_ROWS after import must not be silently ignored.
def lazy_shrink_rows() -> int:
    return int(os.environ.get("NDS_TPU_LAZY_SHRINK_ROWS", str(1 << 20)))


def count_first(bucket: int) -> bool:
    """Whether work at ``bucket`` rows waits for a count: past
    ``lazy_shrink_rows()`` and outside a stream-bounds region (a chunk
    program reads nothing). The one test of the count-first compaction,
    the narrowed join probe and the deferred dimension columns: each
    leaves fact-width work to run at the survivors' bucket."""
    return bucket > lazy_shrink_rows() and not stream_bounds_on()


@_trace.traced("compact")
def compact_table(table: DeviceTable, mask: jnp.ndarray,
                  shrink: bool = False) -> DeviceTable:
    """Keep rows where ``mask`` is true, as a prefix-padded table.

    Default (``shrink=False``, DESIGN.md item 1): NO host sync — live rows
    gather to the prefix of a bucket sized from the producer's bound (a
    filter never grows its input) and the logical count rides along as a
    :class:`DeviceCount`. Downstream joins/aggregations are pad-tolerant,
    so only an output-shaping consumer ever resolves it, batched.

    Past ``NDS_TPU_LAZY_SHRINK_ROWS`` (outside a stream-bounds region), and
    always with ``shrink=True`` (callers about to hold many compacted
    tables at once: load-time filters, chunk accumulation), the count is
    read FIRST — one batched host sync — and sizes the indices, so the row
    gather runs at the survivors' bucket and not at the producer's."""
    m = mask & live_mask(table.plen, table.nrows)
    cap = min(bucket_len(count_bound(table.nrows)), bucket_len(table.plen))
    bound = min(count_bound(table.nrows), cap)
    if shrink or count_first(cap):
        # adaptive: past this bucket size the gather here and the
        # downstream sorts/segment ops a fat bucket drags through cost more
        # than one (batched) round trip, so resolve now — the transfer
        # still drains the whole pending batch
        n = DeviceCount(jnp.sum(m), bound).to_int()
        return take_padded(table, compact_indices(m, n), n)
    idx = compact_indices(m, cap)         # cap is a bucket: its own width
    return take_padded(table, idx, DeviceCount(jnp.sum(m), bound))


def resolve_table(table: DeviceTable, shrink: bool = True) -> DeviceTable:
    """Resolve a table's lazy count to a host int (batched — one transfer
    drains every pending count of the thread) and, by default, slice the
    physical bucket down to the tight capacity. Lazy compaction kept live
    rows in the prefix, so shrinking is a metadata-cheap device slice."""
    n = table.nrows
    if not isinstance(n, DeviceCount):
        return table
    ni = n.to_int()
    cap = bucket_len(ni)
    if not shrink or cap >= table.plen:
        return DeviceTable(table.columns, ni, plen=table.plen)
    from nds_tpu.engine.column import slice_col_prefix
    cols = {nm: slice_col_prefix(c, cap) for nm, c in table.columns.items()}
    return DeviceTable(cols, ni, plen=cap)


@jax.jit
@_trace.scoped("gather")
def _gather_cols_impl(idx, datas, valids):
    """One fused gather of every column (and validity mask) of a table —
    a single device dispatch where a per-column loop costs 2 x ncols
    dispatches."""
    outs = tuple(jnp.take(d, idx, axis=0, mode="clip") for d in datas)
    vouts = tuple(None if v is None else jnp.take(v, idx, axis=0, mode="clip")
                  for v in valids)
    return outs, vouts


@jax.jit
@_trace.scoped("gather")
def _compose_impl(index, match, idx):
    """A deferred group's row index and match mask at the rows ``idx``
    keeps: ``take(take(src, index), idx) == take(src, take(index, idx))``
    element for element, pad slots included."""
    return (jnp.take(index, idx, axis=0, mode="clip"),
            None if match is None else jnp.take(match, idx, axis=0,
                                                mode="clip"))


@jax.jit
@_trace.scoped("gather")
def _null_extend_impl(valids, match):
    return tuple(match if v is None else v & match for v in valids)


def _gather_rows(table: DeviceTable, idx: jnp.ndarray, tally: list,
                 composed: bool = False) -> dict:
    """The columns of ``table`` at rows ``idx``, by name. ``tally`` counts
    the arrays gathered at ``idx``'s width (data, validity, and a deferred
    group's composed index and match mask) and, second, those of them that
    are columns reached through a composed index."""
    from dataclasses import replace as _replace
    gathered, groups = table.split()
    names = list(gathered)
    cols = list(gathered.values())
    datas = tuple(c.data for c in cols)
    valids = tuple(c.valid for c in cols)
    arrays = len(datas) + sum(v is not None for v in valids)
    tally[0] += arrays
    tally[1] += arrays * composed
    out = {}
    if cols or not groups:
        datas, valids = _gather_cols_impl(idx, datas, valids)
        out = {n: _replace(c, data=d, valid=v)
               for n, c, d, v in zip(names, cols, datas, valids)}
    for group, src in groups:
        # a deferred group: the source's rows through the composed index
        # (the source may hold deferred groups of its own: a snowflake)
        sub_idx, match = _compose_impl(group.index, group.match, idx)
        tally[0] += 1 + (match is not None)
        sub = _gather_rows(group.source.select(list(src.values())), sub_idx,
                           tally, True)
        if match is not None:                  # a LEFT join's misses: NULL
            ext = _null_extend_impl(tuple(c.valid for c in sub.values()),
                                    match)
            sub = {s: _replace(c, valid=v)
                   for (s, c), v in zip(sub.items(), ext)}
        out.update({n: sub[s] for n, s in src.items()})
    return {n: out[n] for n in table.column_names}


@_trace.traced("gather")
def gather_table_rows(table: DeviceTable, idx: jnp.ndarray, nrows: int,
                      deferred: bool = False) -> DeviceTable:
    """Fused whole-table row gather (clip mode); logical length ``nrows``.
    A deferred column group of ``table`` is gathered from its source through
    the composed index, so the result holds every column. ``deferred``:
    ``idx`` is itself a pair table's index (:func:`gather_deferred`)."""
    tally = [0, 0]
    cols = _gather_rows(table, idx, tally, deferred)
    # what the gather moves, from host-known shapes: index width x arrays
    _trace.annotate(cells=int(idx.shape[0]) * tally[0])
    if tally[1]:
        # columns' arrays that skipped the gather at their join's width
        _trace.annotate(deferredArrays=tally[1])
    return DeviceTable(cols, nrows, plen=int(idx.shape[0]))


def gather_deferred(group, names, nrows) -> dict:
    """Columns ``names`` of a deferred group at the group's own width: the
    gather its join would have made at once, misses of a LEFT join
    null-extended."""
    got = gather_table_rows(group.source.select(names), group.index,
                            nrows, group.pair).columns
    if group.match is None:
        return got
    # column by column, as the LEFT join always did: chunk programs trace
    # these operations and must stay byte-identical
    return {n: Column(c.kind, c.data, c.valid_mask() & group.match,
                      c.dict_values, c.enc) for n, c in got.items()}


def take_padded(table: DeviceTable, idx: jnp.ndarray, nrows: int) -> DeviceTable:
    """Gather rows by (possibly out-of-range padded) ``idx``; logical length
    ``nrows``. The physical length follows ``idx`` (already bucketed by the
    callers), including for column-less tables, so the plen floor survives
    compaction."""
    cap = int(idx.shape[0])
    if table.plen == 0:
        cols = {n: _null_column_like(c, cap)
                for n, c in table.columns.items()}
        return DeviceTable(cols, 0, plen=cap)
    if not table.column_names:
        return DeviceTable({}, nrows, plen=cap)
    return gather_table_rows(table, idx, nrows)


# ---------------------------------------------------------------------------
# sort-key preparation
# ---------------------------------------------------------------------------


# ONE dedicated lock for every _identity_cache-managed dict (_rank_cache,
# _merged_cache, _dense_dim_cache, _dim_span_cache, _union_cache, and
# exprs.py's dictionary memos): all of their mutations funnel through
# _identity_cache, so guarding the insert/evict here guards them all.
# compute() stays OFF-lock — it may sync or trace, and the lock-discipline
# audit (analysis/conc_audit.py) forbids either under a lock. Losing a
# concurrent-insert race just recomputes one idempotent value.
_IDENTITY_LOCK = threading.Lock()


def _identity_cache(cache: dict, max_size: int, key_arrays: tuple, compute,
                    static_key=()):
    """Bounded FIFO cache keyed by the identity of host arrays (plus an
    optional hashable ``static_key`` for non-array parameters the cached
    value depends on). The entry holds references to the keyed arrays so a
    recycled id() can never alias a freed object; evicts oldest-first past
    ``max_size``. Thread-safe: lock-free GIL-atomic read, mutations under
    :data:`_IDENTITY_LOCK`.

    Under trace-replay the cache is BYPASSED: record and replay must
    consume the same host-read sequence, and a record-time cache hit
    (from an earlier query) would skip a read the replay trace performs
    (tracer ids are always fresh)."""
    if replay_mode() != "off":
        return compute()
    key = (static_key,) + tuple(id(a) for a in key_arrays)
    hit = cache.get(key)
    if hit is not None and all(h is a for h, a in zip(hit[0], key_arrays)):
        return hit[1]
    value = compute()
    with _IDENTITY_LOCK:
        # single winner per key: a concurrent miss that landed first
        # keeps its entry and THIS caller adopts it — identity-keyed
        # consumers downstream must see ONE host object per logical key
        hit = cache.get(key)
        if hit is not None and all(h is a
                                   for h, a in zip(hit[0], key_arrays)):
            return hit[1]
        if len(cache) >= max_size:
            cache.pop(next(iter(cache)))
        cache[key] = (key_arrays, value)
    return value


_rank_cache: dict = {}


def _dict_ranks(dict_values) -> tuple:
    """(code -> lexicographic rank, rank -> code) maps for one string
    dictionary, cached per dictionary (sorts repeat the same dictionaries
    every query). Cached as HOST arrays: a device array built inside a jit
    trace is a constant tracer, and caching one leaks it into later eager
    calls (UnexpectedTracerError)."""
    def compute():
        order = np.argsort(dict_values.astype(str), kind="stable")
        ranks = np.empty(len(order), dtype=np.int64)
        ranks[order] = np.arange(len(order))
        return ranks, order.astype(np.int64)
    return _identity_cache(_rank_cache, 512, (dict_values,), compute)


def ordered_codes(col: Column) -> jnp.ndarray:
    """For a string column, map dictionary codes to lexicographic ranks so
    integer comparisons order like string comparisons."""
    return jnp.take(_dict_ranks(col.dict_values)[0], col.data)


def plain_col(col: Column) -> Column:
    """Decoded (logical-representation) view of a possibly-encoded column
    — the one choke point value-consuming ops funnel through. A fused
    elementwise device op, zero host syncs (see Column.plain)."""
    return col.plain() if col.enc is not None else col


def plain_data(col: Column) -> jnp.ndarray:
    """Decoded data array of a possibly-encoded column."""
    return col.plain().data if col.enc is not None else col.data


def sortable_view(col: Column) -> jnp.ndarray:
    """Numeric view of a column that sorts in SQL ascending order.
    FOR/dict int encodings are order-preserving, so encoded codes sort
    exactly like the logical values — no decode needed."""
    if col.kind == "str":
        return ordered_codes(col)
    if col.kind == "bool":
        return col.data.astype(jnp.int32)
    return col.data


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
@_trace.scoped("sort")
def _lexsort_impl(views, valids, descending, nulls_last, pad_key, n_valid):
    """Jit-fused multi-key sort by iterative order-preserving re-coding.

    ``views`` are numeric sortable views (host-side string ranking already
    applied); ``valids`` is a tuple of masks-or-None (structure is static);
    flag tuples are static.

    Instead of one variadic sort over up to 2k+1 operands — whose XLA:TPU
    comparator compile time grows superlinearly in operand count and has
    hung the TPU compiler outright on ORDER BY clauses with many keys
    (the same failure mode iterative re-coding fixed for q4-class GROUP
    BYs) — each key folds into one combined int64 code via
    :func:`_dense_codes` (codes are assigned in ascending value order, so
    folding ``dense(combined)*fold + code`` preserves lexicographic order),
    and a single stable single-key argsort finishes. Every fold reuses the
    same single-key sort executable.
    """
    n = views[0].shape[0]
    fold = jnp.int64(2 * n + 4)
    combined = None
    if pad_key:
        combined = (jnp.arange(n) >= n_valid).astype(jnp.int64)  # live first
    for v, valid, desc, nl in zip(views, valids, descending, nulls_last):
        # _dense_codes sorts the key in its own dtype (f64 keys sort as
        # floats — no s64 bitcast, which the TPU x64-emulation pass cannot
        # compile) and yields int64 codes in ascending value order
        if desc:
            v = -v.astype(jnp.int64) if v.dtype != jnp.float64 else -v
        if v.dtype == jnp.float64:
            # NaNs must compare EQUAL (one code, greatest — Spark's float
            # ordering) so later keys can still break their ties; boundary
            # detection via != would give every NaN its own code
            nan = jnp.isnan(v)
            c = _dense_codes(jnp.where(nan, jnp.inf, v))
            code = 2 * c + nan.astype(jnp.int64) + 1      # 1..2n
        else:
            code = _dense_codes(v) + 1                    # 1..n
        if valid is not None:
            # null sentinels sit outside every real code (max 2n < 2n+3)
            code = jnp.where(valid, code,
                             jnp.int64(2 * n + 3) if nl else jnp.int64(0))
        combined = code if combined is None else \
            _dense_codes(combined) * fold + code
    if combined is None:
        return jnp.arange(n)
    return jnp.argsort(combined, stable=True)


@_trace.traced("sort")
def lexsort_indices(cols, descending=None, nulls_last=None,
                    n_valid: int | None = None) -> jnp.ndarray:
    """Stable multi-key sort. ``cols`` primary-first; per-key descending and
    nulls-last flags (SQL default: asc, nulls first — Spark semantics).
    With ``n_valid``, rows past the logical count sort after every live row
    (the padded-table invariant is preserved by any reorder)."""
    n = len(cols[0])
    if descending is None:
        descending = [False] * len(cols)
    if nulls_last is None:
        nulls_last = [False] * len(cols)
    # a device count may sit below the physical length; the pad sort key is
    # harmless when they happen to be equal, so lazily-counted tables always
    # take it (no sync)
    pad_key = n_valid is not None and (
        isinstance(n_valid, DeviceCount) or n_valid < n)
    views = tuple(sortable_view(c) for c in cols)
    valids = tuple(c.valid for c in cols)
    return _lexsort_impl(views, valids, tuple(descending), tuple(nulls_last),
                         pad_key, 0 if n_valid is None else count_arr(n_valid))


# ---------------------------------------------------------------------------
# grouping
# ---------------------------------------------------------------------------


def _dense_codes(v: jnp.ndarray) -> jnp.ndarray:
    """Dense group codes of a single 1-D key array (exact, via one
    single-key stable sort). Codes are < len(v); no host sync."""
    n = v.shape[0]
    order = jnp.argsort(v, stable=True)
    sv = jnp.take(v, order)
    boundary = jnp.concatenate([jnp.ones(1, dtype=bool), sv[1:] != sv[:-1]])
    code_sorted = jnp.cumsum(boundary.astype(jnp.int64)) - 1
    return jnp.zeros(n, dtype=jnp.int64).at[order].set(code_sorted)


_PAD_GROUP_KEY = jnp.iinfo(jnp.int64).max // 2


@functools.partial(jax.jit, static_argnums=(2,))
@_trace.scoped("group_ids.rep")
def _group_rep_impl(gids, n_valid, cap):
    """First-occurrence row index of each live group, bucket-padded to
    ``cap`` (static); the pad group scatters out of range and is dropped."""
    plen = gids.shape[0]
    live = jnp.arange(plen) < n_valid
    scatter_ids = jnp.where(live, gids, cap)
    return jnp.full(cap, plen, dtype=jnp.int64).at[scatter_ids].min(
        jnp.arange(plen, dtype=jnp.int64), mode="drop")


@jax.jit
@_trace.scoped("group_ids")
def _group_ids_impl(views, valids, n_valid):
    """Jit-fused iterative dense re-coding (see :func:`group_ids`). One XLA
    program per (arity, null pattern, bucket); returns per-row dense group
    ids with pads in one trailing group, plus the live group count as a
    device scalar (the caller's single host sync)."""
    plen = views[0].shape[0]
    live = jnp.arange(plen) < n_valid
    fold = jnp.int64(2 * plen + 2)
    combined = None
    for v, valid in zip(views, valids):
        if valid is not None:
            # zero data under nulls: all-null rows must compare equal
            v = jnp.where(valid, v, jnp.zeros((), dtype=v.dtype))
        codes = _dense_codes(v)
        if valid is not None:
            codes = 2 * codes + (~valid).astype(jnp.int64)
        if combined is None:
            combined = codes
        else:
            # fold and immediately re-densify: both operands stay < 2*plen+2,
            # so the product below never overflows int64
            combined = _dense_codes(combined) * fold + codes
    # pad rows form one trailing group (the sort key exceeds any real code)
    combined = jnp.where(live, combined, _PAD_GROUP_KEY)
    gids = _dense_codes(combined)
    ngroups = jnp.max(jnp.where(live, gids, -1)) + 1
    return gids, ngroups


# pack multi-key groupings into one sort key when the combined bit-width
# fits: saves K sorts on the K+1-sort iterative fold. Only worth the extra
# range-probe sync on big tables; small-table groupings are latency-bound.
# Read at USE time: the threshold feeds the traced per-chunk program, so
# it is a pipeline-cache key member (engine/stream.py _cache_key) and an
# import freeze would let a post-import change serve a stale pipeline.
def group_pack_min() -> int:
    return int(os.environ.get("NDS_TPU_GROUP_PACK_MIN", str(1 << 20)))


@jax.jit
@_trace.scoped("group_ids.key_ranges")
def _int_key_ranges(views, n_valid):
    """Fused (min, max) of every integer key view over live rows — one
    dispatch, one host transfer for the whole key set."""
    plen = views[0].shape[0]
    live = jnp.arange(plen) < n_valid
    mins = jnp.stack([jnp.min(jnp.where(live, v.astype(jnp.int64), _I64_MAX))
                      for v in views])
    maxs = jnp.stack([jnp.max(jnp.where(live, v.astype(jnp.int64), _I64_MIN))
                      for v in views])
    return mins, maxs


@functools.partial(jax.jit, static_argnums=(3,))
@_trace.scoped("group_ids.packed")
def _group_ids_packed(views, valids, offsets, widths, n_valid):
    """Single-sort grouping: every key's offset code (null flag folded)
    packs into one int64, so ONE :func:`_dense_codes` sort replaces the
    K+1 sorts of the iterative fold (the SF1 q22/q78 scaling axis:
    4-key groupings over 10M+ rows)."""
    plen = views[0].shape[0]
    combined = jnp.zeros(plen, dtype=jnp.int64)
    for v, valid, off, width in zip(views, valids, offsets, widths):
        code = (v.astype(jnp.int64) - off)
        if valid is not None:
            code = 2 * jnp.where(valid, code, 0) + (~valid).astype(jnp.int64)
        combined = (combined << width) | code
    live = jnp.arange(plen) < n_valid
    combined = jnp.where(live, combined, _PAD_GROUP_KEY)
    gids = _dense_codes(combined)
    ngroups = jnp.max(jnp.where(live, gids, -1)) + 1
    return gids, ngroups


def _packed_group_plan(key_cols, views, n_valid):
    """(offsets, widths) when the combined key fits 62 bits, else None.
    String/bool key spans are host-known (dictionary sizes); integer keys
    cost ONE fused range sync — only attempted past ``group_pack_min()``."""
    int_idx = [i for i, c in enumerate(key_cols)
               if c.kind not in ("str", "bool")]
    spans = [None] * len(key_cols)
    for i, c in enumerate(key_cols):
        if c.kind == "str":
            spans[i] = (0, max(len(c.dict_values) - 1, 0))
        elif c.kind == "bool":
            spans[i] = (0, 1)
    if int_idx:
        def fetch():
            mins, maxs = _int_key_ranges(
                tuple(views[i] for i in int_idx), n_valid)
            add_syncs()
            t0 = time.perf_counter_ns()
            out = (np.asarray(mins), np.asarray(maxs))
            add_sync_wait(time.perf_counter_ns() - t0)
            return out

        mins, maxs = host_read("group_ranges", fetch)
        for k, i in enumerate(int_idx):
            if mins[k] > maxs[k]:              # no live rows
                spans[i] = (0, 0)
            else:
                spans[i] = (int(mins[k]), int(maxs[k]))
    offsets, widths, total = [], [], 0
    for (lo, hi), c in zip(spans, key_cols):
        span = hi - lo
        if c.valid is not None:
            span = 2 * span + 1                # null flag folded in
        width = max(int(span).bit_length(), 1)
        offsets.append(lo)
        widths.append(width)
        total += width
    if total > 62:
        return None
    return tuple(offsets), tuple(widths)


@_trace.traced("group_ids")
def group_ids(key_cols, n_valid: int | None = None):
    """Grouping by iterative dense re-coding.

    Returns ``(gids, ngroups, rep_indices, cap)``: per-row dense group id
    (pad rows land in one trailing, discardable group), the live group
    count, the (bucket-padded, ``cap``-long) row index of each group's first
    occurrence, and the bucket capacity every grouped output should be
    allocated with (``num_segments=cap`` keeps segment-op shapes canonical;
    pad-group contributions land in output slots past ``ngroups`` or are
    dropped).

    One single-key sort per key column (+1 to densify each fold) instead of a
    single k-key lexsort: XLA:TPU compile time for a sort comparator grows
    superlinearly in operand count, and TPC-DS group-bys reach 8+ key columns
    (q4's 8-column customer rollup hung the TPU compiler outright).
    SQL GROUP BY treats nulls as equal; each column's code folds its null
    flag in (``2*value_code + is_null``), so all-null rows share a code
    distinct from any real value's. The fold multiplier is the static bound
    ``2*plen+2`` (codes are < plen), so no per-fold host sync is needed.
    """
    plen = len(key_cols[0])
    if n_valid is None:
        n_valid = plen
    if plen == 0:
        cap = bucket_len(0)
        return (jnp.zeros(0, dtype=jnp.int64), 0,
                jnp.full(cap, 1, dtype=jnp.int64), cap)
    views = tuple(sortable_view(c) for c in key_cols)
    valids = tuple(c.valid for c in key_cols)
    nv = count_arr(n_valid)
    plan = None
    if len(key_cols) > 1 and plen >= group_pack_min():
        plan = _packed_group_plan(key_cols, views, nv)
    if plan is not None:
        gids, ng_dev = _group_ids_packed(views, valids, plan[0], plan[1],
                                         nv)
    else:
        gids, ng_dev = _group_ids_impl(views, valids, nv)
    # the one host sync — routed through the pending batch, so any lazy
    # counts the query accumulated upstream (filter compactions, inner-join
    # pair counts) resolve in the SAME transfer
    ngroups = DeviceCount(ng_dev, count_bound(n_valid)).to_int()
    cap = bucket_len(ngroups)
    rep = _group_rep_impl(gids, nv, cap)
    return gids, ngroups, rep, cap


# ---------------------------------------------------------------------------
# aggregation kernels
# ---------------------------------------------------------------------------

_F64_MIN = jnp.finfo(jnp.float64).min
_F64_MAX = jnp.finfo(jnp.float64).max
_I64_MIN = jnp.iinfo(jnp.int64).min
_I64_MAX = jnp.iinfo(jnp.int64).max


@functools.partial(jax.jit, static_argnums=(2,))
@_trace.scoped("agg.count")
def _agg_count_impl(valid, gids, ngroups):
    ones = (jnp.ones(gids.shape[0], dtype=jnp.int64) if valid is None
            else valid.astype(jnp.int64))
    return jax.ops.segment_sum(ones, gids, num_segments=ngroups)


@_trace.traced("agg", fn="count")
def agg_count(col: Column | None, gids, ngroups) -> Column:
    """count(*) when col is None else count(col) (non-null). Pad rows need
    no masking here: grouping routes them to a trailing group that lands
    past the logical group count or is dropped by the segment op.

    Counts are exactly representable in f32 below 2^24 rows, so unlike the
    decimal sums this EXACT aggregate can ride the Pallas MXU kernel —
    count appears in nearly every query (count(*), avg validity), which is
    what makes the kernel hot on the default exact-decimal bench. (The
    2^24 exactness claim and this gate are checked by
    ``analysis/num_audit.kernel_claim_checks``.)"""
    valid = None if col is None else col.valid
    if int(gids.shape[0]) < (1 << 24):
        from nds_tpu.engine.kernels import pallas_active, segment_sum_fused
        if pallas_active(ngroups):
            g = gids if valid is None else jnp.where(valid, gids, -1)
            _, counts = segment_sum_fused(
                jnp.zeros(gids.shape[0], dtype=jnp.float32), g, ngroups)
            return Column("i64", counts.astype(jnp.int64))
    return Column("i64", _agg_count_impl(valid, gids, ngroups))


@functools.partial(jax.jit, static_argnums=(3, 4))
@_trace.scoped("agg.sum")
def _agg_sum_impl(data, valid, gids, ngroups, as_f64):
    v = (jnp.ones(data.shape[0], dtype=bool) if valid is None else valid)
    d = jnp.where(v, data, 0)
    d = d if as_f64 else d.astype(jnp.int64)
    out = jax.ops.segment_sum(d, gids, num_segments=ngroups)
    cnt = jax.ops.segment_sum(v.astype(jnp.int32), gids, num_segments=ngroups)
    return out, cnt > 0


@_trace.traced("agg", fn="sum")
def agg_sum(col: Column, gids, ngroups) -> Column:
    col = plain_col(col)           # sums need logical values (fused decode)
    if col.kind == "f64":
        from nds_tpu.engine.kernels import pallas_active, segment_sum_fused
        if pallas_active(ngroups):
            # opt-in MXU fast path (f32 accumulation; the exact path below is
            # the default because validation compares at decimal tolerance).
            # The kernel's counts are per-group valid counts (gid -1 = null),
            # so they double as the result validity mask.
            valid = col.valid_mask()
            g = jnp.where(valid, gids, -1)
            sums, counts = segment_sum_fused(
                jnp.where(valid, col.data, 0), g, ngroups)
            return Column("f64", sums.astype(jnp.float64), counts > 0)
        out, nonempty = _agg_sum_impl(col.data, col.valid, gids, ngroups, True)
        return Column("f64", out, nonempty)
    if is_dec(col.kind):
        # EXACT MXU path for the default decimal bench: two's-complement
        # limb accumulation (kernels.segment_sum_exact), bit-exact for any
        # int64 — no reliance on the declared precision.
        from nds_tpu.engine.kernels import (exact_sum_supported,
                                            segment_sum_exact)
        if exact_sum_supported(ngroups, int(gids.shape[0])):
            valid = col.valid_mask()
            g = jnp.where(valid, gids, -1)
            sums, counts = segment_sum_exact(
                jnp.where(valid, col.data, 0), g, ngroups)
            return Column(f"dec(38,{col.scale})", sums, counts > 0)
    out, nonempty = _agg_sum_impl(col.data, col.valid, gids, ngroups, False)
    kind = f"dec(38,{col.scale})" if is_dec(col.kind) else "i64"
    return Column(kind, out, nonempty)


@functools.partial(jax.jit, static_argnums=(3, 4))
@_trace.scoped("agg.min")
def _agg_min_impl(view, valid, gids, ngroups, is_max):
    v = (jnp.ones(view.shape[0], dtype=bool) if valid is None else valid)
    if view.dtype == jnp.float64:
        sentinel = _F64_MIN if is_max else _F64_MAX
        work = view
    else:
        sentinel = _I64_MIN if is_max else _I64_MAX
        work = view.astype(jnp.int64)
    data = jnp.where(v, work, sentinel)
    seg = jax.ops.segment_max if is_max else jax.ops.segment_min
    out = seg(data, gids, num_segments=ngroups)
    cnt = jax.ops.segment_sum(v.astype(jnp.int32), gids, num_segments=ngroups)
    return out, cnt > 0


@_trace.traced("agg", fn="minmax")
def agg_min(col: Column, gids, ngroups, is_max=False) -> Column:
    if col.kind == "f64":
        from nds_tpu.engine.kernels import pallas_active, \
            segment_minmax_fused
        if pallas_active(ngroups):
            # float min/max rides the tiled one-hot kernel; exact kinds
            # (int/decimal/string ranks) stay on the XLA path below
            valid = col.valid_mask()
            g = jnp.where(valid, gids, -1)
            mins, maxs = segment_minmax_fused(col.data, g, ngroups)
            cnt = jax.ops.segment_sum(valid.astype(jnp.int32),
                                      jnp.where(valid, gids, 0),
                                      num_segments=ngroups)
            out = (maxs if is_max else mins).astype(jnp.float64)
            return Column("f64", jnp.where(cnt > 0, out, 0.0), cnt > 0)
    out, out_valid = _agg_min_impl(sortable_view(col), col.valid, gids,
                                   ngroups, bool(is_max))
    if col.kind == "str":
        # min/max of strings: map the winning rank back to a dictionary code
        # (the rank<->code maps are cached per dictionary)
        rank_to_code = _dict_ranks(col.dict_values)[1]
        codes = jnp.take(rank_to_code,
                         jnp.clip(out, 0, rank_to_code.shape[0] - 1))
        return Column("str", codes.astype(jnp.int32), out_valid, col.dict_values)
    if col.kind == "f64":
        return Column("f64", out, out_valid)
    # order-preserving encodings: min/max of codes IS the code of the
    # min/max value, so the result stays encoded (decode at materialize)
    return Column(col.kind, out.astype(col.data.dtype), out_valid,
                  enc=col.enc)


@functools.partial(jax.jit, static_argnums=(3,))
@_trace.scoped("agg.avg")
def _agg_avg_impl(data, valid, gids, ngroups):
    v = (jnp.ones(data.shape[0], dtype=bool) if valid is None else valid)
    d = jnp.where(v, data, 0.0)
    s = jax.ops.segment_sum(d, gids, num_segments=ngroups)
    c = jax.ops.segment_sum(v.astype(jnp.float64), gids, num_segments=ngroups)
    return jnp.where(c > 0, s / jnp.maximum(c, 1.0), 0.0), c > 0


@_trace.traced("agg", fn="avg")
def agg_avg(col: Column, gids, ngroups) -> Column:
    col = plain_col(col)
    if is_dec(col.kind):
        # exact MXU sum first (same gate as agg_sum), then one f64 divide:
        # better than accumulating rounded f64 terms AND rides the hardware
        from nds_tpu.engine.kernels import (exact_sum_supported,
                                            segment_sum_exact)
        if exact_sum_supported(ngroups, int(gids.shape[0])):
            valid = col.valid_mask()
            g = jnp.where(valid, gids, -1)
            sums, counts = segment_sum_exact(
                jnp.where(valid, col.data, 0), g, ngroups)
            out = jnp.where(
                counts > 0,
                (sums.astype(jnp.float64) / (10.0 ** col.scale)) /
                jnp.maximum(counts, 1).astype(jnp.float64), 0.0)
            return Column("f64", out, counts > 0)
    data = col.data.astype(jnp.float64)
    if is_dec(col.kind):
        data = data / (10.0 ** col.scale)
    if col.kind == "f64":
        # avg is exactly the kernel's (sums, counts) pair in one MXU pass;
        # decimal avgs stay on the exact XLA path like decimal sums
        from nds_tpu.engine.kernels import pallas_active, segment_sum_fused
        if pallas_active(ngroups):
            valid = col.valid_mask()
            g = jnp.where(valid, gids, -1)
            sums, counts = segment_sum_fused(
                jnp.where(valid, data, 0.0), g, ngroups)
            out = jnp.where(counts > 0,
                            sums.astype(jnp.float64) /
                            jnp.maximum(counts.astype(jnp.float64), 1.0), 0.0)
            return Column("f64", out, counts > 0)
    out, nonempty = _agg_avg_impl(data, col.valid, gids, ngroups)
    return Column("f64", out, nonempty)


@functools.partial(jax.jit, static_argnums=(3,))
@_trace.scoped("agg.stddev")
def _agg_stddev_impl(data, valid, gids, ngroups):
    v = (jnp.ones(data.shape[0], dtype=bool) if valid is None else valid)
    d = jnp.where(v, data, 0.0)
    s1 = jax.ops.segment_sum(d, gids, num_segments=ngroups)
    s2 = jax.ops.segment_sum(d * d, gids, num_segments=ngroups)
    c = jax.ops.segment_sum(v.astype(jnp.float64), gids, num_segments=ngroups)
    mean = s1 / jnp.maximum(c, 1.0)
    var = (s2 - c * mean * mean) / jnp.maximum(c - 1.0, 1.0)
    return jnp.sqrt(jnp.maximum(var, 0.0)), c > 1


@_trace.traced("agg", fn="stddev_samp")
def agg_stddev_samp(col: Column, gids, ngroups) -> Column:
    col = plain_col(col)
    data = col.data.astype(jnp.float64)
    if is_dec(col.kind):
        data = data / (10.0 ** col.scale)
    out, enough = _agg_stddev_impl(data, col.valid, gids, ngroups)
    return Column("f64", out, enough)


# ---------------------------------------------------------------------------
# filter / compact
# ---------------------------------------------------------------------------


@_trace.traced("filter")
def filter_table(table: DeviceTable, predicate: Column) -> DeviceTable:
    """Keep rows where the predicate is true (SQL: null counts as false)."""
    mask = predicate.data.astype(bool)
    if predicate.valid is not None:
        mask = mask & predicate.valid
    return compact_table(table, mask)


# ---------------------------------------------------------------------------
# joins
# ---------------------------------------------------------------------------

_HASH_C1 = np.uint64(0x9E3779B97F4A7C15)
_HASH_C2 = np.uint64(0xBF58476D1CE4E5B9)


def _mix64(x: jnp.ndarray) -> jnp.ndarray:
    x = x.astype(jnp.uint64)
    x = (x ^ (x >> 30)) * jnp.uint64(_HASH_C2)
    x = (x ^ (x >> 27)) * jnp.uint64(0x94D049BB133111EB)
    return x ^ (x >> 31)


@functools.partial(jax.jit, static_argnums=(2, 3))
@_trace.scoped("join.key_hash")
def _key_hash_impl(views, valids, side_salt: int, null_safe: bool, n_valid,
                   excluded=None):
    """64-bit composite hash of prepared key views (see :func:`_hash_views`).

    Default SQL join semantics: rows with any null key get a per-row unique
    value that cannot match the other side (null joins nothing). With
    ``null_safe`` (set operations, null-safe equality), the null flag is
    folded into the hash instead so null keys compare equal. Pad rows past
    ``n_valid``, and rows flagged in ``excluded`` (a deferred filter mask the
    planner chose not to materialize), always get the unmatchable per-row
    value."""
    n = views[0].shape[0]
    h = jnp.full(n, jnp.uint64(0x243F6A8885A308D3), dtype=jnp.uint64)
    any_null = jnp.zeros(n, dtype=bool)
    for v, valid in zip(views, valids):
        if v.dtype == jnp.float64:
            # equality-preserving int words (the hash is only a candidate
            # prefilter — _verify_pairs compares exactly — and a f64->s64
            # bitcast does not compile under the TPU x64-emulation rewrite):
            # the integer part plus a 52-bit fraction word keep distinct
            # doubles in distinct buckets at full double resolution
            vf = jnp.nan_to_num(v)
            ip = jnp.clip(vf, -9.0e18, 9.0e18).astype(jnp.int64)
            frac = ((vf - jnp.floor(vf)) * float(2 ** 52)).astype(jnp.int64)
            words = (ip.astype(jnp.uint64), frac.astype(jnp.uint64))
        else:
            words = (v.astype(jnp.uint64),)
        # the null-marker mix must be applied identically on both join sides,
        # including columns with no mask at all
        if valid is not None:
            words = tuple(jnp.where(valid, w, jnp.uint64(0)) for w in words)
            marker = jnp.where(valid, jnp.uint64(0),
                               jnp.uint64(0xA5A5A5A5A5A5A5A5))
            any_null = any_null | ~valid
        else:
            marker = jnp.zeros(n, dtype=jnp.uint64)
        h = _mix64(h ^ marker)
        for w in words:
            h = _mix64(h ^ w * jnp.uint64(_HASH_C1))
    unmatchable = jnp.zeros(n, dtype=bool) if null_safe else any_null
    unmatchable = unmatchable | (jnp.arange(n) >= n_valid)
    if excluded is not None:
        unmatchable = unmatchable | excluded
    row_ids = jnp.arange(n, dtype=jnp.uint64)
    # bit layout: bits 0-1 side tag, bit 2 = REAL marker (exactly zero on
    # sentinels — the exchange path classifies on it), row id from bit 3
    sentinel = jnp.uint64(1 if side_salt else 2) + (row_ids << jnp.uint64(3))
    return jnp.where(unmatchable, sentinel, h | jnp.uint64(4))


def _hash_views(left_keys, right_keys):
    """Per-pair hashable views of the join keys. String pairs are mapped
    through one merged dictionary ordering first: the per-column dictionary
    codes of the two sides are NOT comparable (equal strings get different
    codes), so hashing raw codes would silently drop every cross-dictionary
    match."""
    lviews, rviews = [], []
    for lk, rk in zip(left_keys, right_keys):
        if lk.kind == "str" and rk.kind == "str":
            lv, rv = ordered_codes_merged(lk, rk)
        else:
            # encoded int keys decode to the shared logical space (codes
            # from different encodings are not comparable) — a fused
            # elementwise widen inside the jit program, zero syncs
            lv, rv = plain_data(lk), plain_data(rk)
        lviews.append(lv)
        rviews.append(rv)
    return tuple(lviews), tuple(rviews)


def _verify_pairs(l_idx, r_idx, left_keys, right_keys,
                  null_safe: bool = False) -> jnp.ndarray:
    """Exact key equality for candidate pairs (hash-collision safety).
    With ``null_safe``, null == null."""
    ok = jnp.ones(l_idx.shape[0], dtype=bool)
    for lk, rk in zip(left_keys, right_keys):
        if lk.kind == "str" and rk.kind == "str":
            # dictionary codes come from different dicts; compare via ranks in
            # a merged ordering
            lmap, rmap = ordered_codes_merged(lk, rk)
            lv = jnp.take(lmap, l_idx)
            rv = jnp.take(rmap, r_idx)
        else:
            lv = jnp.take(plain_data(lk), l_idx)
            rv = jnp.take(plain_data(rk), r_idx)
        eq = lv == rv
        lvalid = None if lk.valid is None else jnp.take(lk.valid, l_idx)
        rvalid = None if rk.valid is None else jnp.take(rk.valid, r_idx)
        if null_safe:
            lnull = jnp.zeros_like(eq) if lvalid is None else ~lvalid
            rnull = jnp.zeros_like(eq) if rvalid is None else ~rvalid
            eq = jnp.where(lnull | rnull, lnull & rnull, eq)
        else:
            if lvalid is not None:
                eq = eq & lvalid
            if rvalid is not None:
                eq = eq & rvalid
        ok = ok & eq
    return ok


_merged_cache: dict = {}


def ordered_codes_merged(a: Column, b: Column):
    """Map two string columns' codes into one shared value ordering, cached
    per dictionary pair (host arrays — see :func:`_dict_ranks`)."""
    def compute():
        union, inverse = np.unique(
            np.concatenate([a.dict_values.astype(str), b.dict_values.astype(str)]),
            return_inverse=True)
        a_map = inverse[: len(a.dict_values)].astype(np.int64)
        b_map = inverse[len(a.dict_values):].astype(np.int64)
        return a_map, b_map
    a_map, b_map = _identity_cache(
        _merged_cache, 256, (a.dict_values, b.dict_values), compute)
    return jnp.take(jnp.asarray(a_map), a.data), \
        jnp.take(jnp.asarray(b_map), b.data)


@functools.partial(jax.jit, static_argnames="side")
@_trace.scoped("join.probe")
def _probe_search_impl(rh_sorted, lh, side):
    """One binary search of the probe side's hashes against the hash-sorted
    build side. A jitted body so that the searches, the largest device item
    of a fact-to-fact join, carry a scope name when the eager arm issues
    them; one program a side, as the bare ``jnp.searchsorted`` calls were
    (both sides in one body cost a chain join 0.2 s more on a v5e)."""
    return jnp.searchsorted(rh_sorted, lh, side=side)


# the build side's prefix bitmap: a power of two of slots, at least 8x the
# build bucket (a live build row sets one slot in eight at most), capped
_PRESENT_BITS_MAX = 26
# a narrowed PK probe's bitmap of the dimension's mixed keys: at least 16x
# the dimension's bucket, under the same cap (a live row sets one slot in
# sixteen at most: store_sales against store_returns at SF1 keeps 375 k
# candidates of 2.88 M rows, 72% of a 512 Ki bucket; at 8x about 466 k)
_PK_PRESENT_FACTOR = 16
# a probe-side sentinel (side tag 2, REAL bit clear: _key_hash_impl's
# layout) for the pad slots of a narrowed probe: equals no build hash
_PROBE_PAD = np.uint64(2)


@functools.partial(jax.jit, static_argnames="bits")
@_trace.scoped("join.candidates")
def _probe_mask_impl(rh, lh, bits):
    """Which probe rows can have a candidate, at the probe bucket, with no
    search: the row's hash is REAL (not a pad, an excluded row or, unless
    null-safe, a null key) AND its top ``bits`` bits are those of some REAL
    build hash (``present``, one scatter at the build bucket, one gather
    here). Equal hashes share a prefix, so a row this drops has count 0
    under the full search too; a row it keeps may still have none."""
    real = jnp.uint64(4)
    shift = jnp.uint64(64 - bits)
    slot = jnp.where((rh & real) != 0, (rh >> shift).astype(jnp.int32),
                     1 << bits)
    present = jnp.zeros(1 << bits, dtype=bool).at[slot].set(
        True, mode="drop")
    return ((lh & real) != 0) & jnp.take(present,
                                         (lh >> shift).astype(jnp.int32))


@jax.jit
@_trace.scoped("join.candidates")
def _probe_narrow_impl(lh, idx):
    """The candidates' hashes at their own bucket (``idx`` from
    :func:`compact_indices`; pad slots search for :data:`_PROBE_PAD`)."""
    return jnp.take(lh, idx, mode="fill", fill_value=_PROBE_PAD)


@functools.partial(jax.jit, static_argnames="plen")
@_trace.scoped("join.candidates")
def _probe_widen_impl(idx, counts, lo, plen):
    """A narrowed search's counts and offsets back at the probe bucket:
    zeros on every row that was not searched (pad slots drop)."""
    return (jnp.zeros(plen, counts.dtype).at[idx].set(counts, mode="drop"),
            jnp.zeros(plen, lo.dtype).at[idx].set(lo, mode="drop"))


def _narrowed_indices(mask, plen: int):
    """The step a count-first search shares: ONE batched, counted,
    replay-logged read of how many rows of ``mask`` (at ``plen``) can
    match, then their indices at that count's bucket. Returns ``(n, idx)``;
    ``idx`` is None where the bucket is no smaller than ``plen`` (the
    search then runs at full width and the read was the only cost)."""
    n = DeviceCount(jnp.sum(mask), plen).to_int()
    return n, compact_indices(mask, n) if bucket_len(n) < plen else None


def _probe_candidates(left_keys, right_keys, null_safe=False,
                      n_left=None, n_right=None, l_excl=None, r_excl=None):
    """Hash-probe phase shared by the monolithic and chunked joins: returns
    ``(counts, lo, order, total)`` — per-left-row candidate counts, start
    offsets into the hash-sorted right side, the right-side sort order, and
    the total candidate-pair count (host sync). Eagerly, past
    ``NDS_TPU_LAZY_SHRINK_ROWS`` probe rows, the two searches run over the
    rows that can match alone (one more batched read, the candidates'
    count): ``counts`` and ``lo`` are then 0 on every other row; the full
    search's ``counts`` is 0 there too, so the pairs are the same."""
    plen_l = len(left_keys[0])
    plen_r = len(right_keys[0])
    n_left = plen_l if n_left is None else n_left
    n_right = plen_r if n_right is None else n_right
    lviews, rviews = _hash_views(left_keys, right_keys)
    lvalids = tuple(c.valid for c in left_keys)
    rvalids = tuple(c.valid for c in right_keys)
    rh = _key_hash_impl(rviews, rvalids, 1, null_safe, count_arr(n_right),
                        r_excl)
    order = jnp.argsort(rh)
    rh_sorted = jnp.take(rh, order)
    if stream_bounds_on():
        # chunk-invariant program: no data-dependent sizing sync. The
        # caller sizes its pair bucket from static bounds and registers a
        # device-side overflow flag (checked at the pipeline's single
        # materializing sync).
        lh = _key_hash_impl(lviews, lvalids, 0, null_safe,
                            count_arr(n_left), l_excl)
        lo = jnp.searchsorted(rh_sorted, lh, side="left")
        hi = jnp.searchsorted(rh_sorted, lh, side="right")
        return hi - lo, lo, order, None
    lh = _key_hash_impl(lviews, lvalids, 0, null_safe, count_arr(n_left),
                        l_excl)
    idx = None
    if count_first(plen_l):
        # past the bucket where compact_table reads its count first, so
        # does the probe: the two searches cost 20 dependent gathers each
        # at the width they run at, and after the pk chain's deferred
        # masks few probe rows can match. One batched read of the
        # candidates' count; the searches then run at the survivors'
        # bucket, or at full width where that is no smaller
        bits = min((8 * max(plen_r, 1) - 1).bit_length(), _PRESENT_BITS_MAX)
        mask = _probe_mask_impl(rh, lh, bits=bits)
        _, idx = _narrowed_indices(mask, plen_l)
        if idx is not None:
            lh = _probe_narrow_impl(lh, idx)
    lo = _probe_search_impl(rh_sorted, lh, side="left")
    hi = _probe_search_impl(rh_sorted, lh, side="right")
    counts = hi - lo
    # the bucket the two searches ran at (under plen_l: narrowed)
    _trace.annotate(probeRows=int(lh.shape[0]))
    if idx is not None:
        counts, lo = _probe_widen_impl(idx, counts, lo, plen=plen_l)
    total = host_sync(jnp.sum(counts))                 # host sync 1
    return counts, lo, order, total


def _key_cells(keys) -> int:
    """Physical length x arrays (data and validity) of a list of key
    columns: what a join reads of one side, from host-known shapes."""
    return sum(len(c) * (1 + (c.valid is not None)) for c in keys)


@_trace.traced("join")
def join_indices(left_keys, right_keys, how: str = "inner",
                 null_safe: bool = False,
                 n_left: int | None = None, n_right: int | None = None,
                 l_excl=None, r_excl=None, probe=None):
    """Equi-join. Returns ``(l_idx, r_idx, n_pairs, l_extra, n_lx, r_extra,
    n_rx)``: bucket-padded matched pair indices with their logical count,
    plus (for outer joins) the bucket-padded unmatched row indices of each
    side. Pad slots hold out-of-range indices (gathers clip, scatters drop).
    ``l_excl``/``r_excl`` are deferred filter masks (True = row filtered
    out): such rows join nothing, which lets the planner push a filter into
    the join without a compaction sync. ``probe`` passes a precomputed
    :func:`_probe_candidates` result.
    """
    plen_l = len(left_keys[0])
    plen_r = len(right_keys[0])
    n_left = plen_l if n_left is None else n_left
    n_right = plen_r if n_right is None else n_right
    counts, lo, order, total = probe if probe is not None else \
        _probe_candidates(left_keys, right_keys, null_safe,
                          n_left, n_right, l_excl, r_excl)
    if total is None or total > 0:
        if total is None:
            # stream-bounds join: the candidate total stays on device, so
            # the pair bucket is sized from STATIC bounds (probe-side
            # bucket x a power-of-two fanout allowance). A chunk whose
            # true candidate count exceeds it would silently drop pairs,
            # so the excess registers as a device-side overflow flag the
            # streaming executor checks at its single materializing sync.
            total_dev = jnp.sum(counts)
            cand = min(bucket_len(count_bound(n_left)) * stream_fanout(),
                       bucket_len(pair_budget()))
            stream_overflow(total_dev > cand)
            pair_live = jnp.arange(cand) < total_dev
            n_pairs_bound = cand
        else:
            cand = bucket_len(total)
            pair_live = live_mask(cand, total)
            n_pairs_bound = total
        l_idx = jnp.repeat(jnp.arange(plen_l), counts, total_repeat_length=cand)
        starts = jnp.cumsum(counts) - counts
        pos = jnp.arange(cand) - jnp.repeat(starts, counts, total_repeat_length=cand)
        r_pos = jnp.repeat(lo, counts, total_repeat_length=cand) + pos
        r_idx = jnp.take(order, jnp.clip(r_pos, 0, max(plen_r - 1, 0)))
        ok = _verify_pairs(l_idx, r_idx, left_keys, right_keys, null_safe)
        ok = ok & pair_live
        # NO pair-count sync: verified pairs compact to the prefix of the
        # candidate bucket (the verify only removes hash collisions, so the
        # bucket is near-tight) and the exact count rides as a DeviceCount.
        # An outer join resolves it below — batched with the extra counts
        # into ONE transfer (DESIGN.md item 3) — because the concatenated
        # output layout needs host offsets; an inner join never syncs here.
        n_pairs = DeviceCount(jnp.sum(ok), n_pairs_bound)
        keep = jnp.nonzero(ok, size=cand, fill_value=cand)[0]
        # out-of-range pads: point pad pairs past both inputs
        l_idx = jnp.take(l_idx, keep, mode="fill", fill_value=plen_l)
        r_idx = jnp.take(r_idx, keep, mode="fill", fill_value=plen_r)
    else:
        n_pairs = 0
        cap0 = bucket_len(0)
        l_idx = jnp.full(cap0, plen_l, dtype=jnp.int64)
        r_idx = jnp.full(cap0, plen_r, dtype=jnp.int64)

    l_extra = r_extra = None
    n_lx = n_rx = 0
    miss = miss_r = None
    if how in ("left", "full"):
        matched = jnp.zeros(plen_l, dtype=bool).at[l_idx].set(
            True, mode="drop")
        miss = ~matched & live_mask(plen_l, n_left)
        if l_excl is not None:
            miss = miss & ~l_excl
        n_lx = DeviceCount(jnp.sum(miss), count_bound(n_left))
    if how in ("right", "full"):
        matched_r = jnp.zeros(plen_r, dtype=bool).at[r_idx].set(
            True, mode="drop")
        miss_r = ~matched_r & live_mask(plen_r, n_right)
        if r_excl is not None:
            miss_r = miss_r & ~r_excl
        n_rx = DeviceCount(jnp.sum(miss_r), count_bound(n_right))
    # one batched transfer resolves every count this join created
    if miss is not None:
        n_lx = n_lx.to_int()
        l_extra = compact_indices(miss, n_lx)
    if miss_r is not None:
        n_rx = n_rx.to_int()
        r_extra = compact_indices(miss_r, n_rx)
    # what the join touches, from host-known shapes: both sides' key arrays
    # at their buckets, the pair indices and the unmatched-row indices out
    _trace.annotate(cells=_key_cells(left_keys) + _key_cells(right_keys)
                    + 2 * int(l_idx.shape[0])
                    + sum(int(x.shape[0]) for x in (l_extra, r_extra)
                          if x is not None))
    return l_idx, r_idx, n_pairs, l_extra, n_lx, r_extra, n_rx


@jax.jit
@_trace.scoped("semi_join")
def _semi_sorted_impl(lv, lvalid, rv, rvalid, n_left, n_right):
    """Sort-based existence probe on directly comparable key views: dead
    right rows take the sentinel (never exposing their value), live rows
    sort live-first, so one leftmost searchsorted + equality + liveness
    check answers "does any LIVE right row hold this value" — exact (no
    hash, no collision verify), duplicate-tolerant, and sync-free."""
    plen_r = rv.shape[0]
    ok_r = jnp.arange(plen_r) < n_right
    if rvalid is not None:
        ok_r = ok_r & rvalid
    dk = jnp.where(ok_r, rv.astype(jnp.int64), _PK_SENTINEL)
    order = jnp.lexsort((~ok_r, dk))
    dks = jnp.take(dk, order)
    lvv = lv.astype(jnp.int64)
    lo = jnp.clip(jnp.searchsorted(dks, lvv), 0, max(plen_r - 1, 0))
    hit = (jnp.take(dks, lo) == lvv) & jnp.take(jnp.take(ok_r, order), lo)
    ok_l = jnp.arange(lv.shape[0]) < n_left
    if lvalid is not None:
        ok_l = ok_l & lvalid
    return hit & ok_l


@_trace.traced("semi_join")
def semi_join_mask(left_keys, right_keys, negate: bool = False,
                   null_safe: bool = False,
                   n_left: int | None = None,
                   n_right: int | None = None) -> jnp.ndarray:
    """Boolean per-left-row mask: has (semi) / lacks (anti) a match on the
    right. Used for IN / EXISTS / NOT EXISTS and (null-safe) set ops.
    Pad rows always come back False."""
    plen_l = len(left_keys[0])
    n_left = plen_l if n_left is None else n_left
    lk, rk = left_keys[0], right_keys[0]
    if len(left_keys) == 1 and not null_safe and \
            lk.kind != "f64" and rk.kind != "f64" and \
            (lk.kind == rk.kind or
             {lk.kind, rk.kind} <= {"i64", "date"}):
        # single integer-comparable key (i64/date/decimal/str ranks): the
        # sort probe answers existence directly — no candidate-pair sync
        # (_probe_candidates' total), which is one blocking round trip per
        # IN/EXISTS subquery on the generic path (DESIGN.md item 2)
        if lk.kind == "str" and rk.kind == "str":
            lview, rview = ordered_codes_merged(lk, rk)
        elif lk.kind != "str" and rk.kind != "str":
            lview, rview = plain_data(lk), plain_data(rk)
        else:
            lview = rview = None
        if lview is not None:
            plen_r = len(rk)
            n_r = plen_r if n_right is None else n_right
            # both sides' key arrays at their buckets and the mask out
            _trace.annotate(cells=_key_cells(left_keys)
                            + _key_cells(right_keys) + plen_l)
            matched = _semi_sorted_impl(lview, lk.valid, rview, rk.valid,
                                        count_arr(n_left), count_arr(n_r))
            out = ~matched if negate else matched
            return out & live_mask(plen_l, n_left)
    l_idx, _, _, _, _, _, _ = join_indices(
        left_keys, right_keys, "inner", null_safe, n_left, n_right)
    # the mask out alone: the op.join span just closed counted the keys
    # and the pair indices
    _trace.annotate(cells=plen_l)
    matched = jnp.zeros(plen_l, dtype=bool).at[l_idx].set(True, mode="drop")
    out = ~matched if negate else matched
    return out & live_mask(plen_l, n_left)


_PK_SENTINEL = jnp.iinfo(jnp.int64).max


@jax.jit
@_trace.scoped("pk_gather")
def _pk_gather_impl(fkey, fvalid, dkey, dvalid, n_fact, n_dim,
                    f_excl, d_excl):
    """Exact merge-probe of fact keys against a UNIQUE dimension key.

    Dead dimension rows (pads, filtered, null keys) take an unmatchable
    sentinel before the sort, so one searchsorted + equality check finds the
    unique match — no hash, no collision verify, no host sync. Returns
    ``(r_idx, matched)`` at fact physical length.
    """
    plen_d = dkey.shape[0]
    ok_d = jnp.arange(plen_d) < n_dim
    if dvalid is not None:
        ok_d = ok_d & dvalid
    if d_excl is not None:
        ok_d = ok_d & ~d_excl
    dk = jnp.where(ok_d, dkey.astype(jnp.int64), _PK_SENTINEL)
    # live-first tie-break: a dead row's sentinel must sort after a live row
    # holding the same (legitimate) key value, so leftmost searchsorted
    # always lands on the live row when one exists
    order = jnp.lexsort((~ok_d, dk))
    dks = jnp.take(dk, order)
    fk = fkey.astype(jnp.int64)
    lo = jnp.clip(jnp.searchsorted(dks, fk), 0, plen_d - 1)
    hit = jnp.take(dks, lo) == fk
    plen_f = fkey.shape[0]
    ok_f = jnp.arange(plen_f) < n_fact
    if fvalid is not None:
        ok_f = ok_f & fvalid
    if f_excl is not None:
        ok_f = ok_f & ~f_excl
    # gate on the matched dim row's liveness rather than on the fact key
    # value: a legitimate key equal to the sentinel (2^63-1) can only "hit"
    # a live dim row holding that same real key, so it still matches, while
    # hits on dead (sentinel-keyed) dim rows are rejected
    matched = hit & ok_f & jnp.take(jnp.take(ok_d, order), lo)
    return jnp.take(order, lo), matched


@functools.partial(jax.jit, static_argnames="bits")
@_trace.scoped("pk_gather.candidates")
def _pk_mask_impl(fkey, fvalid, dkey, dvalid, n_fact, n_dim, f_excl, d_excl,
                  bits):
    """Which fact rows can match, at the fact bucket, with no search: the
    row is live (:func:`_pk_gather_impl`'s ``ok_f``) AND the top ``bits``
    bits of its MIXED key are those of some live dimension row's
    (``present``: one scatter at the dimension's bucket, one gather here;
    the key itself is no hash: a packed key's top bits are its first
    column's). Equal keys share a prefix, so a row this drops misses under
    the full search too; a row it keeps may still miss."""
    plen_d = dkey.shape[0]
    ok_d = jnp.arange(plen_d) < n_dim
    if dvalid is not None:
        ok_d = ok_d & dvalid
    if d_excl is not None:
        ok_d = ok_d & ~d_excl
    shift = jnp.uint64(64 - bits)
    slot = jnp.where(ok_d,
                     (_mix64(dkey.astype(jnp.int64)) >> shift)
                     .astype(jnp.int32), 1 << bits)
    present = jnp.zeros(1 << bits, dtype=bool).at[slot].set(
        True, mode="drop")
    plen_f = fkey.shape[0]
    ok_f = jnp.arange(plen_f) < n_fact
    if fvalid is not None:
        ok_f = ok_f & fvalid
    if f_excl is not None:
        ok_f = ok_f & ~f_excl
    return ok_f & jnp.take(
        present, (_mix64(fkey.astype(jnp.int64)) >> shift).astype(jnp.int32))


@jax.jit
@_trace.scoped("pk_gather.candidates")
def _pk_narrow_impl(fkey, idx):
    """The candidates' keys at their own bucket (``idx`` from
    :func:`compact_indices`; the pad slots' value is never read: they lie
    past the candidates' count)."""
    return jnp.take(fkey, idx, mode="fill", fill_value=0)


@functools.partial(jax.jit, static_argnames="plen")
@_trace.scoped("pk_gather.candidates")
def _pk_widen_impl(idx, r_idx, matched, plen):
    """A narrowed probe's answer back at the fact bucket: row 0 and no
    match on every row that was not searched (pad slots drop)."""
    return (jnp.zeros(plen, r_idx.dtype).at[idx].set(r_idx, mode="drop"),
            jnp.zeros(plen, dtype=bool).at[idx].set(matched, mode="drop"))


def _pk_gather_sorted(fview, fvalid, dview, dvalid, n_fact, n_dim,
                      f_excl, d_excl):
    """The sorted arm of a PK gather: :func:`_pk_gather_impl`, whose binary
    search costs 20 dependent gathers a fact row at the width it runs at.
    Past the bucket where ``compact_table`` reads its count first, so does
    this probe: one batched read of how many fact rows can match at all,
    and the search runs at their bucket (the same program at a second
    shape), or at full width where that is no smaller. ``matched`` is the
    full search's bit for bit and ``r_idx`` is on every matched row."""
    plen_f = int(fview.shape[0])
    n_fact = count_arr(n_fact)
    idx = None
    if count_first(plen_f):
        bits = min((_PK_PRESENT_FACTOR * max(int(dview.shape[0]), 1) - 1)
                   .bit_length(), _PRESENT_BITS_MAX)
        mask = _pk_mask_impl(fview, fvalid, dview, dvalid, n_fact, n_dim,
                             f_excl, d_excl, bits=bits)
        n_cand, idx = _narrowed_indices(mask, plen_f)
    if idx is None:
        _trace.annotate(probeRows=plen_f)
        return _pk_gather_impl(fview, fvalid, dview, dvalid, n_fact, n_dim,
                               f_excl, d_excl)
    # the mask folded the fact side's liveness: the candidates are a live
    # prefix of their bucket
    _trace.annotate(probeRows=int(idx.shape[0]))
    r_idx, matched = _pk_gather_impl(_pk_narrow_impl(fview, idx), None,
                                     dview, dvalid, n_cand, n_dim, None,
                                     d_excl)
    return _pk_widen_impl(idx, r_idx, matched, plen=plen_f)


_dense_dim_cache: dict = {}


def _dense_dim_info(dim_key: Column, n_dim: int):
    """(base, device position map) when the dimension key is a dense-ish
    unique integer range (every TPC-DS surrogate key is), else None.
    Cached per key-array identity — built once per loaded dimension, it
    replaces the per-join searchsorted (a 17-iteration binary-search loop
    over emulated int64, ~0.6s for a 4M-row probe on v5e) with ONE gather."""
    if dim_key.kind == "str" or dim_key.enc is not None or n_dim == 0 \
            or n_dim > (1 << 24):
        return None                # encoded dim keys take the sort probe

    def compute():
        def fetch():
            live = np.asarray(dim_key.data[:n_dim]).astype(np.int64)
            if dim_key.valid is not None and \
                    not bool(np.all(np.asarray(dim_key.valid[:n_dim]))):
                return None                   # null PKs: sort path handles
            mn = int(live.min())
            span = int(live.max()) - mn + 1
            # sparse keys blow the map; 4x slack covers SCD-style gaps
            if span > max(4 * n_dim, 1 << 16) or span > (1 << 26):
                return None
            pos = np.full(span, n_dim, dtype=np.int64)  # n_dim = miss mark
            pos[live - mn] = np.arange(n_dim)
            return mn, pos

        # the host part (the fetched key array -> position map) routes
        # through the replay log; only the device upload stays outside
        got = timed_read("dense_dim", fetch)
        if got is None:
            return None
        mn, pos = got
        return mn, jnp.asarray(pos)

    # n_dim in the key: the position map's miss marker and coverage are
    # built for one logical row count, so a re-probe of the same array at a
    # different n_dim must not reuse a stale map
    return _identity_cache(_dense_dim_cache, 64, (dim_key.data,), compute,
                           static_key=n_dim)


@jax.jit
@_trace.scoped("pk_gather.dense")
def _pk_gather_dense_impl(fkey, fvalid, dkey, dvalid, pos_map, base,
                          n_fact, n_dim, f_excl, d_excl):
    """Dense-range merge probe: position-map gather instead of sort +
    searchsorted. Same contract as :func:`_pk_gather_impl`."""
    plen_d = dkey.shape[0]
    plen_f = fkey.shape[0]
    ok_d = jnp.arange(plen_d) < n_dim
    if dvalid is not None:
        ok_d = ok_d & dvalid
    if d_excl is not None:
        ok_d = ok_d & ~d_excl
    fk = fkey.astype(jnp.int64)
    off = fk - base
    span = pos_map.shape[0]
    inb = (off >= 0) & (off < span)
    r_idx = jnp.take(pos_map, jnp.clip(off, 0, span - 1))
    r_ok = inb & (r_idx < n_dim)
    r_idx = jnp.clip(r_idx, 0, plen_d - 1)
    hit = r_ok & (jnp.take(dkey.astype(jnp.int64), r_idx) == fk)
    hit = hit & jnp.take(ok_d, r_idx)
    ok_f = jnp.arange(plen_f) < n_fact
    if fvalid is not None:
        ok_f = ok_f & fvalid
    if f_excl is not None:
        ok_f = ok_f & ~f_excl
    return r_idx, hit & ok_f


@_trace.traced("pk_gather")
def pk_gather_join(fact_key: Column, dim_key: Column,
                   n_fact: int, n_dim: int, f_excl=None, d_excl=None):
    """Planner-facing wrapper of :func:`_pk_gather_impl`: prepares
    comparable integer views (merged dictionary ranks for string pairs),
    and takes the dense-range position-map probe when the dimension key
    is a dense unique integer range (all TPC-DS surrogate keys)."""
    # the dense position map is HOST-built per dimension, so a lazy dim
    # count resolves here (batched); dimensions are load-time tables with
    # host counts on every hot path, so this stays sync-free in practice
    if isinstance(n_dim, DeviceCount):
        n_dim = n_dim.to_int()
    if fact_key.kind == "str" and dim_key.kind == "str":
        fview, dview = ordered_codes_merged(fact_key, dim_key)
    else:
        fview, dview = plain_data(fact_key), plain_data(dim_key)
        dense = _dense_dim_info(dim_key, n_dim)
        if dense is not None:
            base, pos_map = dense
            return _pk_gather_dense_impl(
                fview, fact_key.valid, dview, dim_key.valid, pos_map,
                jnp.int64(base), count_arr(n_fact), n_dim, f_excl, d_excl)
    return _pk_gather_sorted(fview, fact_key.valid, dview, dim_key.valid,
                             n_fact, n_dim, f_excl, d_excl)


_dim_span_cache: dict = {}


@jax.jit
@_trace.scoped("pk_gather.pack_keys")
def _pack_keys_impl(views, valids, offsets, widths, spans):
    """Pack offset key codes into one int64, with a combined validity
    (per-key nulls AND in-range — a fact key outside the dim's span can
    never match)."""
    plen = views[0].shape[0]
    packed = jnp.zeros(plen, dtype=jnp.int64)
    ok = jnp.ones(plen, dtype=bool)
    for v, valid, off, width, span in zip(views, valids, offsets, widths,
                                          spans):
        k = v.astype(jnp.int64) - off
        ok = ok & (k >= 0) & (k <= span)
        if valid is not None:
            ok = ok & valid
        packed = (packed << width) | jnp.clip(k, 0, span)
    return packed, ok


@_trace.traced("pk_gather")
def pk_gather_join_multi(fact_keys, dim_keys, n_fact: int, n_dim: int,
                         f_excl=None, d_excl=None):
    """Composite-key merge probe against a UNIQUE key set (the fact/returns
    composite primary keys): pack every key into one int64 (widths from the
    dim side's value spans — one fused range sync, identity-cached per key
    set) and run the single-key exact probe. Returns ``(r_idx, matched)``
    or None when the keys cannot pack (non-integer kinds or >62 combined
    bits) — callers fall back to the hash join."""
    if len(fact_keys) == 1:
        return pk_gather_join(fact_keys[0], dim_keys[0], n_fact, n_dim,
                              f_excl, d_excl)
    kinds = {c.kind for c in list(fact_keys) + list(dim_keys)}
    if any(k in ("str", "f64") or k.startswith("dec") for k in kinds):
        return None
    if isinstance(n_dim, DeviceCount):      # host span plan (see above)
        n_dim = n_dim.to_int()
    # encoded keys pack through their decoded logical views (the span
    # plan is identity-cached per dim-key ARRAY, which is unencoded on
    # every dimension; the fact side decodes fused)
    fact_keys = [plain_col(c) for c in fact_keys]
    dim_keys = [plain_col(c) for c in dim_keys]

    def compute():
        def fetch():
            mins, maxs = _int_key_ranges(
                tuple(c.data for c in dim_keys), n_dim)
            add_syncs()
            t0 = time.perf_counter_ns()
            out = (np.asarray(mins), np.asarray(maxs))
            add_sync_wait(time.perf_counter_ns() - t0)
            return out

        mins, maxs = host_read("dim_ranges", fetch)
        offsets, widths, spans, total = [], [], [], 0
        for lo, hi in zip(mins, maxs):
            span = max(int(hi) - int(lo), 0)
            width = max(int(span).bit_length(), 1)
            offsets.append(int(lo))
            widths.append(width)
            spans.append(span)
            total += width
        if total > 62:
            return None
        return tuple(offsets), tuple(widths), tuple(spans)

    plan = _identity_cache(_dim_span_cache, 128,
                           tuple(c.data for c in dim_keys), compute,
                           static_key=n_dim)
    if plan is None:
        return None
    offsets, widths, spans = plan
    fpacked, fok = _pack_keys_impl(
        tuple(c.data for c in fact_keys),
        tuple(c.valid for c in fact_keys), offsets, widths, spans)
    dpacked, dok = _pack_keys_impl(
        tuple(c.data for c in dim_keys),
        tuple(c.valid for c in dim_keys), offsets, widths, spans)
    return _pk_gather_sorted(fpacked, fok, dpacked, dok, n_fact, n_dim,
                             f_excl, d_excl)


def _null_column_like(col: Column, n: int) -> Column:
    data = jnp.zeros((n,) + col.data.shape[1:], dtype=col.data.dtype)
    return Column(col.kind, data, jnp.zeros(n, dtype=bool), col.dict_values,
                  enc=col.enc)


# candidate-pair budget for one materialized join chunk: beyond this the
# inner join splits the probe side into capacity-bounded chunks (the >HBM
# streaming answer SURVEY §5.7 calls for; the reference's analog is the
# RAPIDS spill store + spark.sql.shuffle.partitions,
# ref: nds/power_run_gpu.template:29-37). Read at USE time: the budget
# sizes the stream-mode pair bucket inside the traced per-chunk program,
# so it is a pipeline-cache key member (engine/stream.py _cache_key).
def pair_budget() -> int:
    return int(os.environ.get("NDS_TPU_PAIR_BUDGET", str(1 << 22)))

# stream-bounds pair-bucket fanout: inside the compiled chunk pipeline a
# hash join cannot sync for its candidate total, so the bucket is the
# probe side's bound times this power-of-two allowance (kept power-of-two
# so bucket shapes stay canonical); overflow falls back to the eager loop.
# Read at USE time (not import): tests and Throughput children that set
# NDS_TPU_STREAM_FANOUT after import must not be silently ignored. The
# static memory model (analysis/mem_audit.py) mirrors this read.
def stream_fanout() -> int:
    return _pow2_ceil(int(os.environ.get("NDS_TPU_STREAM_FANOUT", "4")))


@functools.partial(jax.jit, static_argnames=("cand",))
@_trace.scoped("join.span_pairs")
def _span_pair_indices(counts, lo, order, s, e, cand):
    """Candidate pair indices restricted to probe rows [s, e); padded to the
    static capacity ``cand`` (span boundaries are dynamic, so every span
    with the same capacity reuses one executable)."""
    plen_l = counts.shape[0]
    plen_r = order.shape[0]
    row = jnp.arange(plen_l)
    c_counts = jnp.where((row >= s) & (row < e), counts, 0)
    l_idx = jnp.repeat(row, c_counts, total_repeat_length=cand)
    starts = jnp.cumsum(c_counts) - c_counts
    pos = jnp.arange(cand) - jnp.repeat(starts, c_counts,
                                        total_repeat_length=cand)
    r_pos = jnp.repeat(lo, c_counts, total_repeat_length=cand) + pos
    r_idx = jnp.take(order, jnp.clip(r_pos, 0, max(plen_r - 1, 0)))
    return l_idx, r_idx


def _chunk_spans(counts_np, budget):
    """Greedy contiguous spans of probe rows whose candidate-pair sums stay
    within ``budget`` (a single row exceeding it gets its own span).
    Vectorized: this path triggers exactly when the probe side is large, so
    a per-row Python loop would cost seconds of host time per join."""
    n = len(counts_np)
    cum = np.cumsum(counts_np, dtype=np.int64)
    spans, s = [], 0
    while s < n:
        base = cum[s - 1] if s else 0
        # last row index whose cumulative stays within budget from `base`
        e = int(np.searchsorted(cum, base + budget, side="right"))
        if e <= s:
            e = s + 1                    # oversized single row: own span
        spans.append((s, e))
        s = e
    return spans


def pair_table(left: DeviceTable, l_idx, right: DeviceTable, r_idx,
               nrows) -> DeviceTable:
    """The pair table of a join, nothing gathered: row ``i`` is row
    ``l_idx[i]`` of ``left`` beside row ``r_idx[i]`` of ``right``, each
    side one deferred group (on a name both sides hold, ``right``'s column
    wins). A residual reads the columns it names through ``table[name]``,
    each gathered alone at the pairs' bucket; whatever compacts or joins
    the table next composes the two indices (a side's own deferred groups,
    a snowflake, stay reachable: :func:`_gather_rows` recurses)."""
    return DeviceTable({}, nrows, plen=int(l_idx.shape[0])) \
        .with_deferred(left, l_idx, pair=True) \
        .with_deferred(right, r_idx, pair=True)


def _chunked_inner_join(left, right, left_keys, right_keys, probe,
                        residual_fn) -> DeviceTable:
    """Inner join made span-by-span so peak memory is bounded by
    ``pair_budget()`` pairs, with residual predicates applied per span
    before anything is kept — the pair expansion never exists whole, and
    no column of it at all: a span's :func:`pair_table` lets the residual
    gather the columns it names, the survivors are kept as their two index
    arrays, and the result is one pair table over the spans' indices,
    concatenated and squeezed as :func:`concat_tables` does any parts."""
    counts, lo, order, total = probe

    def fetch():
        counts_np = np.asarray(counts)
        return (_chunk_spans(counts_np, pair_budget()),
                np.concatenate([[0], np.cumsum(counts_np)]))

    spans, cum = timed_read("chunk_spans", fetch)
    parts, n_spans = [], 0
    cells = _key_cells(left_keys) + _key_cells(right_keys)
    for (s, e) in spans:
        span_total = int(cum[e] - cum[s])
        if span_total == 0:
            continue
        cand = bucket_len(span_total)
        l_idx, r_idx = _span_pair_indices(counts, lo, order, s, e, cand)
        ok = _verify_pairs(l_idx, r_idx, left_keys, right_keys)
        ok = ok & live_mask(cand, span_total)
        if residual_fn is not None:
            ok = ok & residual_fn(pair_table(left, l_idx, right, r_idx, cand))
        n_live = host_sync(jnp.sum(ok))                # host sync per span
        n_spans += 1
        cells += 2 * cand
        if n_live == 0:
            continue
        pairs = DeviceTable({"l": Column("i64", l_idx),
                             "r": Column("i64", r_idx)}, cand)
        parts.append(take_padded(pairs, compact_indices(ok, n_live), n_live))
        cells += 2 * parts[-1].plen
    # what the arm touches, from host-known shapes: both sides' key arrays,
    # and per span the two pair-index arrays at the candidates' bucket and
    # the two survivors' at theirs
    _trace.annotate(cells=cells, spans=n_spans)
    if not parts:
        cap0 = bucket_len(0)
        return pair_table(
            left, jnp.full(cap0, len(left_keys[0]), dtype=jnp.int64),
            right, jnp.full(cap0, len(right_keys[0]), dtype=jnp.int64), 0)
    kept = concat_tables(parts) if len(parts) > 1 else parts[0]
    return pair_table(left, kept["l"].data, right, kept["r"].data,
                      kept.nrows)


def _exchange_inner_join(left, right, left_keys, right_keys, mesh,
                         l_excl, r_excl, residual_fn) -> DeviceTable:
    """Repartition join over the mesh: both sides are row-sharded (too big
    for the broadcast threshold), so their (hash, row id) pairs move through
    the ICI all-to-all exchange and the probe runs device-local on
    co-partitioned key ranges (the planner's repartition-join arm; SURVEY.md
    §5.8, the UCX-shuffle role of the reference's accelerated stack)."""
    from nds_tpu.parallel.exchange import exchange_join_pairs
    plen_l = len(left_keys[0])
    plen_r = len(right_keys[0])
    lviews, rviews = _hash_views(left_keys, right_keys)
    lh = _key_hash_impl(lviews, tuple(c.valid for c in left_keys), 0,
                        False, count_arr(left.nrows), l_excl)
    rh = _key_hash_impl(rviews, tuple(c.valid for c in right_keys), 1,
                        False, count_arr(right.nrows), r_excl)
    l_idx_x, r_idx_x, live = exchange_join_pairs(
        lh, jnp.arange(plen_l, dtype=jnp.int64),
        rh, jnp.arange(plen_r, dtype=jnp.int64), mesh)
    ok = live & _verify_pairs(l_idx_x, r_idx_x, left_keys, right_keys)
    n_pairs = host_sync(jnp.sum(ok))                   # host sync
    keep = jnp.nonzero(ok, size=bucket_len(n_pairs),
                       fill_value=int(ok.shape[0]))[0]
    l_idx = jnp.take(l_idx_x, keep, mode="fill", fill_value=plen_l)
    r_idx = jnp.take(r_idx_x, keep, mode="fill", fill_value=plen_r)
    matched = DeviceTable(
        {**gather_table_rows(left, l_idx, n_pairs).columns,
         **gather_table_rows(right, r_idx, n_pairs).columns}, n_pairs)
    if residual_fn is not None:
        mask = residual_fn(matched) & live_mask(matched.plen, n_pairs)
        matched = compact_table(matched, mask)
    return matched


@_trace.traced("join")
def join_tables(left: DeviceTable, right: DeviceTable, left_on, right_on,
                how: str = "inner", l_excl=None, r_excl=None,
                residual_fn=None) -> DeviceTable:
    """Materialized equi-join of two tables; column name collisions must be
    resolved by the caller (planner aliases). ``l_excl``/``r_excl`` fold
    deferred filter masks into the join (see :func:`join_indices`).
    ``residual_fn`` (inner joins) maps a pair table to a keep mask —
    non-equi residual predicates evaluated inside the join. Past
    ``pair_budget()`` candidates (the chunked path) that table is
    :func:`pair_table`'s, two deferred groups, so the residual gathers the
    columns it reads by name and no pair expansion is ever made whole; the
    result is such a table too, and a column of it is gathered when the
    statement first reads it."""
    left_keys = [left[c] for c in left_on]
    right_keys = [right[c] for c in right_on]
    probe = None
    if how == "inner":
        from nds_tpu.parallel.exchange import mesh_of
        lm = mesh_of(*(c.data for c in left_keys))
        rm = mesh_of(*(c.data for c in right_keys))
        if lm is not None and rm is not None:
            # both sides row-sharded => repartition join over the exchange
            # (tables under the broadcast threshold are replicated at load,
            # so fact x dim joins never take this path)
            return _exchange_inner_join(left, right, left_keys, right_keys,
                                        lm, l_excl, r_excl, residual_fn)
        probe = _probe_candidates(left_keys, right_keys,
                                  n_left=left.nrows, n_right=right.nrows,
                                  l_excl=l_excl, r_excl=r_excl)
        # probe[3] is None under stream-bounds: the chunked (span-by-span)
        # join syncs per span, so the streamed path always takes the
        # bound-bucket monolithic arm below
        if probe[3] is not None and probe[3] > pair_budget():
            return _chunked_inner_join(left, right, left_keys, right_keys,
                                       probe, residual_fn)
    l_idx, r_idx, n_pairs, l_extra, n_lx, r_extra, n_rx = join_indices(
        left_keys, right_keys, how,
        n_left=left.nrows, n_right=right.nrows,
        l_excl=l_excl, r_excl=r_excl, probe=probe)
    matched = DeviceTable(
        {**gather_table_rows(left, l_idx, n_pairs).columns,
         **gather_table_rows(right, r_idx, n_pairs).columns}, n_pairs)
    if residual_fn is not None and how == "inner":
        mask = residual_fn(matched) & live_mask(matched.plen, n_pairs)
        matched = compact_table(matched, mask)
    parts = [matched]
    if l_extra is not None and n_lx:
        cols = dict(gather_table_rows(left, l_extra, n_lx).columns)
        cols.update({n: _null_column_like(c, int(l_extra.shape[0]))
                     for n, c in right.columns.items()})
        parts.append(DeviceTable(cols, n_lx))
    if r_extra is not None and n_rx:
        cols = {n: _null_column_like(c, int(r_extra.shape[0]))
                for n, c in left.columns.items()}
        cols.update(gather_table_rows(right, r_extra, n_rx).columns)
        parts.append(DeviceTable(cols, n_rx))
    return concat_tables(parts) if len(parts) > 1 else matched


# ---------------------------------------------------------------------------
# concatenation (UNION ALL) with dictionary merging
# ---------------------------------------------------------------------------


_union_cache: dict = {}


def _align_str_dicts(cols):
    """(per-part code arrays, shared dictionary) for string columns whose
    dictionaries may differ: remap every part's codes into one merged
    value table (identity fast path when all parts share one dictionary).
    The merged dictionary is cached per input-dictionary identity tuple so
    repeated executions hand out the SAME host object — downstream
    identity-keyed caches (expression fusion, rank maps) would otherwise
    miss and retrace every run."""
    dicts = [c.dict_values for c in cols]
    if all(d is dicts[0] for d in dicts):
        return [c.data for c in cols], dicts[0]

    def compute():
        union, inverse = np.unique(
            np.concatenate([d.astype(str) for d in dicts]),
            return_inverse=True)
        # cache HOST arrays only: a device constant created inside a jit
        # trace is a tracer, and caching one leaks it across traces
        maps, off = [], 0
        for d in dicts:
            maps.append(inverse[off:off + len(d)].astype(np.int32))
            off += len(d)
        return maps, union.astype(object)

    maps, union = _identity_cache(_union_cache, 256, tuple(dicts), compute)
    return [jnp.take(jnp.asarray(m), c.data) for m, c in zip(maps, cols)], \
        union


def _align_encodings(cols):
    """Decode parts whose encodings differ (codes from different
    encodings are not concatenable); identical encodings concatenate
    narrow and stay encoded — the partitioned accumulator union path."""
    enc0 = cols[0].enc
    if all(encs_equal(c.enc, enc0) for c in cols) and \
            len({c.data.dtype for c in cols}) == 1:
        return cols, enc0
    return [plain_col(c) for c in cols], None


def concat_columns(cols) -> Column:
    kind = cols[0].kind
    if kind == "str":
        datas, dict_values = _align_str_dicts(cols)
        data = jnp.concatenate(datas)
        valid = _concat_valids(cols)
        return Column("str", data.astype(jnp.int32), valid, dict_values)
    cols, enc = _align_encodings(cols)
    data = jnp.concatenate([c.data for c in cols])
    return Column(kind, data, _concat_valids(cols), enc=enc)


def _concat_valids(cols):
    if all(c.valid is None for c in cols):
        return None
    return jnp.concatenate([c.valid_mask() for c in cols])


@jax.jit
@_trace.scoped("concat")
def _concat_cols_impl(parts_datas, parts_valids, part_nrows):
    """Fused concatenation of every column of a UNION ALL (plus the live
    mask) in one device dispatch. ``parts_valids`` entries are per-column
    tuples mixing arrays and None (all-valid parts materialize ones only
    when some sibling carries a mask)."""
    datas = tuple(jnp.concatenate(ds) for ds in parts_datas)
    valids = []
    for ds, vs in zip(parts_datas, parts_valids):
        if vs is None:
            valids.append(None)
        else:
            valids.append(jnp.concatenate([
                v if v is not None else jnp.ones(d.shape[0], dtype=bool)
                for d, v in zip(ds, vs)]))
    plens = [d.shape[0] for d in parts_datas[0]]
    live = jnp.concatenate([jnp.arange(p) < n
                            for p, n in zip(plens, part_nrows)])
    return datas, tuple(valids), live


@_trace.traced("concat")
def concat_tables(tables) -> DeviceTable:
    """UNION ALL. Physical concatenation interleaves each part's pad rows, so
    the result is re-compacted back to prefix-padded form; the logical counts
    are already known on host, so this costs no sync. All columns concatenate
    in one fused dispatch (string columns pre-align their dictionaries on
    host)."""
    names = tables[0].column_names
    # physical concatenation lays parts out with host offsets, so lazy
    # counts must resolve here — all parts in ONE batched transfer
    total = sum(count_int(t.nrows) for t in tables)
    if not names:
        return DeviceTable({}, total, plen=max(bucket_len(total), total))

    parts_datas, parts_valids, metas = [], [], []
    for n in names:
        cols = [t[n] for t in tables]
        kind = cols[0].kind
        enc = None
        if kind == "str":
            datas, dict_values = _align_str_dicts(cols)
        else:
            cols, enc = _align_encodings(cols)
            datas, dict_values = [c.data for c in cols], None
        vs = None if all(c.valid is None for c in cols) else \
            tuple(c.valid for c in cols)
        parts_datas.append(tuple(datas))
        parts_valids.append(vs)
        metas.append((n, kind, dict_values, enc))

    part_nrows = tuple(count_int(t.nrows) for t in tables)
    datas, valids, live = _concat_cols_impl(
        tuple(parts_datas), tuple(parts_valids), part_nrows)
    out = {}
    for (n, kind, dict_values, enc), d, v in zip(metas, datas, valids):
        if kind == "str":
            d = d.astype(jnp.int32)
        out[n] = Column(kind, d, v, dict_values, enc)
    raw = DeviceTable(out, total)
    # fast path only when the summed physical length is itself a canonical
    # bucket: a non-bucket plen (e.g. 16+32=48) would leak into the XLA
    # shape universe and defeat executable reuse downstream
    if total == int(live.shape[0]) and total == bucket_len(total):
        res = raw                                     # no pads anywhere
    else:
        res = take_padded(raw, compact_indices(live, total), total)
    # what the append wrote, from host-known shapes: every column's arrays
    # (data and validity) at the output's bucket
    _trace.annotate(cells=res.plen * sum(
        1 + (v is not None) for v in valids))
    return res


# ---------------------------------------------------------------------------
# sort / limit
# ---------------------------------------------------------------------------


def sort_table(table: DeviceTable, keys, descending=None, nulls_last=None) -> DeviceTable:
    order = lexsort_indices([table[k] if isinstance(k, str) else k for k in keys],
                            descending, nulls_last, n_valid=table.nrows)
    return gather_table_rows(table, order, table.nrows)


def limit_table(table: DeviceTable, n: int) -> DeviceTable:
    """First ``n`` logical rows (callers sort first; pads always trail).
    LIMIT is output-shaping: a lazy count legitimately resolves here
    (batched), per DESIGN.md item 1's consumer taxonomy."""
    new_n = min(n, count_int(table.nrows))
    cap = bucket_len(new_n)
    if cap >= table.plen:
        return DeviceTable(dict(table.columns), new_n)
    return gather_table_rows(table, jnp.arange(cap), new_n)
