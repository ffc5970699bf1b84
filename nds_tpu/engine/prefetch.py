# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Bounded prefetch ring: the asynchronous ingest half of the data plane.

Every byte the streamed executor consumes used to enter through ONE host
thread doing the arrow slice, narrow-codec encode and ``jax.device_put``
INLINE in the drive loop — the "double-buffered prefetch" was depth-1
and serial on the driver (dispatch asynchrony hid the device compute,
never the host-side slice+encode). This module moves that host work off
the driver thread:

* a single WORKER thread pulls upcoming chunks from the source iterator
  (``ChunkedTable.padded_chunks`` / the eager loop's ``device_chunks``),
  applies the caller's ``prepare`` step (flatten + nbytes accounting +
  sharded placement — the host slice, encode and upload), and hands the
  ready payloads through a bounded queue;
* the queue depth (``NDS_TPU_PREFETCH_DEPTH``, read at ring-BUILD time,
  default 2) is the BACKPRESSURE bound: the worker blocks once ``depth``
  prepared chunks are waiting, so the ring's extra live set is exactly
  ``depth x chunk bytes`` — the number ``analysis/mem_audit.py`` prices
  into pipeline admission (the lockstep rule);
* delivery is ORDERED by construction (one worker, one FIFO queue):
  chunk k always arrives before chunk k+1, which the accumulator
  scatter and the partition histogram rely on only for determinism of
  the trace labels — the math itself is order-independent;
* ``close()`` is the clean shutdown: it signals the worker, drains the
  queue so a backpressure-blocked ``put`` wakes, and joins the thread —
  called from the drive loops' ``finally`` so an overflow/eager-rerun or
  a trace-divergence exception never leaks a thread or pins payloads;
* a worker exception is PROPAGATED: it rides the queue as an error
  payload and re-raises in the driver at the next fetch, so a corrupt
  chunk store or a codec bug fails the statement exactly like the
  inline path would (strict mode and the eager fallback both see the
  original exception).

``depth <= 0`` disables the ring entirely: :func:`chunk_ring` returns an
inline pump that runs ``prepare`` on the driver thread at each fetch —
bit-for-bit today's path (same thread, same order, same dispatch
interleaving), the escape hatch and the A/B baseline of the slow-source
differential (``tests/test_prefetch.py``).

Contract for ``prepare`` (and the source iterator's per-item work, which
also runs on the worker): NO host reads and NO spans. The worker thread
has its own thread-local sync counters and span ring, so a sync there
would vanish from the driver's accounting and a span would land in the
``unattributed`` diagnostics ring — the ``host-sync-in-prefetch-worker``
jax_lint rule (error severity) rejects both statically, and the conc
audit's ring-liveness probe (``tools/conc_audit_diff.py``) exercises the
shutdown path under real threads. Slice + encode + ``device_put`` are
all sync-free by construction (numpy work plus an async upload), which
is why the whole ingest step can leave the driver thread at all.

What the worker spends its time on is measured all the same, by the ring
itself: it times three stages per chunk — ``source`` (the iterator's
``next``: slice + encode), ``prepare`` (flatten + ``device_put``) and
``backpressure`` (the blocked ``put``) — parks ``(stage, ts_ns, dur_ns,
chunk)`` on the ring instance under the lock that guards the worker's
fault events, and the DRIVER re-records them at its next fetch / at
``close()`` as ``prefetch.source`` / ``prefetch.prepare`` /
``prefetch.backpressure`` spans under the scan's ``stream`` span
(``obs.record_interval``, marked ``thread="worker"``): the
``_drain_worker_faults`` pattern, so the thread-scoped rings stand and
nothing lands unattributed. On the profiler's clock the worker opens
``nds:prefetch.*`` annotations live on its own thread (an annotation is
not a span: no ring, no counters). The inline pump records the same
stages as ordinary spans on the driver.

The driver-side fetch (:meth:`ChunkRing.next_chunk`) accumulates the
time the driver spent BLOCKED waiting on the ring (``stall_ns``) — the
number ``StreamEvent.prefetch_stall_ms`` surfaces per scan and
``tools/trace_report.py`` prices as its own phase column: overlap is
evidence, not assertion. With the ring disabled the same counter holds
the inline slice+encode+upload time (the cost the ring exists to hide),
so the depth-0 vs depth-N differential reads directly off the events.
"""

from __future__ import annotations

import os
import queue
import threading
import time

from nds_tpu.engine import faults as _F
from nds_tpu.obs import trace as _obs

# sentinel kinds riding the queue (payloads are (kind, value) pairs)
_ITEM = "item"
_DONE = "done"
_ERR = "err"


def _prepare_guarded(prepare, item):
    """One prepare attempt behind the ``prefetch`` fault seam: the
    injection point sits exactly where a real slice/encode/upload fault
    would interrupt (``device-put`` injections fire inside ``prepare``
    itself — engine/stream.py's ``_prepare_chunk``)."""
    _F.fault_point("prefetch")
    return item if prepare is None else prepare(item)

# how long a blocked worker put waits between shutdown checks: short
# enough that close() never stalls the caller, long enough to stay off
# the scheduler's back during normal backpressure
_PUT_POLL_S = 0.05


def prefetch_depth() -> int:
    """``NDS_TPU_PREFETCH_DEPTH``: bounded ring depth (chunks the worker
    may run ahead of the driver). Read at ring-BUILD time, never frozen
    at import (the PR 6/13 env-knob discipline); ``<= 0`` disables the
    ring — the inline, bit-for-bit-today path. Default 2: one chunk
    uploading while one sits ready, matching the double-buffer the
    drive loop's async dispatch already assumed."""
    try:
        return int(os.environ.get("NDS_TPU_PREFETCH_DEPTH", "2"))
    except ValueError:
        return 2


class _InlineRing:
    """Depth-0 escape hatch: same interface, no thread — ``prepare``
    runs on the driver at each fetch, exactly the pre-ring drive loop.
    ``stall_ns`` then measures the inline host fetch (slice + encode +
    upload) so the differential against a live ring is observable."""

    def __init__(self, it, prepare=None, start=0):
        self._it = iter(it)
        self._prepare = prepare
        self._chunk = int(start)         # index of the next chunk fetched
        self.stall_ns = 0

    def next_chunk(self):
        t0 = time.perf_counter_ns()
        try:
            # the worker's stages, on the driver (ordinary spans here)
            with _obs.span("prefetch.source", chunk=self._chunk) as sp:
                item = next(self._it, None)
                if item is None:
                    sp.drop()            # end of stream: no chunk, no span
                    return None
            # same bounded-retry policy as the threaded worker (the
            # ``prefetch`` transient seam), on the driver thread — the
            # depth-0 pump stays bit-for-bit except under a real fault
            with _obs.span("prefetch.prepare", chunk=self._chunk):
                self._chunk += 1
                return _F.with_retry(
                    "prefetch",
                    lambda: _prepare_guarded(self._prepare, item))
        finally:
            self.stall_ns += time.perf_counter_ns() - t0

    def stall_ms(self) -> float:
        return self.stall_ns / 1e6

    def close(self) -> None:
        self._it = iter(())

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


class ChunkRing:
    """Bounded, ordered, single-worker prefetch ring over one chunk
    iterator. See the module docstring for the full contract."""

    def __init__(self, it, prepare=None, depth=2, name="nds-prefetch",
                 start=0):
        self._q: queue.Queue = queue.Queue(maxsize=max(int(depth), 1))
        self._stop = threading.Event()
        self._exhausted = False
        self.stall_ns = 0
        # worker-side recovery evidence: FaultEvents are thread-scoped
        # (like sync counters), so a retry that recovered ON THE WORKER
        # parks its event here and the driver re-records it into its own
        # ring at the next fetch — instance state under one dedicated
        # lock (the conc-audit classification)
        self._faults: list = []
        self._faults_lock = threading.Lock()
        # worker-side stage timings, parked the same way (same lock) and
        # re-recorded by the driver under the scan's "stream" span — read
        # here, on the driver thread that builds the ring
        self._stages: list = []
        self._span_parent, self._span_qid = _obs.enclosing("stream")
        self._thread = threading.Thread(
            target=self._work, args=(iter(it), prepare, int(start)),
            daemon=True,
            name=name)
        self._thread.start()

    # ------------------------------------------------------------ worker

    def _put(self, payload) -> bool:
        """Backpressure-bounded put that stays responsive to shutdown:
        returns False when the ring closed while waiting."""
        while not self._stop.is_set():
            try:
                self._q.put(payload, timeout=_PUT_POLL_S)
                return True
            except queue.Full:
                continue
        return False

    def _sink(self, seam, action, attempt=0, detail=""):
        """Worker-side FaultEvent sink (see __init__)."""
        with self._faults_lock:
            self._faults.append((seam, action, attempt, detail))

    def _drain_worker_faults(self) -> None:
        """Re-record worker-side recovery events on the DRIVER thread so
        they land in the query's own evidence ring."""
        with self._faults_lock:
            got, self._faults[:] = list(self._faults), []
        for (seam, action, attempt, detail) in got:
            _F.record_fault_event(seam, action, attempt=attempt,
                                  detail=detail)

    def _note(self, name: str):
        """Live ``nds:<name>`` profiler annotation on the worker thread,
        naming the scan's ``stream`` span as its parent."""
        return _obs.annotation(name, parent=self._span_parent,
                               qid=self._span_qid)

    def _stage(self, stage: str, t0: int, chunk: int) -> None:
        """Park one finished worker stage for the driver to re-record."""
        dur = time.perf_counter_ns() - t0
        with self._faults_lock:
            self._stages.append((stage, t0, dur, chunk))

    def _drain_worker_stages(self) -> None:
        """Re-record the worker's stage timings on the DRIVER thread, as
        spans under the scan's ``stream`` span (thread="worker")."""
        with self._faults_lock:
            got, self._stages[:] = list(self._stages), []
        for (stage, ts_ns, dur_ns, chunk) in got:
            _obs.record_interval("prefetch." + stage, ts_ns, dur_ns,
                                 parent=self._span_parent,
                                 qid=self._span_qid, chunk=chunk)

    def _work(self, it, prepare, n) -> None:
        try:
            while True:
                t0 = time.perf_counter_ns()
                with self._note("prefetch.source"):
                    item = next(it, _DONE)
                if item is _DONE:
                    break
                self._stage("source", t0, n)
                if self._stop.is_set():
                    return
                # bounded deterministic retry of the prepare step (the
                # ``prefetch`` transient seam): a transient slice/encode/
                # upload fault recovers in place; exhausted or
                # non-transient errors ride the queue and re-raise at the
                # driver's next fetch exactly like the inline path
                t0 = time.perf_counter_ns()
                with self._note("prefetch.prepare"):
                    payload = _F.with_retry(
                        "prefetch",
                        lambda i=item: _prepare_guarded(prepare, i),
                        record=self._sink)
                self._stage("prepare", t0, n)
                t0 = time.perf_counter_ns()
                with self._note("prefetch.backpressure"):
                    put = self._put((_ITEM, payload))
                self._stage("backpressure", t0, n)
                if not put:
                    return
                n += 1
            self._put((_DONE, None))
        except BaseException as exc:  # propagate to the driver, always
            self._put((_ERR, exc))

    # ------------------------------------------------------------ driver

    def next_chunk(self):
        """Next prepared payload, or None at end of stream. Re-raises a
        worker exception at the point the inline path would have raised
        it. The blocked wait is accumulated into ``stall_ns``."""
        if self._exhausted:
            return None
        t0 = time.perf_counter_ns()
        kind, value = self._q.get()
        self.stall_ns += time.perf_counter_ns() - t0
        self._drain_worker_faults()
        self._drain_worker_stages()
        if kind is _ITEM:
            return value
        self._exhausted = True
        if kind is _ERR:
            self.close()
            raise value
        return None

    def stall_ms(self) -> float:
        """Driver milliseconds spent blocked on the ring so far — the
        ``StreamEvent.prefetch_stall_ms`` evidence."""
        return self.stall_ns / 1e6

    def close(self) -> None:
        """Clean shutdown (idempotent): signal the worker, drain the
        queue so a backpressure-blocked put wakes, join the thread. Any
        worker-side recovery evidence still parked is re-recorded here
        so a fault on the FINAL chunk is never lost; so are the worker's
        parked stage timings."""
        self._stop.set()
        self._exhausted = True
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=60.0)
        self._drain_worker_faults()
        self._drain_worker_stages()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


def chunk_ring(it, prepare=None, depth=None, name="nds-prefetch", start=0):
    """The ONE ring constructor the drive loops use: a :class:`ChunkRing`
    when the (build-time) depth is positive, the inline pump otherwise.
    ``prepare`` runs on the worker thread — it must never host-read or
    open a span (``host-sync-in-prefetch-worker`` enforces this
    statically). ``start`` is the index of the first chunk ``it`` yields
    (the compiled drive loops convert chunk 0 themselves), so the stage
    spans carry the scan's own chunk numbers."""
    d = prefetch_depth() if depth is None else int(depth)
    if d <= 0:
        return _InlineRing(it, prepare, start=start)
    return ChunkRing(it, prepare, depth=d, name=name, start=start)
