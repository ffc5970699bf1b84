# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Runtime failure listener: the TPU-native stand-in for the reference's
Scala SparkListener + Py4J bridge (ref: nds/jvm_listener/src/main/scala/com/
nvidia/spark/rapids/listener/TaskFailureListener.scala:27-36 and
nds/python_listener/PythonListener.py:21-61).

The reference registers an in-JVM listener that captures every non-Success
task end reason and fans it out to Python callbacks. Here the execution
engine is in-process, so the bridge collapses to a process-local registry:
the engine's partition executor reports every retried/failed partition task
and every device runtime error (XLA/PJRT) to all registered listeners, which
feed the ``CompletedWithTaskFailures`` status taxonomy in
:mod:`nds_tpu.report`.
"""

from __future__ import annotations

import threading
import traceback
from collections import deque
from dataclasses import dataclass, field


@dataclass
class TaskFailure:
    """One failed/retried unit of work inside an otherwise-running query."""

    where: str        # e.g. "partition 3/8 of hash_join probe"
    reason: str       # exception text / device error
    fatal: bool = False


class FailureListener:
    """Accumulates task-failure reasons for one query run
    (ref: nds/python_listener/PythonListener.py:30-49)."""

    def __init__(self):
        self.failures: list[TaskFailure] = []
        self._lock = threading.Lock()

    def notify(self, where: str, reason: str, fatal: bool = False) -> None:
        with self._lock:
            self.failures.append(TaskFailure(where, reason, fatal))

    def register(self) -> "FailureListener":
        Manager.register(self)
        return self

    def unregister(self) -> None:
        Manager.unregister(self)


class Manager:
    """Fan-out registry (ref: nds/jvm_listener/.../Manager.scala:24-63).

    Listeners are scoped to the thread that registered them: concurrent
    in-process query streams (Throughput Run) each see only their own task
    failures. Failures raised from a thread with no scoped listener (e.g. a
    shared device-runtime callback thread) are recorded in
    ``Manager.unattributed`` for diagnostics but are NOT fanned out — one
    stream's device error must never mark every concurrent stream
    ``CompletedWithTaskFailures``.
    """

    _listeners: list[FailureListener] = []       # (owner_thread_id, listener) pairs
    _owners: list[int] = []
    _lock = threading.Lock()
    # bounded ring (newest kept): a failure storm on an unattributed
    # thread must evict O(1) per record, not O(n) list.pop(0)
    _UNATTRIBUTED_MAX = 1000
    unattributed: deque = deque(maxlen=_UNATTRIBUTED_MAX)

    @classmethod
    def register(cls, listener: FailureListener) -> None:
        with cls._lock:
            if listener not in cls._listeners:
                cls._listeners.append(listener)
                cls._owners.append(threading.get_ident())

    @classmethod
    def unregister(cls, listener: FailureListener) -> None:
        with cls._lock:
            if listener in cls._listeners:
                i = cls._listeners.index(listener)
                cls._listeners.pop(i)
                cls._owners.pop(i)

    @classmethod
    def notify_all(cls, where: str, reason: str, fatal: bool = False) -> None:
        me = threading.get_ident()
        with cls._lock:
            targets = [l for l, o in zip(cls._listeners, cls._owners)
                       if o == me]
            if not targets:
                cls.unattributed.append(TaskFailure(where, reason, fatal))
                return
        for l in targets:
            l.notify(where, reason, fatal)


@dataclass
class StreamEvent:
    """Accounting record for one >HBM streamed scan execution: which path
    served it (the compiled chunk pipeline or the eager chunk loop), how
    many chunks flowed, and how many host syncs the pipeline charged —
    the number the streamed-path sync budget (tests/test_synccount.py)
    pins. Drained per query by the drivers (power.py / bench.py) into the
    per-query summaries, next to the plain sync counters."""

    where: str                 # e.g. "store_sales"
    chunks: int
    syncs: int                 # host syncs charged while the scan executed
    path: str                  # "compiled" | "eager"
    reason: str = ""           # why the compiled path was not taken
    rows: int = -1             # survivor rows the scan kept (compiled
    #                            pipeline: the accumulator's final count —
    #                            the number tools/mem_audit_diff.py checks
    #                            against the static bound; -1 = unknown)
    partitions: int = 1        # grace-style partition count of the
    #                            compiled pipeline (1 = unpartitioned)
    part_rows: tuple = ()      # per-partition survivor counts (partition
    #                            order) — checked against the static
    #                            per-partition bounds by mem_audit_diff
    bytes_h2d: int = -1        # actual host->device prefetch bytes the
    #                            scan uploaded (encoded columnar: the
    #                            NARROW representation — compression wins
    #                            are measured here, not asserted; -1 =
    #                            unknown)
    shards: int = 1            # mesh shard count of the compiled pipeline
    #                            (NDS_TPU_STREAM_SHARDS; 1 = single-device)
    collectives: int = -1      # explicit ICI collective ops the sharded
    #                            pipeline issued (exchange all-to-alls x
    #                            chunks + the one cross-shard materialize
    #                            reduce) — the evidence exec_audit's
    #                            static collective budget is checked
    #                            against; -1 = unknown/unsharded
    bytes_ici: int = -1        # wire bytes those collectives moved
    #                            (encoded codes ride the exchange, so
    #                            compression shrinks this too)
    shard_rows: tuple = ()     # per-shard survivor counts (shard order,
    #                            summed over partitions) — checked against
    #                            mem_audit's per-shard bound
    prefetch_stall_ms: float = -1.0  # driver milliseconds BLOCKED on the
    #                            bounded prefetch ring (engine/prefetch)
    #                            across the whole drive; with the ring
    #                            off (NDS_TPU_PREFETCH_DEPTH=0) the
    #                            inline slice+encode+upload time instead
    #                            — the overlap win is this number
    #                            shrinking, measured per scan, never
    #                            asserted; -1 = unknown (old events)


_stream_tls = threading.local()


def record_stream_event(where: str, chunks: int, syncs: int, path: str,
                        reason: str = "", rows: int = -1,
                        partitions: int = 1, part_rows=(),
                        bytes_h2d: int = -1, shards: int = 1,
                        collectives: int = -1, bytes_ici: int = -1,
                        shard_rows=(),
                        prefetch_stall_ms: float = -1.0) -> None:
    """Engine-side hook (engine/stream.py, sql/planner.py): record how a
    streamed scan executed. Thread-scoped like the sync counters, so
    concurrent Throughput streams account their own pipelines."""
    lst = getattr(_stream_tls, "events", None)
    if lst is None:
        # deque(maxlen): diagnostics ring, never unbounded, O(1) evict
        lst = _stream_tls.events = deque(maxlen=1000)
    lst.append(StreamEvent(where, chunks, syncs, path, reason, rows,
                           partitions, tuple(part_rows), bytes_h2d,
                           shards, collectives, bytes_ici,
                           tuple(shard_rows), prefetch_stall_ms))


def drain_stream_events() -> list:
    """Return and clear the calling thread's streamed-scan events
    (oldest-first drain order; the ring keeps the newest 1000)."""
    lst = getattr(_stream_tls, "events", None)
    if not lst:
        return []
    out = list(lst)
    lst.clear()
    return out


def stream_event_json(e: StreamEvent) -> dict:
    """The ONE JSON shape of a StreamEvent in driver summaries
    (power.py ``streamedScans`` / bench.py per-query results) — optional
    fields appear only when meaningful, so existing consumers see no new
    keys on unpartitioned scans."""
    return {
        "table": e.where, "chunks": e.chunks, "syncs": e.syncs,
        "path": e.path,
        **({"rows": e.rows} if e.rows >= 0 else {}),
        **({"bytesH2d": e.bytes_h2d} if e.bytes_h2d >= 0 else {}),
        **({"partitions": e.partitions, "partRows": list(e.part_rows)}
           if e.partitions > 1 else {}),
        **({"shards": e.shards, "shardRows": list(e.shard_rows),
            "collectives": e.collectives, "bytesIci": e.bytes_ici}
           if e.shards > 1 else {}),
        **({"prefetchStallMs": round(e.prefetch_stall_ms, 3)}
           if e.prefetch_stall_ms >= 0 else {}),
        **({"reason": e.reason} if e.reason else {}),
    }


def stream_evidence(events) -> dict:
    """Aggregate drained :class:`StreamEvent` objects into the compact
    per-query evidence dict the campaign ledger records
    (:mod:`nds_tpu.obs.ledger`): total syncs/chunks, h2d upload and ICI
    wire bytes, partition/shard/collective counts, the compiled-vs-eager
    path split and the fallback reasons. Same aggregation as
    ``ledger.evidence_from_scans`` runs over the JSON shape — this is
    the in-process form for drivers that hold the live events."""
    from nds_tpu.obs.ledger import evidence_from_scans
    return evidence_from_scans([stream_event_json(e) for e in events])


def report_task_failure(where: str, exc: BaseException | str,
                        fatal: bool = False) -> None:
    """Engine-side hook: call on any retried partition task, capacity
    retry, kernel fallback, or device error. ``exc`` may be a caught
    exception or a plain reason string (for retries that raised nothing)."""
    if isinstance(exc, BaseException):
        reason = "".join(
            traceback.format_exception_only(type(exc), exc)).strip()
    else:
        reason = str(exc)
    Manager.notify_all(where, reason, fatal)
