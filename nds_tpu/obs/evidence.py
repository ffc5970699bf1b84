# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""The evidence of one statement: the counter block a driver reads around
one call, once.

``begin()`` clears the calling thread's leftovers (streamed-scan events
and trace records of whatever ran before: set-up must not charge
statement 1) and reads the thread's counters; ``end()`` reads them again
and drains what the call left behind. Everything is thread-scoped like
the counters themselves, so concurrent streams each read their own.
Fault events are drained at ``end()`` only: a recovery that fired between
two statements is evidence of the run and rides the next record.

Used by ``power.run_query_stream``, ``bench.py``'s child and
``tools/bench_compare.py``'s recorder. Zero host syncs: counter reads
and ring drains only.
"""

from __future__ import annotations

from nds_tpu.obs import compiles as _compiles
from nds_tpu.obs import export as _export
from nds_tpu.obs import trace as _trace


class StatementEvidence:
    """Counters read at :func:`begin`; :meth:`end` returns the deltas."""

    __slots__ = ("_syncs", "_wait", "_fetch", "_compile", "_builds")

    def __init__(self):
        from nds_tpu.engine import ops
        from nds_tpu.listener import drain_stream_events
        drain_stream_events()
        _trace.drain_spans()
        self._syncs = ops.sync_count()
        self._wait = ops.sync_wait_ns()
        self._fetch = ops.fetch_bytes()
        self._compile = ops.compile_ns()
        self._builds = _compiles.thread_sums()

    def end(self) -> dict:
        """``{"hostSyncs", "syncWaitMs", "fetchBytes", "compileMs"}`` (the
        counters' deltas, unrounded; ``compileMs`` is JAX's backend step:
        XLA compiles AND persistent-cache reads), the split of the call's
        program builds (:mod:`nds_tpu.obs.compiles`) ``"cacheReadMs"``
        (the reads inside ``compileMs``: ``compileMs - cacheReadMs`` is
        the compiling alone), ``"traceLowerMs"`` (tracing and lowering:
        host time beside ``compileMs``, that no cache saves),
        ``"cacheHits"`` / ``"cacheMisses"``, ``"streamEvents"`` (the drained
        :class:`StreamEvent` objects) with their JSON form
        ``"streamedScans"``, ``"faults"`` (drained ``FaultEvent``
        objects) with ``"faultEvents"``, ``"records"`` (the drained
        trace records, empty with tracing off) and their ``"rollup"``."""
        from nds_tpu.engine import ops
        from nds_tpu.engine.faults import (drain_fault_events,
                                           fault_event_json)
        from nds_tpu.listener import (drain_stream_events,
                                      stream_event_json)
        out = {"hostSyncs": ops.sync_count() - self._syncs,
               "syncWaitMs": (ops.sync_wait_ns() - self._wait) / 1e6,
               "fetchBytes": ops.fetch_bytes() - self._fetch,
               "compileMs": (ops.compile_ns() - self._compile) / 1e6}
        builds, b0 = _compiles.thread_sums(), self._builds
        out["cacheReadMs"] = builds["readMs"] - b0["readMs"]
        out["traceLowerMs"] = (builds["traceMs"] + builds["lowerMs"]
                               - b0["traceMs"] - b0["lowerMs"])
        out["cacheHits"] = builds["hits"] - b0["hits"]
        out["cacheMisses"] = builds["misses"] - b0["misses"]
        events = drain_stream_events()
        faults = drain_fault_events()
        records = _trace.drain_spans()
        out["streamEvents"] = events
        out["streamedScans"] = [stream_event_json(e) for e in events]
        out["faults"] = faults
        out["faultEvents"] = [fault_event_json(e) for e in faults]
        out["records"] = records
        out["rollup"] = _export.rollup(records)
        return out


def begin() -> StatementEvidence:
    """Start one statement's evidence window on the calling thread."""
    return StatementEvidence()
