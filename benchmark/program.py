# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""The system under test, and the only file of the benchmark that imports it.

From the program the benchmark takes the entry the Power Run times
(``Session.sql(text)`` then ``.collect()``: what ``power.run_one_query``
does when no output folder is given; the harness makes the two calls itself
because ``run_one_query`` throws the rows away) and the program's existing
counters and spans, read around each call exactly as ``nds_tpu/power.py``
reads them. Importing this module imports jax: ``run.py`` does so only after
the data children have ended.
"""

from __future__ import annotations

import contextlib
import gc
import sys
import time


class NoAccelerator(Exception):
    pass


class Program:
    def __init__(self, root: str, chips: int, allow_cpu: bool):
        if root not in sys.path:
            sys.path.insert(0, root)
        import jax
        self.jax = jax
        devices = jax.devices()
        self.device = devices[0]
        if self.device.platform == "cpu" and not allow_cpu:
            raise NoAccelerator(
                "JAX found no accelerator (platform cpu); the benchmark "
                "measures on the chip only")
        if len(devices) < chips:
            raise NoAccelerator(f"the cell asks for {chips} chip(s), JAX "
                                f"found {len(devices)}")
        self.count = len(devices)
        import nds_tpu  # noqa: F401  (turns x64 on before any array exists)
        from nds_tpu.engine import ops
        from nds_tpu.obs import export, trace
        from nds_tpu import listener, power
        self.ops, self.obs_trace, self.obs_export = ops, trace, export
        self.listener, self.power = listener, power
        ops.enable_compile_meter()
        self.session = None
        # the benchmark's own count of what JAX really compiled: with the
        # persistent cache on, a program the process has not seen is either
        # a cache hit (read from disk) or a miss (compiled by XLA). The
        # program's compile_ns() charges both.
        self.cache_events = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_listener(self._cache_event)

    def _cache_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_events["misses"] += 1

    # -- device ----------------------------------------------------------------

    def device_info(self) -> dict:
        return {"platform": self.device.platform,
                "kind": self.device.device_kind, "count": self.count}

    def memory_peak_bytes(self) -> int:
        """Peak on the fullest device; 0 where the backend keeps no
        allocator statistics (the CPU of a rehearsal)."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.jax.devices()[:self.count]]
        return int(max(peaks))

    # -- set-up ----------------------------------------------------------------

    def load(self, parquet_dir: str, use_decimal: bool) -> list:
        """``Session`` + the 24-table load, as ``nds_power.py`` does it.
        Returns the ``CreateTempView`` rows (app id, label, ms)."""
        from nds_tpu.engine.session import Session
        self.session = Session({})
        with contextlib.redirect_stdout(sys.stderr):
            return self.power.setup_tables(self.session, parquet_dir,
                                           "parquet", use_decimal, [])

    def replay_pending(self, text: str) -> bool:
        return self.session.replay_pending(
            self.power.strip_stream_markers(text))

    def compile_ns(self) -> int:
        return self.ops.compile_ns()

    # -- one statement -----------------------------------------------------------

    def execute(self, text: str) -> dict:
        """One timed call: sql() then collect(), counters read around it.
        The clock belongs to the caller; this returns what was counted."""
        ops, session = self.ops, self.session
        self.listener.drain_stream_events()
        self.obs_trace.drain_spans()
        syncs0, wait0 = ops.sync_count(), ops.sync_wait_ns()
        comp0, fetch0 = ops.compile_ns(), ops.fetch_bytes()
        hits0, miss0 = self.cache_events["hits"], self.cache_events["misses"]
        rec = {"ok": False, "rows": None, "error": None}
        t0 = time.perf_counter()
        try:
            result = session.sql(self.power.strip_stream_markers(text))
            rec["rows"] = result.collect()
            rec["ok"] = True
        except Exception as e:    # the loop goes on; the failure is counted
            rec["error"] = f"{type(e).__name__}: {e}"[:500]
        rec["call_ms"] = (time.perf_counter() - t0) * 1e3
        rec["host_syncs"] = ops.sync_count() - syncs0
        rec["sync_wait_ms"] = (ops.sync_wait_ns() - wait0) / 1e6
        rec["compile_ms"] = (ops.compile_ns() - comp0) / 1e6
        rec["fetch_bytes"] = ops.fetch_bytes() - fetch0
        rec["cache_hits"] = self.cache_events["hits"] - hits0
        rec["cache_misses"] = self.cache_events["misses"] - miss0
        rec["stream_scans"] = [self.listener.stream_event_json(e) for e in
                               self.listener.drain_stream_events()]
        rec["spans"] = self.obs_trace.drain_spans()
        return rec

    def phases(self, spans) -> dict:
        """{phase: ms} of one call's spans (``obs.export.rollup``)."""
        if not spans:
            return {}
        return dict(self.obs_export.rollup(spans).get("phases", {}))

    def annotation(self, name: str):
        return self.jax.profiler.TraceAnnotation(name)

    def profile(self, trace_dir: str):
        """The profiler over a block, without its Python tracer: that one
        records every Python call (a million events a pass) and slows the
        host it shares with the program. TraceAnnotations still land."""
        options = self.jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        return self.jax.profiler.trace(trace_dir, profiler_options=options)

    # -- after the window --------------------------------------------------------

    def free(self) -> None:
        if self.session is not None:
            self.session.catalog.clear()
            self.session = None
        gc.collect()
