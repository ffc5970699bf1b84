# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Query-trace observability layer.

The reference harness answers "where did the time go?" with Spark's event
log + listener bus; the TPU engine's only slice of that was the failure
listener (:mod:`nds_tpu.listener`) and raw sync counters. This package is
the rest: process-local, thread-scoped span tracing and per-phase metrics
over the planner, the streaming executor and the replay compiler, with a
hard contract — **tracing adds zero host syncs** (host-clock spans only;
device numbers are harvested exclusively at syncs the engine already
pays; ``tests/test_obs.py`` proves sync-count parity traced vs untraced).

* :mod:`nds_tpu.obs.trace` — nestable spans with sync/wait/compile
  counters bridged from :mod:`nds_tpu.engine.ops`, ring-buffer bounded
  and thread-scoped with an explicit drain (the
  ``drain_stream_events`` discipline). One statement is one tree
  (``sid`` / ``parent`` / ``qid`` on every record: ``statement`` ->
  ``parse`` / ``plan`` -> ``op.*`` / ``stream*`` / ``replay.*`` ->
  ``sync:*``); every live span is also a ``jax.profiler``
  ``TraceAnnotation`` (``nds:<name>``), and ``scoped`` / ``op`` put the
  device scope ``nds.<primitive>`` on the jitted primitives.
* :mod:`nds_tpu.obs.export` — Chrome ``trace_event`` export
  (``chrome://tracing`` / Perfetto) and the per-query rollup dict the
  drivers merge into their JSON summaries (per phase ``ms`` / ``count``
  / ``syncs`` / ``selfMs`` / ``syncWaitMs`` / ``compileMs`` /
  ``rootMs``).
* :mod:`nds_tpu.obs.evidence` — the counter block a driver reads around
  one statement (``begin()`` / ``end()``), once for all drivers.
* :mod:`nds_tpu.obs.ledger` — the campaign evidence ledger: the
  schema-versioned, flush-per-query, append-only JSONL record both
  drivers write and every post-hoc tool (``tools/bench_compare.py``,
  ``tools/trace_report.py``, ``tools/sync_profile.py``) reads, plus the
  campaign heartbeat thread.
* :mod:`nds_tpu.obs.metrics` — the live half: the process-local
  rolling-rollup registry (counters, gauges, mergeable fixed-bucket
  histograms with deterministic p50/p95/p99) fed only at existing
  drain/evidence points, snapshotted atomically to
  ``NDS_TPU_METRICS_FILE`` for the mid-run monitor
  (``tools/obs_live.py``) and carried in the ledger as ``metrics``
  records.
"""

from nds_tpu.obs.ledger import (LEDGER_VERSION, Heartbeat,  # noqa: F401
                                Ledger, LedgerData, LedgerError,
                                evidence_from_scans, load_ledger)
from nds_tpu.obs.metrics import (METRICS_VERSION, Registry,  # noqa: F401
                                 export_live, merge_hist_snapshots,
                                 quantile_from_buckets)
from nds_tpu.obs.metrics import default as default_registry  # noqa: F401
from nds_tpu.obs.trace import (NULL_SPAN, SpanRecord, SyncSite,  # noqa: F401
                               annotate, attach, drain_spans, on, op,
                               scoped, set_enabled, span, unattributed)
