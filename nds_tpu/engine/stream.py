# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Compiled streaming executor: sync-budgeted chunk pipeline for >HBM scans.

The eager chunk loop (``Planner._stream_join_parts``) re-plans the join
graph per chunk, and every chunk pays the per-chunk host syncs (join pair
sizing, adaptive compaction) — at SF10 that put 73 of 91 queries past the
<=6-sync budget the device-resident path holds (query37: 128 syncs). The
fix is the same one whole-query replay (engine/replay.py) applies to
device-resident queries, specialized to the streaming shape:

1. RECORD — run the join graph ONCE, eagerly, over the first padded chunk
   under ``ops.recording()`` + ``ops.stream_bounds()``. Stream-bounds mode
   forbids any chunk-data-dependent host decision (``StreamSyncError`` =>
   the query stays on the eager loop), so the only recorded host reads are
   chunk-INVARIANT dimension-side plans (dense key maps, key ranges) —
   which makes the log valid for every chunk, not just the recorded one.
2. COMPILE — re-run the same planner code under ``jax.jit`` with the
   chunk's device buffers (and every other part's columns) as arguments
   and ``ops.replaying(log)`` serving the recorded reads. Because
   ``ChunkedTable.padded_chunks`` pads every chunk (including the final
   partial one) to one fixed power-of-two capacity with a uniform pytree
   structure, the single traced program serves all chunks.
3. DRIVE — loop the chunks through that one executable with
   double-buffered host->device prefetch (chunk k+1 converts and uploads
   while chunk k's compute is in flight — dispatch is asynchronous, so
   issuing compute first overlaps the two), accumulating survivors into
   donated on-device buffers with a device-side running row count.
4. SYNC — one materializing host read at pipeline end fetches the
   survivor count plus the overflow flag. Overflow (a bound-sized pair
   bucket or the accumulator capacity ran out of room on some chunk) means
   rows were dropped on device: the result is discarded and the query
   re-runs through the eager loop, so streamability is only ever a
   performance property, never a correctness one.

The eager loop remains reachable as ``NDS_TPU_STREAM_EXEC=eager`` (escape
hatch) and as the automatic fallback for graphs that are not
chunk-invariant (cartesian layouts, exotic trace divergence).

MULTI-PASS streamed pipelines convert the formerly-eager shapes:

* **Subquery residuals** — a subquery nested in the graph's conjuncts is
  chunk-invariant once decorrelated, so the record phase plans the inner
  query FIRST (``Planner._residual_table`` under
  ``ops.suspend_stream_record()`` — the inner may use its own compiled
  pipeline; two pipelines chain with one materializing sync each) into a
  device-resident residual whose columns become ordinary jit operands of
  the per-chunk program. Cache hits re-plan the residuals per execution
  and shape-validate them against the compiled program.
* **Deferred outer joins** — an eligible LEFT join rides INTO the graph:
  ``_OuterProbe`` (chunked side preserved, ON keys = the probe side's
  PK) applies a sync-free per-chunk gather; ``_OuterBuild`` (chunked
  side null-introducing) emits per-dispatch matched pairs and ORs
  matched-build-row masks into an on-device unmatched-key accumulator —
  the outer extras emit once at materialize time, their counts riding
  the single materializing transfer.
* **Recorded chunk scalars** — ``ops.guarded_scalar_read`` replays a
  first-chunk host scalar for every chunk under a device-side staleness
  guard (mismatch ⇒ overflow flag ⇒ bit-for-bit eager rerun).

``NDS_TPU_STREAM_STRICT=1`` re-raises any record/trace failure that is
not a ``StreamSyncError``/``ReplayMismatch`` (the A/B tests and both
differential harnesses run strict); without it the fallback reason is
tagged with the exception class, so engine bugs stay auditable in
``streamedScans``.

Survivor accumulators are sized from the statement's PROVEN row bound
(the static memory model of ``nds_tpu/analysis/mem_audit.py``: schema PK
uniqueness + stream-fanout pair buckets), so a statement whose bound fits
the ``NDS_TPU_HBM_BYTES`` capacity model can never trip the overflow
rerun; unprovable or over-capacity bounds fall back to the legacy 2^23
guess.

PARTITIONED (grace-style) fan-out accumulation: a provable graph whose
whole-statement bound exceeds the capacity model — the q17-class fan-out
joins — is decomposed by join-key hash instead of falling back to the
legacy clamp. A second tiny jitted pass assigns every live chunk row a
partition id (multiplicative hash of the streamed slot's equi-join keys,
``mem_audit.stream_partition_keys``) and keeps a device-resident
partition histogram; the per-chunk join program gains the id vector and
a traced partition-id operand, masking the chunk to one partition before
the recorded graph runs (a lazy compact — same shapes, same replay log,
so ONE compiled program serves every (chunk, partition) pair). Each
partition accumulates into its OWN proof-sized accumulator
(``mem_audit.partition_row_bound`` — skew-conditional, ENFORCED by a
per-partition overflow flag), and the single materializing sync fetches
every partition's count + flag + the histogram in one transfer, so the
<=6-sync budget holds at any partition count. The partition count is
chosen statically from the proof (``mem_audit.choose_partitions``) and
joins the pipeline-cache key; partition count 1 is byte-for-byte
today's unpartitioned pipeline.

SHARDED execution (``NDS_TPU_STREAM_SHARDS`` > 1, with that many local
devices): the one compiled per-chunk program runs under ``shard_map``
over a 1-D device mesh — every padded chunk's row range splits
contiguously across the shards, dimension-side parts/operands/residuals
ride replicated (the broadcast-join side of the exchange choice), and
each shard accumulates survivors into its OWN proof-sized slice of the
donated accumulators (per-shard overflow flags enforce the per-shard
bound of ``mem_audit.shard_row_bound``). When the graph is ALSO
partitioned (fan-out joins — the case where a join's keys are not
co-partitioned with an arbitrary row split), a per-chunk EXCHANGE pass
hash-routes rows over ICI with the ``parallel/exchange.py`` all-to-all
primitives so each shard owns a key range (encoded codes ride the wire,
so the exchange moves the narrow representation); ``NDS_TPU_STREAM_
EXCHANGE=0`` keeps the local partition pass instead. ONE cross-shard
reduce (all-gather of per-shard counts + psum of overflow flags /
histogram / outer-build bitmaps) runs at the single materializing sync,
so the <=6-host-sync budget holds at any shard count and the explicit
collective count per pipeline pass is a static budget
(``exec_audit``), checked against the trace-time collective accounting
of ``parallel.exchange.collective_trace`` via ``StreamEvent.collectives``
/ ``bytes_ici``. Shard count 1 is byte-for-byte the single-device
pipeline.

Env knobs (all read at pipeline-BUILD time, never frozen at
import): ``NDS_TPU_STREAM_EXEC`` (compiled|eager),
``NDS_TPU_STREAM_ACC_ROWS`` (explicit hard accumulator ceiling / escape
hatch, applied per partition; unset = proof-sized),
``NDS_TPU_STREAM_FANOUT`` (ops.py: stream-mode join pair-bucket
allowance, default 4), ``NDS_TPU_HBM_BYTES`` (capacity model, default
16 GiB), ``NDS_TPU_STREAM_PARTITIONS`` (pin the partition count; unset =
proof-chosen, <=1 disables), ``NDS_TPU_STREAM_SKEW`` (hash-skew safety
factor of the per-partition and per-shard bounds, default 2),
``NDS_TPU_STREAM_SHARDS`` (mesh shard count; <=1 or too few local
devices = single-device), ``NDS_TPU_STREAM_MESH_AXIS`` (mesh axis name,
default ``shard``), ``NDS_TPU_STREAM_EXCHANGE`` (0 disables the
partitioned hash-exchange pass).

ASYNC INGEST (DESIGN.md "Async ingest"): all three drive loops and the
eager chunk loop pull chunks through the bounded prefetch ring of
``engine/prefetch.py`` (``NDS_TPU_PREFETCH_DEPTH``, default 2; 0 = the
inline pump, bit-for-bit the old loops): a worker thread runs the host
slice + narrow encode + async upload for upcoming chunks — sharded
runs place each shard's row slice on its own device inside the worker —
while the driver dispatches compute, and the driver's blocked-on-ring
time is measured per scan as ``StreamEvent.prefetch_stall_ms``. The
ring's extra live set (depth × chunk bytes) is priced off the admitting
capacity by every accumulator-sizing decision here and by
``mem_audit`` statically (the lockstep rule), and the depth joins the
pipeline-cache key. ``NDS_TPU_CHUNK_STORE`` points chunk production at
the persistent pre-encoded store (``io/chunk_store.py``): warm runs
mmap whole-table wire arrays instead of slicing arrow and re-planning
codecs.
"""

from __future__ import annotations

import logging
import os
import threading
import weakref

import jax
import jax.numpy as jnp

from nds_tpu.engine import exprs as _X
from nds_tpu.engine import faults as _F
from nds_tpu.engine import kernels as _K
from nds_tpu.engine import ops as E
from nds_tpu.engine import prefetch as _PF
from nds_tpu.engine.column import Column, slice_col_prefix
from nds_tpu.engine.table import DeviceTable
from nds_tpu.listener import record_stream_event
from nds_tpu.obs import metrics as _metrics
from nds_tpu.obs import trace as _obs

log = logging.getLogger(__name__)

# legacy survivor-accumulator row guess: the clamp applied only when the
# static memory proof cannot admit a bound (unprovable multiplicity, or a
# proven bound past the HBM capacity model). Provable statements size
# their accumulator from the proof instead (see _acc_row_budget), so a
# statement whose bound fits can never trip the overflow rerun.
_DEFAULT_ACC_ROWS = 1 << 23


def _acc_ceiling() -> int | None:
    """NDS_TPU_STREAM_ACC_ROWS: the explicit hard ceiling / escape hatch.
    Read at pipeline-BUILD time (not import) so tests and Throughput
    children that set it after import are honored."""
    env = os.environ.get("NDS_TPU_STREAM_ACC_ROWS")
    return int(env) if env else None


def _strict() -> bool:
    """NDS_TPU_STREAM_STRICT=1: re-raise any record/trace failure that is
    not a StreamSyncError/ReplayMismatch instead of converting it into an
    eager fallback — the mode both differential harnesses and the A/B
    tests run under, so a genuine engine bug can never hide behind the
    fallback's correctness guarantee."""
    return bool(os.environ.get("NDS_TPU_STREAM_STRICT"))


def _proved_plan(parts, keep, join_preds, where_conjuncts, sources, nrows):
    """``(proved_rows, k, part_keys)`` of the streamed graph, from the
    static memory model (analysis/mem_audit.py): the whole-statement
    survivor bound ``bucket(rows) x fanout^k`` (k = join batches with no
    PK-unique side), plus the chunk-side equi-key names a grace-style
    partition pass may hash on. Deferred outer joins (_OuterProbe /
    _OuterBuild) contribute their ON conjuncts and pristine sources —
    their PK-covered edges keep per-row multiplicity at <= 1 exactly like
    inner PK batches. ``(None, None, None)`` when unprovable (unconnected
    graph — a chunk-data-dependent cartesian layout the eager loop serves
    anyway)."""
    try:
        from nds_tpu.analysis.mem_audit import (stream_graph_fanout,
                                                stream_partition_keys,
                                                structural_row_bound)
        from nds_tpu.sql.planner import _OuterBuild, _OuterProbe
        part_cols = [{str(c).lower() for c in p.column_names}
                     for p in parts]
        srcs = list(sources)
        conj = list(join_preds) + list(where_conjuncts)
        for i, p in enumerate(parts):
            if isinstance(p, (_OuterProbe, _OuterBuild)):
                srcs[i] = p.src
                conj.extend(p.conjuncts)
        srcs = [s.lower() if isinstance(s, str) else None for s in srcs]
        k = stream_graph_fanout(part_cols, srcs, keep, conj)
        if k is None:
            return None, None, None
        return (structural_row_bound(int(nrows), k, E.stream_fanout()), k,
                stream_partition_keys(part_cols, srcs, keep, conj))
    except Exception:                    # never let the proof break a query
        return None, None, None


def _ring_bytes(chunk_nbytes: int) -> int:
    """Extra live bytes of the bounded prefetch ring: up to
    ``NDS_TPU_PREFETCH_DEPTH`` prepared chunks wait in the ring beyond
    the one the dispatch loop is consuming. Priced into every admission
    decision below (effective capacity = NDS_TPU_HBM_BYTES − ring) so
    turning the ring up can never size accumulators into memory the
    ring itself is holding — the lockstep twin of
    ``mem_audit.MemModel.ring_bytes``. Depth <= 0 (ring off) prices
    zero: bit-for-bit today's admission arithmetic."""
    return max(_PF.prefetch_depth(), 0) * max(int(chunk_nbytes), 0)


def _partition_plan(nrows, fan_k, part_keys, proved, row_bytes, n_chunks,
                    chunk_out_plen, ring_bytes=0):
    """``(n_partitions, per_partition_row_bound)`` for the pipeline being
    built: >1 only for a provable graph with chunk-side equi keys whose
    whole bound is past capacity (or when NDS_TPU_STREAM_PARTITIONS pins
    a count). Statically derived — it joins the pipeline-cache key via
    the env knobs + table rows. The partition TRIGGER mirrors
    mem_audit's rule shape: the accumulator the whole-graph proof would
    size — ``min(chunk-sum, structural)``, clamped by the env ceiling —
    is what gets compared against capacity (an explicit ceiling already
    pins the allocation, so capacity pressure never forces a partition
    pass under it). ``ring_bytes`` — the prefetch ring's live set —
    comes off the capacity side."""
    if fan_k is None or not part_keys or proved is None:
        return 1, None
    try:
        from nds_tpu.analysis.mem_audit import (choose_partitions,
                                                stream_partitions_env)
        forced = stream_partitions_env()
        bound = min(n_chunks * chunk_out_plen, proved)
        ceiling = _acc_ceiling()
        if ceiling is not None:
            bound = min(bound, ceiling)
        cap = max(_hbm_bytes() - ring_bytes, 1)
        need = bound * row_bytes > cap
        if not need and (forced is None or forced <= 1):
            return 1, None
        return choose_partitions(int(nrows), fan_k, E.stream_fanout(),
                                 row_bytes, cap, forced=forced)
    except Exception:
        return 1, None


def _acc_row_budget(n_chunks, chunk_out_plen, proved, row_bytes,
                    ring_bytes=0):
    """Rows the survivor accumulator is sized for. Always bounded by the
    per-chunk-bucket sum (each chunk contributes at most its output
    bucket); the proof tightens it. The env ceiling, when set, stays a
    hard clamp (overflow then reruns eagerly — correctness never depends
    on the proof); without one, a bound the capacity model cannot admit
    falls back to the legacy guess. ``ring_bytes`` (prefetch live set)
    shrinks the admitting capacity."""
    rows = n_chunks * chunk_out_plen
    if proved is not None:
        rows = min(rows, proved)
    ceiling = _acc_ceiling()
    if ceiling is not None:
        return min(rows, ceiling)
    if proved is None or \
            rows * row_bytes > max(_hbm_bytes() - ring_bytes, 1):
        return min(rows, _DEFAULT_ACC_ROWS)
    return rows


def _part_acc_budget(n_chunks, chunk_out_plen, part_bound, row_bytes,
                     n_parts, ring_bytes=0):
    """Per-partition accumulator rows. The per-partition proof admits the
    bound by construction (choose_partitions), but every partition's
    accumulator is live until the single materializing sync, so the
    TOTAL allocation is additionally clamped to the capacity model —
    past it, actual survivors beyond the clamp trip the per-partition
    overflow flag and rerun eagerly (a perf fallback, never a
    correctness one). The env ceiling stays a hard per-partition clamp;
    the prefetch ring's live set comes off the capacity side."""
    rows = n_chunks * chunk_out_plen
    if part_bound is not None:
        rows = min(rows, part_bound)
    share = max(_hbm_bytes() - ring_bytes, 1) // max(n_parts * row_bytes,
                                                     1)
    rows = min(rows, max(share, chunk_out_plen))
    ceiling = _acc_ceiling()
    if ceiling is not None:
        rows = min(rows, ceiling)
    return rows


def _hbm_bytes() -> int:
    try:
        from nds_tpu.analysis.mem_audit import hbm_capacity_bytes
        return hbm_capacity_bytes()
    except Exception:
        return 16 << 30


def _shard_plan(chunk_cap: int):
    """``(n_shards, mesh, axis)`` of the pipeline being built: >1 only
    when ``NDS_TPU_STREAM_SHARDS`` asks for a power-of-two count this
    process can serve (enough local devices, chunk capacity divisible).
    Statically derived — the count joins the pipeline-cache key via the
    env knob."""
    try:
        from nds_tpu.analysis.mem_audit import stream_shards_env
        from nds_tpu.parallel.exchange import stream_mesh, stream_mesh_axis
        n = stream_shards_env()
        if n <= 1 or chunk_cap % n or chunk_cap // n < 1:
            return 1, None, None
        mesh = stream_mesh(n)
        if mesh is None:
            return 1, None, None
        return n, mesh, stream_mesh_axis()
    except Exception:
        return 1, None, None

# compiled pipelines are cached across statements (a Power Run executes
# each query text 2-4 times); bounded FIFO, identity-validated on hit.
# Mutations take the lock: concurrent Throughput streams share the cache.
# A miss goes through the _PIPELINE_BUILDS singleflight registry
# (key -> Event of the thread currently compiling that shape): waiters
# block OFF-lock and take the winner's entry, so concurrent first sights
# of one shape cost exactly ONE compile — and the compile itself never
# runs under the lock (it would serialize every Throughput stream; the
# conc-audit `compile-under-lock` rule rejects the pattern statically).
_PIPELINE_CACHE: dict = {}
_PIPELINE_MAX = 64
_PIPELINE_LOCK = threading.Lock()
_PIPELINE_BUILDS: dict = {}
# per-shape successful-compile counts (guarded by _PIPELINE_LOCK): the
# evidence tools/conc_audit_diff.py's exactly-one-compile check reads.
_PIPELINE_BUILD_COUNTS: dict = {}


def pipeline_build_counts() -> dict:
    """Snapshot of per-shape compile counts since process start (or the
    last :func:`reset_pipeline_cache`)."""
    with _PIPELINE_LOCK:
        return dict(_PIPELINE_BUILD_COUNTS)


def reset_pipeline_cache() -> None:
    """Drop the pipeline cache and the compile counters (test/harness
    helper: a cold-cache differential needs a known-empty start)."""
    with _PIPELINE_LOCK:
        _PIPELINE_CACHE.clear()
        _PIPELINE_BUILD_COUNTS.clear()


class _NotStreamable(Exception):
    """The recorded join graph made a chunk-data-dependent host decision
    (or its trace diverged); the caller falls back to the eager loop."""


def _restore_counts(snapshot, checks_snapshot):
    """Drop DeviceCounts/deferred checks created by a record or trace
    attempt: their values belong to a discarded execution, and left in the
    pending list they would cost (or poison) a later batched resolve."""
    lst = E._pending_counts()
    lst[:] = [c for c in lst if any(c is s for s in snapshot)]
    E._sync_tls.checks = [
        (c, f) for c, f in (getattr(E._sync_tls, "checks", None) or [])
        if any(c is s for s in checks_snapshot)]


def _flatten_part(part: DeviceTable):
    """(spec, flat) for one non-streamed part: spec is static metadata
    (names, kinds, dictionaries, valid presence, logical count, physical
    length), flat the device buffers in spec order."""
    spec, flat = [], []
    nrows = E.count_int(part.nrows)   # resolved up front by the caller
    for name in part.column_names:
        c = part[name]
        spec.append((name, c.kind, c.dict_values, c.valid is not None,
                     c.enc))
        flat.append(c.data)
        if c.valid is not None:
            flat.append(c.valid)
    return (tuple(spec), nrows, part.plen), flat


def _rebuild_part(spec, flat):
    (cols_spec, nrows, plen) = spec
    cols, i = {}, 0
    for name, kind, dv, has_valid, enc in cols_spec:
        data = flat[i]
        i += 1
        valid = None
        if has_valid:
            valid = flat[i]
            i += 1
        cols[name] = Column(kind, data, valid, dv, enc)
    return DeviceTable(cols, nrows, plen=plen)


def _chunk_signature(chunk: DeviceTable, alias: str):
    """Static chunk metadata: aliased names (the per-chunk program sees the
    chunk as the planner's FROM-alias binding), kinds, dictionaries, and
    narrow encodings (host metadata baked into the trace, so a pipeline
    compiled for one encoding must never serve another)."""
    spec = []
    for name in chunk.column_names:
        c = chunk[name]
        aliased = f"{alias.lower()}.{name.split('.')[-1].lower()}"
        spec.append((aliased, c.kind, c.dict_values, c.enc))
    return tuple(spec)


_LOGICAL_WIDTHS = {"i32": 4, "date": 4, "bool": 1, "f64": 8, "str": 4}


def _logical_chunk_bytes(chunk_spec, chunk_cap, n_chunks) -> int:
    """Unencoded upload bytes the same padded chunks WOULD have moved
    (wide device widths + the validity byte) — the denominator of the
    compression win tools/trace_report.py prices against bytesH2d."""
    per_row = sum(_LOGICAL_WIDTHS.get(k, 8) + 1
                  for (_n, k, _dv, _en) in chunk_spec)
    return per_row * chunk_cap * max(n_chunks, 0)


def _hash_mix(h, data):
    """Fold one key column into the per-row partition hash (uint32) —
    THE partition/shard routing hash of the partition pass and the
    exchange pass. Dictionary codes hash as their int32 codes (the
    whole-table encoding makes them value-stable across chunks); floats
    hash their bit pattern. Multiplicative mixing — any chunk-row
    partitioning keeps the per-partition bound valid, the hash only
    evens the shares. The 32 mixed bits are split into DISJOINT route
    windows (low ``log2(P)`` bits pick the partition, the next
    ``log2(S)`` bits the shard); both env knobs are clamped so the two
    windows always fit: checked per statement (``hash-bits``) and at the
    clamp itself by ``analysis/num_audit.kernel_claim_checks``."""
    if jnp.issubdtype(data.dtype, jnp.floating):
        data = jax.lax.bitcast_convert_type(
            data, jnp.int64 if data.dtype.itemsize == 8 else jnp.int32)
    x = data.astype(jnp.int64)
    lo = (x & jnp.int64(0xffffffff)).astype(jnp.uint32)
    hi = ((x >> 32) & jnp.int64(0xffffffff)).astype(jnp.uint32)
    h = (h ^ lo) * jnp.uint32(2654435761)
    h = h ^ (h >> 16)
    h = (h ^ hi) * jnp.uint32(2246822519)
    return h ^ (h >> 13)


class StreamPipeline:
    """One compiled per-chunk program plus the metadata to drive it.

    ``n_partitions`` > 1 turns on grace-style partitioned accumulation:
    ``key_slots`` index the chunk's flattened buffers that the partition
    hash folds (the streamed slot's equi-join keys), the per-chunk
    program takes the per-row partition ids plus a traced partition-id
    scalar and masks the chunk before the recorded graph runs, and
    ``run`` keeps one proof-sized accumulator per partition — all
    fetched in the single materializing sync."""

    def __init__(self, chunk_spec, chunk_cap, part_specs, keep, log_entries,
                 operands, out_template, acc_cap, part_refs,
                 n_partitions=1, key_slots=(), outer_meta=(),
                 residuals=(), resid_specs=(), build_slots=(),
                 name_catalog=None, n_shards=1, mesh=None,
                 mesh_axis="shard", exchange=False, cap_ex=0,
                 param_nodes=(), param_tags=()):
        self.chunk_spec = chunk_spec      # ((aliased name, kind, dict), ...)
        self.chunk_cap = chunk_cap
        self.part_specs = part_specs      # specs of non-streamed parts
        self.keep = keep
        self.log = log_entries
        self.operands = operands
        self.out_template = out_template  # (names, kinds, dicts, valided)
        self.acc_cap = acc_cap
        # weakrefs to the part buffers, compared by identity on cache hit:
        # a dead ref or different object is a miss (bare id() ints could
        # collide after address reuse), and weakrefs don't pin dropped
        # tables' device memory for the cache entry's lifetime
        self.part_refs = part_refs
        self.n_partitions = n_partitions
        self.key_slots = tuple(key_slots)
        # multi-pass streaming metadata: per non-keep part, None or the
        # deferred-outer-join marker ("probe"/"build", condition AST,
        # conjunct ASTs, src); subquery residuals as (registry key,
        # replan payload) plus their flattened specs (validated against a
        # fresh replan on every cache hit); build_slots index the
        # part_specs whose unmatched-key bitmaps the accumulator carries
        self.outer_meta = tuple(outer_meta)
        self.residuals = tuple(residuals)
        self.resid_specs = tuple(resid_specs)
        self.build_slots = tuple(build_slots)
        self.name_catalog = dict(name_catalog or {})
        # sharded execution: the per-chunk program runs under shard_map
        # over this 1-D local-device mesh; acc_cap is then the PER-SHARD
        # accumulator capacity. ``exchange`` turns on the per-chunk
        # hash-exchange pass (partitioned graphs), with ``cap_ex`` the
        # per-(source shard, destination) bucket capacity.
        self.n_shards = n_shards
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        self.exchange = exchange
        self.cap_ex = cap_ex
        # per-shard physical chunk length the compiled program sees
        self.body_plen = chunk_cap if n_shards == 1 else \
            (n_shards * cap_ex if exchange else chunk_cap // n_shards)
        # parameter binding (DESIGN.md "Parameterized plans"): the build
        # statement's audited-bindable Literal AST nodes, kept alive here
        # so their id()s stay stable for the compiled program's lifetime.
        # At dispatch, each execution's literal VALUES ride as extra jit
        # operands appended after ``operands``; the traced body peels
        # them off and installs the exprs.param_binding the planner's
        # Literal arm consults. Slot ORDER is the cache key's slot
        # signature order — a hit is guaranteed to agree.
        self.param_nodes = tuple(param_nodes)
        self.param_tags = tuple(param_tags)
        self.jitted = None
        self._pid_jit = None
        self._exch_jit = None
        self._reduce_jit = None
        # explicit-collective accounting per compiled program, captured
        # at trace time (parallel.exchange.collective_trace) on the first
        # dispatch — the runtime evidence of the static collective budget
        self.coll_chunk = None
        self.coll_exchange = None
        self.coll_reduce = None
        # first jitted dispatch traces+compiles the per-chunk program;
        # the trace layer labels that dispatch "stream.compile"
        self.traced_once = False

    # ------------------------------------------------------------- compile

    def compile(self, join_preds, where_conjuncts, sources):
        from nds_tpu.sql.planner import Planner, _OuterBuild, _OuterProbe
        chunk_spec, chunk_cap = self.chunk_spec, self.chunk_cap
        part_specs, keep = self.part_specs, self.keep
        rec_log, operands = self.log, self.operands
        names, kinds, dicts, valided, dtypes, encs = self.out_template
        acc_cap = self.acc_cap
        base_sources = list(sources)
        n_partitions, key_slots = self.n_partitions, self.key_slots
        outer_meta = self.outer_meta
        residual_keys = tuple(k for (k, _p) in self.residuals)
        resid_specs = self.resid_specs
        n_builds = len(self.build_slots)
        name_cat = self.name_catalog
        param_nodes, param_tags = self.param_nodes, self.param_tags
        n_params = len(param_nodes)

        body_plen = self.body_plen

        @_obs.scoped("stream.chunk")
        def traced(chunk_flat, n_dev, parts_flat, ops_flat, acc,
                   resid_flat, pids=None, part_id=None, live=None):
            acc_datas, acc_valids, acc_n, acc_ovf, acc_outer = acc
            cols, i = {}, 0
            for (aname, kind, dv, cenc) in chunk_spec:
                cols[aname] = Column(kind, chunk_flat[i], chunk_flat[i + 1],
                                     dv, cenc)
                i += 2
            chunk = DeviceTable(cols, E.DeviceCount(n_dev, body_plen),
                                plen=body_plen)
            mask = live
            if pids is not None:
                pm = pids == part_id
                mask = pm if mask is None else (mask & pm)
            if mask is not None:
                # partition/exchange mask BEFORE the recorded graph: a
                # lazy compact keeps the chunk's physical shape and bound
                # (plen=body_plen), so the recorded host-read log stays
                # valid for every (chunk, partition, shard) combination.
                # Under its own stream-bounds region: at production chunk
                # sizes (plen > NDS_TPU_LAZY_SHRINK_ROWS) compact_table's
                # adaptive resolve would otherwise host-sync on a tracer
                # and silently divert the whole pipeline to eager
                with E.stream_bounds():
                    chunk = E.compact_table(chunk, mask)
            sub, pi = [], 0
            for j in range(len(part_specs) + 1):
                if j == keep:
                    sub.append(chunk)
                    continue
                t = _rebuild_part(part_specs[pi], parts_flat[pi])
                meta = outer_meta[pi] if pi < len(outer_meta) else None
                if meta is not None:
                    mk, mcond, mconjs, msrc = meta
                    t = (_OuterProbe if mk == "probe" else _OuterBuild)(
                        t, mcond, list(mconjs), msrc)
                sub.append(t)
                pi += 1
            # a fresh planner with an EMPTY catalog: the per-chunk program
            # must close over no device-resident state (a cached pipeline
            # would pin it for process lifetime). Subquery residuals are
            # pre-planned DEVICE OPERANDS: the registry is seeded from the
            # pipeline's residual arguments, so the subquery eval arms
            # consume them without ever touching a catalog
            pl = Planner({}, base_tables=set())
            pl.name_catalog = name_cat
            for rkey, rspec, rflat in zip(residual_keys, resid_specs,
                                          resid_flat):
                pl._subquery_residuals[rkey] = (
                    None, _rebuild_part(rspec, rflat))
            # audited-bindable literal operands ride at the END of
            # ops_flat (appended per execution by run); peel them off so
            # the replay log sees exactly its recorded operand count, and
            # install the binding the planner's Literal arm consults —
            # the bound conjuncts then trace against operand Columns
            # instead of baking this execution's values as constants
            bindings = {}
            if n_params:
                params = ops_flat[-n_params:]
                ops_flat = ops_flat[:-n_params]
                bindings = {id(nd): (tag, v) for nd, tag, v
                            in zip(param_nodes, param_tags, params)}
            with _X.param_binding(bindings):
                with E.replaying(rec_log, ops_flat):
                    with E.stream_bounds() as sb:
                        with E.outer_match_collector() as omc:
                            out = pl._join_parts(sub, list(join_preds),
                                                 list(where_conjuncts),
                                                 list(base_sources))
                        flags = list(sb.flags)
                        matched = list(omc.masks)
            if list(out.column_names) != list(names):
                raise E.ReplayMismatch(
                    "streamed trace produced a different output schema "
                    "than the recording")
            if len(matched) != n_builds:
                raise E.ReplayMismatch(
                    "streamed trace registered a different outer-build "
                    "mask count than the recording")
            out_n = E.count_arr(out.nrows)
            live = jnp.arange(out.plen) < out_n
            pos = jnp.where(live, acc_n + jnp.arange(out.plen), acc_cap)
            new_datas, new_valids = [], []
            for j, n in enumerate(names):
                c = out[n]
                new_datas.append(
                    acc_datas[j].at[pos].set(c.data, mode="drop"))
                if valided[j]:
                    new_valids.append(
                        acc_valids[j].at[pos].set(c.valid_mask(),
                                                  mode="drop"))
                else:
                    new_valids.append(acc_valids[j])
            new_n = acc_n + out_n
            ovf = acc_ovf | (new_n > acc_cap)
            for f in flags:
                ovf = ovf | f
            new_outer = tuple(b | m for b, m in zip(acc_outer, matched))
            return (tuple(new_datas), tuple(new_valids), new_n, ovf,
                    new_outer)

        # donate the accumulators: the pipeline's working set stays
        # (chunk in flight) + (chunk uploading) + ONE accumulator copy
        # per partition (the partition mask routes each dispatch to its
        # own accumulator, donated through)
        if self.n_shards == 1:
            self.jitted = jax.jit(traced, donate_argnums=(4,))

            if n_partitions > 1:
                P = n_partitions

                @_obs.scoped("stream.partition")
                def pid_fn(chunk_flat, n_dev, hist):
                    h = jnp.full((chunk_cap,), 2166136261, dtype=jnp.uint32)
                    for s in key_slots:
                        h = _hash_mix(h, chunk_flat[s])
                    pids = (h & jnp.uint32(P - 1)).astype(jnp.int32)
                    live = jnp.arange(chunk_cap) < n_dev
                    counts = jnp.bincount(jnp.where(live, pids, P),
                                          length=P + 1)[:P]
                    return pids, hist + counts.astype(hist.dtype)

                # the extra jitted partition pass: per-row partition ids +
                # the device-resident input histogram (donated through) —
                # no host syncs anywhere in it
                self._pid_jit = jax.jit(pid_fn, donate_argnums=(2,))
            return self

        # ---- sharded compile: the SAME traced body under shard_map ----
        from jax.sharding import PartitionSpec as PSpec
        from nds_tpu.parallel.exchange import shard_map_compat
        S, axis = self.n_shards, self.mesh_axis
        shard_plen = body_plen
        contiguous = not self.exchange
        row, rep = PSpec(axis), PSpec()

        def shard_body(chunk_flat, n_dev, parts_flat, ops_flat, acc,
                       resid_flat, pids, part_id, live):
            # contiguous row split: shard s owns rows [s*plen, (s+1)*plen)
            # of the chunk, so its live count derives from the global one
            # (no collective). Exchanged chunks carry liveness in ``live``
            # instead — every physical slot is in range, the mask decides.
            if contiguous:
                s = jax.lax.axis_index(axis).astype(jnp.int64)
                n_local = jnp.clip(n_dev - s * shard_plen, 0, shard_plen)
            else:
                n_local = jnp.asarray(shard_plen, dtype=jnp.int64)
            return traced(chunk_flat, n_local, parts_flat, ops_flat, acc,
                          resid_flat, pids, part_id, live)

        # accumulators are row-sharded (each shard scatters into its own
        # acc_cap slice); un-valided columns keep their replicated scalar
        # placeholder. Parts/operands/residuals ride replicated — the
        # broadcast-join side of the exchange choice.
        acc_spec = (tuple(row for _ in names),
                    tuple(row if v else rep for v in valided),
                    row, row, tuple(row for _ in self.build_slots))
        in_specs = (row, rep, rep, rep, acc_spec, rep, row, rep, row)
        sm = shard_map_compat(shard_body, self.mesh, in_specs, acc_spec)
        self.jitted = jax.jit(sm, donate_argnums=(4,))

        if self.exchange:
            self._exch_jit = self._make_exchange()
        elif n_partitions > 1:
            P = n_partitions

            @_obs.scoped("stream.partition")
            def pid_fn(chunk_flat, n_dev, hist):
                s = jax.lax.axis_index(axis).astype(jnp.int64)
                n_local = jnp.clip(n_dev - s * shard_plen, 0, shard_plen)
                h = jnp.full((shard_plen,), 2166136261, dtype=jnp.uint32)
                for ks in key_slots:
                    h = _hash_mix(h, chunk_flat[ks])
                pids = (h & jnp.uint32(P - 1)).astype(jnp.int32)
                live = jnp.arange(shard_plen) < n_local
                counts = jnp.bincount(jnp.where(live, pids, P),
                                      length=P + 1)[:P]
                return pids, hist + counts.astype(hist.dtype).reshape(
                    hist.shape)

            sm_pid = shard_map_compat(pid_fn, self.mesh,
                                      (row, rep, row), (row, row))
            self._pid_jit = jax.jit(sm_pid, donate_argnums=(2,))
        self._reduce_jit = self._make_reduce()
        return self

    def _make_exchange(self):
        """Jitted per-chunk hash-EXCHANGE pass of a sharded partitioned
        pipeline: each shard hashes its contiguous row slice on the
        graph's equi keys (the same hash the partition ids use), packs
        rows into per-destination-shard buckets, and the
        ``parallel/exchange.py`` all-to-all routes them so every shard
        owns a key range — the repartition a join needs when its keys
        are not co-partitioned with the arbitrary upload split. Returns
        the exchanged buffers + validity + partition ids + the updated
        per-shard histogram and overflow flag (a bucket past ``cap_ex``
        drops rows on device ⇒ the flag forces the eager rerun). No host
        syncs anywhere in it; its collectives are counted at trace time
        against the static budget."""
        from jax.sharding import PartitionSpec as PSpec
        from nds_tpu.parallel.exchange import (all_to_all_exchange,
                                               shard_map_compat)
        S, P = self.n_shards, self.n_partitions
        axis = self.mesh_axis
        shard_plen = self.chunk_cap // S
        cap_ex = self.cap_ex
        key_slots = self.key_slots
        pshift = max(P.bit_length() - 1, 0)      # partition ids use the
        #                                          low bits; shard routing
        #                                          the next log2(S) bits

        @_obs.scoped("stream.exchange")
        def exch_body(chunk_flat, n_dev, hist, ovf):
            s = jax.lax.axis_index(axis).astype(jnp.int64)
            n_local = jnp.clip(n_dev - s * shard_plen, 0, shard_plen)
            alive = jnp.arange(shard_plen) < n_local
            h = jnp.full((shard_plen,), 2166136261, dtype=jnp.uint32)
            for ks in key_slots:
                h = _hash_mix(h, chunk_flat[ks])
            pids = (h & jnp.uint32(P - 1)).astype(jnp.int32)
            hist = hist + jnp.bincount(jnp.where(alive, pids, P),
                                       length=P + 1)[:P].astype(
                hist.dtype).reshape(hist.shape)
            dest = jnp.where(
                alive,
                ((h >> pshift) & jnp.uint32(S - 1)).astype(jnp.int32),
                jnp.int32(S))                    # dead rows route past S
            order = jnp.argsort(dest)
            sd = jnp.take(dest, order)
            first = jnp.searchsorted(sd, sd, side="left")
            pos = jnp.arange(shard_plen) - first
            fits = (pos < cap_ex) & (sd < S)
            counts = jax.ops.segment_sum(
                (sd < S).astype(jnp.int32), sd, num_segments=S + 1)[:S]
            over = jnp.any(counts > cap_ex)
            valid = jnp.zeros((S, cap_ex), dtype=bool).at[sd, pos].set(
                fits, mode="drop")
            bufs = {}
            for i, buf in enumerate(chunk_flat):
                if buf is None:
                    continue
                v = jnp.take(buf, order)
                bufs[str(i)] = jnp.zeros(
                    (S, cap_ex), dtype=buf.dtype).at[sd, pos].set(
                    jnp.where(fits, v, jnp.zeros((), dtype=buf.dtype)),
                    mode="drop")
            pv = jnp.take(pids, order)
            bufs["pids"] = jnp.zeros(
                (S, cap_ex), dtype=pids.dtype).at[sd, pos].set(
                jnp.where(fits, pv, jnp.zeros((), dtype=pids.dtype)),
                mode="drop")
            ex, vex = all_to_all_exchange(bufs, valid, axis)
            out_flat = tuple(
                ex[str(i)].reshape(-1) if b is not None else None
                for i, b in enumerate(chunk_flat))
            return (out_flat, vex.reshape(-1), ex["pids"].reshape(-1),
                    hist, ovf | over.reshape(ovf.shape))

        row, rep = PSpec(axis), PSpec()
        sm = shard_map_compat(exch_body, self.mesh,
                              (row, rep, row, row),
                              (row, row, row, row, row))
        return jax.jit(sm, donate_argnums=(2, 3))

    def _make_reduce(self):
        """THE one cross-shard reduce of a sharded pipeline, fused at the
        single materializing sync: all-gather of per-shard survivor
        counts, psum of the per-shard overflow flags and the partition
        histogram, and a psum-OR of each outer-build bitmap (build rows
        matched by ANY shard of ANY partition are matched) — replicated
        outputs, so the following host fetch is one plain transfer. Its
        collectives are counted at trace time against the static
        budget."""
        from jax.sharding import PartitionSpec as PSpec
        from nds_tpu.parallel.exchange import (all_gather_counted,
                                               psum_counted,
                                               shard_map_compat)
        axis = self.mesh_axis
        build_meta = [(self.part_specs[s][1], self.part_specs[s][2])
                      for s in self.build_slots]

        @_obs.scoped("stream.reduce")
        def body(ns, flags, hist, *bitmaps):
            counts = all_gather_counted(ns, axis, tiled=True)     # (S, P)
            ovf = psum_counted(flags.astype(jnp.int32), axis)[0]  # (P,)
            hist_tot = psum_counted(hist, axis)[0]                # (P,)
            outs = [counts, ovf, hist_tot]
            for (n_live, plen), bm in zip(build_meta, bitmaps):
                matched = psum_counted(bm.astype(jnp.int32),
                                       axis)[0] > 0               # (plen,)
                miss = ~matched & (jnp.arange(plen) < n_live)
                outs.append(miss)
                outs.append(jnp.sum(miss))
            return tuple(outs)

        row, rep = PSpec(axis), PSpec()
        sm = shard_map_compat(
            body, self.mesh,
            (row, row, row) + tuple(row for _ in build_meta),
            tuple(rep for _ in range(3 + 2 * len(build_meta))))
        return jax.jit(sm)

    # ---------------------------------------------------------------- run

    def _flatten_chunk(self, chunk: DeviceTable):
        flat = []
        for name in chunk.column_names:
            c = chunk[name]
            flat.append(c.data)
            flat.append(c.valid)
        return tuple(flat)

    def _prepare_chunk(self, chunk: DeviceTable):
        """The per-chunk host work the prefetch ring runs OFF the driver
        thread: flatten the padded chunk's buffers (the jnp conversion
        inside ``padded_chunks`` already queued the async upload), stamp
        the live count, and account the actual h2d bytes. NO host reads,
        NO spans — the ``host-sync-in-prefetch-worker`` contract (padded
        chunks carry a plain-int live count, so no DeviceCount resolve
        is ever needed here)."""
        _F.fault_point("device-put")       # upload seam (transient;
        #                                    recovered by the prefetch
        #                                    ring's bounded retry)
        flat = self._flatten_chunk(chunk)
        n_dev = jnp.asarray(int(chunk.nrows), dtype=jnp.int64)
        h2d = sum(int(x.nbytes) for x in flat if x is not None)
        return flat, n_dev, h2d

    def _prepare_chunk_sharded(self, chunk: DeviceTable):
        """Sharded twin of :meth:`_prepare_chunk`: additionally places
        each shard's row slice on its own device (row-sharded
        ``device_put``) INSIDE the worker, so the h2d uploads fan out
        across the mesh off the driver thread instead of funneling
        through one inline upload."""
        from jax.sharding import NamedSharding, PartitionSpec as PSpec
        _F.fault_point("device-put")
        row = NamedSharding(self.mesh, PSpec(self.mesh_axis))
        flat = self._flatten_chunk(chunk)
        n_dev = jnp.asarray(int(chunk.nrows), dtype=jnp.int64)
        h2d = sum(int(x.nbytes) for x in flat if x is not None)
        flat = tuple(None if x is None else jax.device_put(x, row)
                     for x in flat)
        return flat, n_dev, h2d

    def init_acc(self):
        names, kinds, dicts, valided, dtypes, encs = self.out_template
        if self.n_shards > 1:
            return self._init_acc_sharded()
        datas, valids = [], []
        for j, dtype in enumerate(dtypes):
            datas.append(jnp.zeros(self.acc_cap, dtype=dtype))
            valids.append(jnp.zeros(self.acc_cap, dtype=bool)
                          if valided[j] else jnp.zeros((), dtype=bool))
        outer = tuple(jnp.zeros(self.part_specs[s][2], dtype=bool)
                      for s in self.build_slots)
        return (tuple(datas), tuple(valids),
                jnp.asarray(0, dtype=jnp.int64), jnp.asarray(False), outer)

    def _init_acc_sharded(self):
        """Sharded accumulators: every array is row-sharded over the
        mesh, so each shard owns its ``acc_cap`` slice (datas), its count
        and overflow slot, and its outer-build bitmap row — donated
        through every dispatch like the single-device accumulator."""
        from jax.sharding import NamedSharding, PartitionSpec as PSpec
        names, kinds, dicts, valided, dtypes, encs = self.out_template
        S = self.n_shards
        row = NamedSharding(self.mesh, PSpec(self.mesh_axis))
        rep = NamedSharding(self.mesh, PSpec())
        datas, valids = [], []
        for j, dtype in enumerate(dtypes):
            datas.append(jax.device_put(
                jnp.zeros(S * self.acc_cap, dtype=dtype), row))
            valids.append(jax.device_put(
                jnp.zeros(S * self.acc_cap, dtype=bool), row)
                if valided[j]
                else jax.device_put(jnp.zeros((), dtype=bool), rep))
        outer = tuple(jax.device_put(
            jnp.zeros((S, self.part_specs[s][2]), dtype=bool), row)
            for s in self.build_slots)
        return (tuple(datas), tuple(valids),
                jax.device_put(jnp.zeros((S,), dtype=jnp.int64), row),
                jax.device_put(jnp.zeros((S,), dtype=bool), row), outer)

    def _outer_miss(self, bitmaps):
        """(miss mask, device miss count) per outer-build slot: build
        rows no dispatch matched — the outer extras. The counts ride the
        single materializing transfer; the masks stay on device for the
        extras gather."""
        out = []
        for slot, bm in zip(self.build_slots, bitmaps):
            _spec, n_live, plen = self.part_specs[slot]
            miss = ~bm & (jnp.arange(plen) < n_live)
            out.append((miss, jnp.sum(miss)))
        return out

    def run(self, chunks, first_chunk, parts_flat, resid_flat=(),
            params=()):
        """Drive every chunk through the compiled program; returns
        ``(survivor DeviceTable | None-on-overflow, n_chunks, evidence)``
        (overflow => the caller re-runs eagerly). ``evidence`` carries the
        partition counts of a partitioned run and the outer-extras
        masks/counts of deferred outer-build joins. ``chunks`` continues
        AFTER ``first_chunk`` (already converted). ``params`` — THIS
        execution's bound-literal operand values, slot order (passed
        per call, never stored: concurrent cache-hit executions share
        the pipeline object)."""
        if self.n_shards > 1:
            return _run_sharded(self, chunks, first_chunk, parts_flat,
                                resid_flat, params)
        if self.n_partitions > 1:
            return self._run_partitioned(chunks, first_chunk, parts_flat,
                                         resid_flat, params)
        ops = self.operands + tuple(params)
        acc = self.init_acc()
        # bounded prefetch ring (engine/prefetch.py): a worker thread
        # runs the host slice + encode + async upload for upcoming
        # chunks while the driver below dispatches compute — depth 0
        # (NDS_TPU_PREFETCH_DEPTH=0) degrades to the inline pump, bit
        # for bit the old drive loop. The first chunk was already
        # converted by the record phase, so it prepares inline.
        ring = _PF.chunk_ring(chunks, prepare=self._prepare_chunk,
                              start=1)
        n_chunks = 0
        h2d = 0
        try:
            # the first chunk prepares INLINE (the record phase already
            # converted it): same bounded-retry policy as the ring's
            # worker, on the driver (the device-put transient seam)
            with _obs.span("prefetch.prepare", chunk=0):
                cur = _F.with_retry(
                    "device-put",
                    lambda: self._prepare_chunk(first_chunk))
            while cur is not None:
                flat, n_dev, nb = cur
                # actual host->device prefetch bytes (buffer metadata,
                # no sync): encoded columns upload their NARROW form
                h2d += nb
                # asynchronous dispatch: the compiled call returns
                # immediately, so the ring's conversion of upcoming
                # chunks overlaps this chunk's device compute. The first
                # dispatch of a fresh pipeline traces+compiles the
                # per-chunk program; the span names that cost so the
                # compile-vs-drive split is visible per chunk.
                phase = "stream.drive" if self.traced_once \
                    else "stream.compile"
                with _obs.span(phase, chunk=n_chunks):
                    acc = self.jitted(flat, n_dev, parts_flat, ops, acc,
                                      resid_flat)
                self.traced_once = True
                n_chunks += 1
                # stall span: driver time BLOCKED on the ring for the
                # next chunk (ring off: the inline slice+upload). Only
                # real fetches record a span, labeled with the chunk
                # they fetch; the end-of-stream probe drops its span.
                with _obs.span("stream.prefetch", chunk=n_chunks) as sp:
                    cur = ring.next_chunk()
                    if cur is None:
                        sp.drop()
            stall_ms = ring.stall_ms()
        finally:
            ring.close()
        datas, valids, n_dev, ovf, bitmaps = acc
        miss = self._outer_miss(bitmaps)

        def fetch():
            got = jax.device_get([n_dev, ovf] + [n for (_m, n) in miss])
            return (int(got[0]), bool(got[1]),
                    [int(x) for x in got[2:]])

        # THE one materializing sync of the pipeline (outer-extras counts
        # ride the same transfer)
        with _obs.span("stream.materialize", chunks=n_chunks):
            total, overflowed, extras_n = E.timed_read("stream_final",
                                                       fetch)
        evidence = {"h2d": h2d, "stall_ms": stall_ms,
                    "outer": [(slot, m, n) for (slot, (m, _nd), n)
                              in zip(self.build_slots, miss, extras_n)]}
        if overflowed:
            return None, n_chunks, evidence
        return self._slice_acc(datas, valids, total), n_chunks, evidence

    def _slice_acc(self, datas, valids, total):
        """Survivor prefix of one accumulator as a DeviceTable."""
        names, kinds, dicts, valided, dtypes, encs = self.out_template
        cap = E.bucket_len(total)
        cols = {}
        for j, n in enumerate(names):
            col = Column(kinds[j], datas[j],
                         valids[j] if valided[j] else None, dicts[j],
                         encs[j])
            cols[n] = slice_col_prefix(col, cap) if cap < self.acc_cap \
                else col
        return DeviceTable(cols, total, plen=min(cap, self.acc_cap))

    def _run_partitioned(self, chunks, first_chunk, parts_flat,
                         resid_flat=(), params=()):
        """Grace-style drive: each chunk uploads ONCE, the partition pass
        assigns row partition ids (histogram stays device-resident), and
        the one compiled program dispatches once per partition into that
        partition's own donated accumulator. Chunk-major order keeps the
        double-buffered prefetch; partition-major survivor order is
        row-order-independent downstream (joins/filters/aggregation
        distribute over union). ONE materializing sync fetches every
        partition's count + overflow flag + the input histogram (+ any
        outer-extras counts: per-partition unmatched-key bitmaps OR
        together first — a build row matched by ANY partition of ANY
        chunk is matched)."""
        P = self.n_partitions
        ops = self.operands + tuple(params)
        accs = [self.init_acc() for _ in range(P)]
        hist = jnp.zeros(P, dtype=jnp.int64)
        pid_consts = [jnp.asarray(p, dtype=jnp.int32) for p in range(P)]
        ring = _PF.chunk_ring(chunks, prepare=self._prepare_chunk,
                              start=1)
        n_chunks = 0
        h2d = 0
        try:
            with _obs.span("prefetch.prepare", chunk=0):
                cur = _F.with_retry(
                    "device-put",
                    lambda: self._prepare_chunk(first_chunk))
            while cur is not None:
                flat, n_dev, nb = cur
                h2d += nb
                with _obs.span("stream.partition", chunk=n_chunks,
                               partitions=P):
                    pids, hist = self._pid_jit(flat, n_dev, hist)
                for p in range(P):
                    phase = "stream.drive" if self.traced_once \
                        else "stream.compile"
                    with _obs.span(phase, chunk=n_chunks, part=p):
                        accs[p] = self.jitted(
                            flat, n_dev, parts_flat, ops, accs[p],
                            resid_flat, pids=pids, part_id=pid_consts[p])
                    self.traced_once = True
                n_chunks += 1
                with _obs.span("stream.prefetch", chunk=n_chunks) as sp:
                    cur = ring.next_chunk()
                    if cur is None:
                        sp.drop()
            stall_ms = ring.stall_ms()
        finally:
            ring.close()

        bitmaps = [accs[0][4][j] for j in range(len(self.build_slots))]
        for p in range(1, P):
            bitmaps = [b | accs[p][4][j] for j, b in enumerate(bitmaps)]
        miss = self._outer_miss(bitmaps)

        def fetch():
            got = jax.device_get([a[2] for a in accs]
                                 + [a[3] for a in accs] + [hist]
                                 + [n for (_m, n) in miss])
            return ([int(x) for x in got[:P]],
                    [bool(x) for x in got[P:2 * P]],
                    [int(x) for x in got[2 * P]],
                    [int(x) for x in got[2 * P + 1:]])

        # still THE one materializing sync: P counts + P flags + the
        # histogram (+ extras counts) ride one transfer
        with _obs.span("stream.materialize", chunks=n_chunks,
                       partitions=P):
            totals, overflowed, hist_host, extras_n = E.timed_read(
                "stream_final", fetch)
        evidence = {"partitions": P, "part_rows": tuple(totals),
                    "part_input": tuple(hist_host), "h2d": h2d,
                    "stall_ms": stall_ms,
                    "outer": [(slot, m, n) for (slot, (m, _nd), n)
                              in zip(self.build_slots, miss, extras_n)]}
        if any(overflowed):
            return None, n_chunks, evidence
        tables = [self._slice_acc(accs[p][0], accs[p][1], totals[p])
                  for p in range(P) if totals[p] > 0]
        if not tables:                   # every partition empty
            out = self._slice_acc(accs[0][0], accs[0][1], 0)
        elif len(tables) == 1:
            out = tables[0]
        else:
            # counts are host-known here, so the union costs no sync
            out = E.concat_tables(tables)
        return out, n_chunks, evidence


def _run_sharded(pipe, chunks, first_chunk, parts_flat, resid_flat=(),
                 params=()):
    """Mesh-sharded drive (any partition count): every chunk uploads
    ROW-SHARDED over the local-device mesh, dimension parts / replay
    operands / residuals ride replicated, and the one shard_map'd
    compiled program dispatches per partition into per-shard donated
    accumulators. Partitioned graphs route rows first — the hash-
    EXCHANGE pass (parallel/exchange.py all-to-alls, so each shard owns
    a key range) or the local partition pass under
    ``NDS_TPU_STREAM_EXCHANGE=0``. ONE cross-shard reduce at the single
    materializing sync fetches every (shard, partition) count, overflow
    flag, the histogram and any outer-extras — the <=6-sync budget holds
    at any shard count, and the explicit collectives are accounted at
    trace time against the static budget."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as PSpec
    from nds_tpu.parallel.exchange import collective_trace
    S, P = pipe.n_shards, pipe.n_partitions
    row = NamedSharding(pipe.mesh, PSpec(pipe.mesh_axis))
    rep = NamedSharding(pipe.mesh, PSpec())

    def put_rep(x):
        return None if x is None else jax.device_put(x, rep)

    parts_rep = tuple(tuple(put_rep(x) for x in p) for p in parts_flat)
    resid_rep = tuple(tuple(put_rep(x) for x in p) for p in resid_flat)
    # bound-literal operands ride replicated like the replay operands
    ops_rep = tuple(put_rep(x) for x in pipe.operands + tuple(params))
    accs = [pipe.init_acc() for _ in range(P)]
    hist = jax.device_put(jnp.zeros((S, P), dtype=jnp.int64), row)
    ex_ovf = jax.device_put(jnp.zeros((S,), dtype=bool), row)
    pid_consts = [jnp.asarray(p, dtype=jnp.int32) for p in range(P)]

    def first_traced(coll_attr, call):
        """Dispatch; capture the program's trace-time collective counts
        on its first (tracing) call."""
        if getattr(pipe, coll_attr) is None:
            with collective_trace() as ct:
                out = call()
            setattr(pipe, coll_attr, dict(ct.counts))
            return out
        return call()

    # sharded prefetch ring: the worker places each shard's row slice on
    # its OWN device (row-sharded device_put inside _prepare_chunk_
    # sharded), so the h2d bandwidth scales with the mesh instead of
    # funneling through one inline upload on the driver thread
    ring = _PF.chunk_ring(chunks, prepare=pipe._prepare_chunk_sharded,
                          start=1)
    n_chunks = 0
    h2d = 0
    try:
        with _obs.span("prefetch.prepare", chunk=0):
            cur = _F.with_retry(
                "device-put",
                lambda: pipe._prepare_chunk_sharded(first_chunk))
        while cur is not None:
            flat, n_dev, nb = cur
            h2d += nb
            pids = live = None
            if pipe.exchange:
                # collective-dispatch seam (degradable): an injected
                # exchange fault propagates to stream_execute, which
                # degrades the statement to the single-device eager
                # rerun and records the FaultEvent
                _F.fault_point("exchange")
                with _obs.span("stream.exchange", chunk=n_chunks,
                               shards=S, partitions=P):
                    flat, live, pids, hist, ex_ovf = first_traced(
                        "coll_exchange",
                        lambda f=flat, nd=n_dev, h=hist, o=ex_ovf:
                        pipe._exch_jit(f, nd, h, o))
            elif P > 1:
                with _obs.span("stream.partition", chunk=n_chunks,
                               partitions=P, shards=S):
                    pids, hist = pipe._pid_jit(flat, n_dev, hist)
            for p in range(P):
                phase = "stream.drive" if pipe.traced_once \
                    else "stream.compile"
                args = (flat, n_dev, parts_rep, ops_rep, accs[p],
                        resid_rep, pids,
                        pid_consts[p] if P > 1 else None, live)
                with _obs.span(phase, chunk=n_chunks, part=p):
                    accs[p] = first_traced(
                        "coll_chunk", lambda a=args: pipe.jitted(*a))
                pipe.traced_once = True
            n_chunks += 1
            with _obs.span("stream.prefetch", chunk=n_chunks) as sp:
                cur = ring.next_chunk()
                if cur is None:
                    sp.drop()
        stall_ms = ring.stall_ms()
    finally:
        ring.close()

    # one cross-shard reduce, one materializing transfer
    ns = jnp.stack([a[2] for a in accs], axis=1)          # (S, P)
    flags = jnp.stack([a[3] for a in accs], axis=1)       # (S, P)
    flags = flags | ex_ovf[:, None]
    bitmaps = []
    for j in range(len(pipe.build_slots)):
        bm = accs[0][4][j]
        for p in range(1, P):
            bm = bm | accs[p][4][j]
        bitmaps.append(bm)

    with _obs.span("stream.materialize", chunks=n_chunks, shards=S,
                   partitions=P):
        outs = first_traced("coll_reduce",
                            lambda: pipe._reduce_jit(ns, flags, hist,
                                                     *bitmaps))
        got = E.timed_read("stream_final",
                           lambda: jax.device_get(list(outs)))
    counts = np.asarray(got[0], dtype=np.int64)           # (S, P)
    ovf_host = [int(x) for x in np.asarray(got[1]).ravel()]
    hist_host = [int(x) for x in np.asarray(got[2]).ravel()]
    extras_pairs = list(zip(outs[3::2], [int(x) for x in got[4::2]]))

    def ops_of(c):
        return (c["a2a"] + c["psum"] + c["all_gather"]) if c else 0

    def bytes_of(c):
        return c["bytes"] if c else 0

    dispatches = n_chunks * P
    collectives = (ops_of(pipe.coll_chunk) * dispatches
                   + ops_of(pipe.coll_exchange) * n_chunks
                   + ops_of(pipe.coll_reduce))
    bytes_ici = (bytes_of(pipe.coll_chunk) * dispatches
                 + bytes_of(pipe.coll_exchange) * n_chunks
                 + bytes_of(pipe.coll_reduce))
    evidence = {"h2d": h2d, "shards": S, "stall_ms": stall_ms,
                "shard_rows": tuple(int(x) for x in counts.sum(axis=1)),
                "collectives": collectives, "bytes_ici": bytes_ici,
                "outer": [(slot, m, n) for (slot, (m, n)) in
                          zip(pipe.build_slots, extras_pairs)]}
    if P > 1:
        evidence["partitions"] = P
        evidence["part_rows"] = tuple(int(x) for x in counts.sum(axis=0))
        evidence["part_input"] = tuple(hist_host)
    if any(ovf_host):
        return None, n_chunks, evidence
    tables = [_slice_acc_sharded(pipe, accs[p][0], accs[p][1],
                                 counts[:, p])
              for p in range(P) if counts[:, p].sum() > 0]
    if not tables:                       # every shard of every partition
        out = _slice_acc_sharded(pipe, accs[0][0], accs[0][1],
                                 np.zeros(S, dtype=np.int64))
    elif len(tables) == 1:
        out = tables[0]
    else:
        # counts are host-known here, so the union costs no sync
        out = E.concat_tables(tables)
    return out, n_chunks, evidence


def _slice_acc_sharded(pipe, datas, valids, shard_counts):
    """Survivor rows of one sharded accumulator as a DeviceTable: shard
    ``s``'s survivors live at ``[s*acc_cap, s*acc_cap + count_s)`` of the
    row-sharded arrays — counts are host-known after the materializing
    transfer, so the gather index builds on host and the device gather
    costs no sync. Pad rows zero out, matching the zero-initialized
    accumulator padding of the single-device path."""
    import numpy as np
    names, kinds, dicts, valided, dtypes, encs = pipe.out_template
    counts = [int(c) for c in shard_counts]
    total = sum(counts)
    cap = E.bucket_len(total)
    idx_host = np.concatenate(
        [np.arange(c, dtype=np.int64) + s * pipe.acc_cap
         for s, c in enumerate(counts)] + [np.zeros(0, np.int64)])
    idx = jnp.asarray(np.concatenate(
        [idx_host, np.zeros(cap - total, np.int64)]))
    live = jnp.arange(cap) < total
    cols = {}
    for j, n in enumerate(names):
        d = jnp.take(datas[j], idx, mode="clip")
        d = jnp.where(live, d, jnp.zeros((), dtype=d.dtype))
        v = None
        if valided[j]:
            v = jnp.take(valids[j], idx, mode="clip") & live
        cols[n] = Column(kinds[j], d, v, dicts[j], encs[j])
    return DeviceTable(cols, total, plen=cap)


def _weak(x):
    """weakref.ref when the buffer supports it; a strong closure otherwise
    (plain ndarrays aren't weakref-able) — callers just call the ref."""
    try:
        return weakref.ref(x)
    except TypeError:
        return lambda obj=x: obj


def _dicts_equal(a, b) -> bool:
    import numpy as np
    if a is None or b is None:
        return a is b
    return a is b or np.array_equal(a, b)


def _param_bind_active() -> bool:
    """Parameter binding is ON by default (``NDS_TPU_PARAM_BIND=0`` is
    the escape hatch; the mode is a cache-key member)."""
    return os.environ.get("NDS_TPU_PARAM_BIND", "1") != "0"


def _param_slots(planner, parts, keep, where_conjuncts, chunk_spec):
    """Audited-bindable slots of THIS statement's WHERE conjuncts:
    ``((conjunct index, field path, typetag, Literal node), ...)`` in
    deterministic walk order. Ownership mirrors ``_build_pipeline``'s
    ``owned()`` exactly — ``planner._expr_tables`` owners == {keep} —
    so a slot can only come from a conjunct the planner evaluates
    purely over chunk columns in-trace. The classification rule itself
    (comparand positions, type tags, safe domains) is
    ``analysis/param_audit.conjunct_bind_slots`` — the ONE rule the
    static auditor proves corpus-wide and the diff harness locks."""
    from nds_tpu.analysis.param_audit import (conjunct_bind_slots,
                                              drift_active)
    names_keep = {nm for (nm, _k, _dv, _en) in chunk_spec}
    sub_cols = [names_keep if i == keep else set(p.column_names)
                for i, p in enumerate(parts)]
    all_cols = set().union(*sub_cols)
    drift = drift_active()
    slots = []
    for ci, c in enumerate(where_conjuncts):
        has_sub = planner._has_subquery(c)
        owned = False
        if not has_sub:
            tabs = planner._expr_tables(c, all_cols)
            owners = set()
            for p_i, pc in enumerate(sub_cols):
                for t in tabs:
                    if any(cc.startswith(t + ".") for cc in pc):
                        owners.add(p_i)
            owned = owners == {keep}
        for (path, node, tag) in conjunct_bind_slots(
                c, owned, has_sub, drift=drift):
            slots.append((ci, path, tag, node))
    return tuple(slots)


def _param_operands(bind_slots):
    """This execution's bound-literal operand values, slot order —
    device-typed scalars (a Python int would re-trace as a weak type)."""
    from nds_tpu.analysis.param_audit import slot_param_value
    out = []
    for (_ci, _path, tag, node) in bind_slots:
        v = slot_param_value(node.value, tag)
        out.append(jnp.asarray(
            v, dtype=jnp.float64 if tag == "f64" else jnp.int64))
    return tuple(out)


def _cache_key(alias, keep, join_preds, where_conjuncts, sources,
               part_infos, chunk_spec, chunk_cap, stream_rows, outer_meta,
               bind_slots=()):
    from nds_tpu.analysis.mem_audit import (stream_partitions_env,
                                            stream_shards_env,
                                            stream_skew_factor)
    from nds_tpu.analysis.param_audit import skeleton_conjunct_key
    from nds_tpu.engine.column import enc_key
    from nds_tpu.sql.parser import expr_key
    # audited-bindable conjuncts key on their template SKELETON (literal
    # values become typed placeholders): K parameter vectors of one
    # template collapse onto one entry, one compile. The slot signature
    # rides alongside — two statements only share an entry when their
    # bindable slots line up exactly (count, position, operand type).
    by_conj = {}
    for (ci, path, tag, node) in bind_slots:
        by_conj.setdefault(ci, []).append((path, node, tag))
    return (
        tuple(expr_key(c) for c in join_preds),
        tuple(skeleton_conjunct_key(c, by_conj[i]) if i in by_conj
              else expr_key(c)
              for i, c in enumerate(where_conjuncts)),
        tuple((ci, path, tag) for (ci, path, tag, _n) in bind_slots),
        # bind/drift mode are key members read AT KEY TIME (conc-audit
        # cache-key completeness): flipping either can never serve a
        # pipeline compiled under the other mode
        os.environ.get("NDS_TPU_PARAM_BIND", "1"),
        os.environ.get("NDS_TPU_PARAM_DRIFT"),
        keep, tuple(sources), alias.lower(), chunk_cap,
        tuple((n, k, enc_key(en)) for (n, k, _dv, en) in chunk_spec),
        tuple(((tuple((cn, ck, hv, enc_key(en))
                      for (cn, ck, _dv, hv, en) in spec[0]),
                spec[1], spec[2]))
              for (spec, _flat) in part_infos),
        # deferred outer joins are part of the compiled program's shape
        tuple((m[0], expr_key(m[1]), m[3]) if m else None
              for m in outer_meta),
        # accumulator-sizing knobs: a pipeline built under a different
        # ceiling/capacity/fanout/partitioning must not be reused (its
        # compiled acc shapes bake the old budget in), and the streamed
        # table's row count feeds both the proof and the static
        # partition count
        _acc_ceiling(), _hbm_bytes(), E.stream_fanout(),
        stream_partitions_env(), stream_skew_factor(), int(stream_rows),
        # the prefetch ring's depth shapes the admission arithmetic
        # (effective capacity = HBM − depth × chunk bytes), which sizes
        # the compiled accumulator shapes — a depth change must MISS
        _PF.prefetch_depth(),
        # sharded-execution knobs: a pipeline compiled for one mesh shape
        # (or exchange mode) must never serve another
        stream_shards_env(), os.environ.get("NDS_TPU_STREAM_EXCHANGE"),
        os.environ.get("NDS_TPU_STREAM_MESH_AXIS"),
        # the Pallas mode picks which segment implementation traces
        # into the chunk program
        _K._pallas_mode(),
        # read-at-use engine knobs reachable from the traced per-chunk
        # program (cache-key completeness, enforced statically by
        # analysis/conc_audit.py): pair-bucket budget and group-pack
        # threshold shape the compiled join/group plan; the kernel
        # eligibility budgets pick which segment implementation traces;
        # lazy-shrink is stream-gated off but keyed anyway — the key is
        # the ONE place a knob change is allowed to surface.
        E.pair_budget(), E.group_pack_min(), E.lazy_shrink_rows(),
        _K.max_groups(), _K.exact_onehot_budget(),
    )


def _spec_match(a, b) -> bool:
    """Structural equality of two flattened-part specs (names, kinds,
    validity presence, logical count, physical length, dictionary
    CONTENT) — the test a freshly replanned subquery residual must pass
    before a cached pipeline (whose program baked the old residual's
    shapes and recorded reads) may serve it."""
    from nds_tpu.engine.column import encs_equal
    (ac, an, ap), (bc, bn, bp) = a, b
    if an != bn or ap != bp or len(ac) != len(bc):
        return False
    for (n1, k1, d1, v1, e1), (n2, k2, d2, v2, e2) in zip(ac, bc):
        if n1 != n2 or k1 != k2 or v1 != v2 or not _dicts_equal(d1, d2) \
                or not encs_equal(e1, e2):
            return False
    return True


def _replan_residuals(planner, pipe):
    """Cache-hit path: re-plan every subquery residual for THIS execution
    (its data may have changed) and flatten the results as pipeline
    operands. Returns the flattened infos, or None when any residual's
    shape no longer matches the cached program (caller rebuilds). The
    replanned tables also seed the statement planner's registry, so an
    eventual eager fallback reuses them instead of re-planning per
    chunk."""
    infos = []
    for (rkey, payload), want in zip(pipe.residuals, pipe.resid_specs):
        rt = E.resolve_table(planner._plan_residual(payload))
        planner._subquery_residuals[rkey] = (payload, rt)
        spec, flat = _flatten_part(rt)
        if not _spec_match(spec, want):
            return None
        infos.append((spec, flat))
    return infos


def _resolve_residuals(planner, key, pipe):
    """Per-EXECUTION residual replan for a validated cache hit:
    ``(pipe, resid_infos)`` ready to run, or ``(None, ())`` on residual
    shape drift (the stale entry is evicted under the lock — the caller
    rebuilds). Replan failures PROPAGATE: a device OOM or planner bug
    while re-planning a subquery residual must never be mistaken for an
    unkeyable statement. Shared by the fast path and the singleflight
    waiters."""
    if not pipe.residuals:
        return pipe, ()
    got = _replan_residuals(planner, pipe)
    if got is None:
        with _PIPELINE_LOCK:
            if _PIPELINE_CACHE.get(key) is pipe:
                _PIPELINE_CACHE.pop(key, None)
                _PIPELINE_BUILD_COUNTS.pop(key, None)
        _metrics.default().inc(_metrics.PIPE_EVICT)
        return None, ()
    return pipe, got


def _cache_hit(key, chunk_spec, part_infos):
    pipe = _PIPELINE_CACHE.get(key)
    if pipe is None:
        return None
    # identity-validate part buffers (a maintenance refresh swaps them:
    # the recorded dimension-side host reads would be stale) and
    # content-validate chunk dictionaries (a re-registered streamed table
    # re-encodes; same shapes, different value tables). A stale entry can
    # never hit again — evict it now rather than waiting for FIFO churn.
    from nds_tpu.engine.column import encs_equal
    flat_now = [x for (_spec, flat) in part_infos for x in flat]
    then = [r() for r in pipe.part_refs]
    stale = len(flat_now) != len(then) or \
        any(b is None or a is not b for a, b in zip(flat_now, then)) or \
        any(not _dicts_equal(dv_now, dv_then)
            or not encs_equal(en_now, en_then)
            for (_, _, dv_now, en_now), (_, _, dv_then, en_then)
            in zip(chunk_spec, pipe.chunk_spec))
    if stale:
        with _PIPELINE_LOCK:
            if _PIPELINE_CACHE.get(key) is pipe:
                _PIPELINE_CACHE.pop(key, None)
                _PIPELINE_BUILD_COUNTS.pop(key, None)
        _metrics.default().inc(_metrics.PIPE_EVICT)
        return None
    return pipe


def stream_execute(planner, parts, keep, join_preds, where_conjuncts,
                   sources):
    """Execute a join graph whose ``keep``-th part is a ``_StreamedScan``
    through the compiled chunk pipeline. Returns ``(table, None)`` on
    success, or ``(None, reason)`` when the graph is not streamable /
    overflowed — the caller (``Planner._stream_join_parts``) falls back
    to the eager chunk loop and records the eager StreamEvent AFTER that
    loop, so its syncs cover the whole fallback path, not just the failed
    compile attempt. A ``(None, None)`` return means fall back silently
    (no event)."""
    if E.replay_mode() != "off":
        # never nest inside whole-query record/replay: the pipeline's own
        # recording would interleave with the outer log
        return None, None
    from nds_tpu.sql.planner import _OuterBuild, _OuterProbe
    scan = parts[keep]
    chunked, alias = scan.chunked, scan.alias
    syncs0 = E.sync_count()

    # resolve every non-streamed part's count up front (one batched
    # transfer, usually free): part counts are per-statement constants of
    # the compiled program. Deferred outer joins flatten their tables like
    # any other part; the marker metadata rides outer_meta.
    E.resolve_counts()
    part_infos = []
    outer_meta = []
    for i, p in enumerate(parts):
        if i == keep:
            continue
        if isinstance(p, _OuterProbe):
            part_infos.append(_flatten_part(p.table))
            outer_meta.append(("probe", p.condition, tuple(p.conjuncts),
                               p.src))
        elif isinstance(p, _OuterBuild):
            part_infos.append(_flatten_part(p.table))
            outer_meta.append(("build", p.condition, tuple(p.conjuncts),
                               p.src))
        else:
            part_infos.append(_flatten_part(p))
            outer_meta.append(None)
    # the chunk slot must never be the dimension side of a PK-gather plan:
    # that plan fetches the dim side's key ranges on host, which would
    # bake CHUNK data into the chunk-invariant program
    masked_sources = list(sources)
    masked_sources[keep] = None

    chunk_iter = chunked.padded_chunks()
    # chunk 0 is sliced and encoded here, on the driver (the ring takes
    # over from chunk 1): the same stage name the ring's worker reports
    with _obs.span("prefetch.source", chunk=0):
        first = next(chunk_iter)
    chunk_spec = _chunk_signature(first, alias)
    chunk_cap = chunked.chunk_cap
    n_chunks = chunked.num_chunks()

    key = None
    hit0 = None
    bind_slots = ()
    pipe, resid_infos = None, ()
    try:
        if _param_bind_active():
            bind_slots = _param_slots(planner, parts, keep,
                                      where_conjuncts, chunk_spec)
        key = _cache_key(alias, keep, join_preds, where_conjuncts,
                         masked_sources, part_infos, chunk_spec, chunk_cap,
                         chunked.nrows, outer_meta, bind_slots)
        hit0 = _cache_hit(key, chunk_spec, part_infos)
    except Exception:
        hit0, key = None, None           # unkeyable statement: no cache
    # residual replan runs OUTSIDE the unkeyable guard: its failures are
    # real execution errors, not cache-key problems
    if hit0 is not None:
        pipe, resid_infos = _resolve_residuals(planner, key, hit0)
    parts_flat = tuple(tuple(flat) for (_spec, flat) in part_infos)

    claim = None
    if pipe is None and key is not None:
        # singleflight: claim the compile for this shape or wait (off-
        # lock) for the thread already compiling it, then take its
        # entry. A waiter whose post-wait lookup misses again (the
        # winner's entry was FIFO-evicted or went stale) LOOPS back to
        # claim rather than building unclaimed — exactly one compile
        # per shape holds even under churn. A build that REFUSES (not
        # chunk-invariant) is deliberately not negative-cached: the
        # refusal can depend on chunk DATA the key cannot see, so each
        # waiter retries in turn — a serialized retry of a trace that
        # fails during GIL-bound planner replay, which the pre-
        # singleflight "parallel" attempts serialized anyway.
        while pipe is None and claim is None:
            with _PIPELINE_LOCK:
                in_cache = key in _PIPELINE_CACHE
                pending = None if in_cache else _PIPELINE_BUILDS.get(key)
                if not in_cache and pending is None:
                    claim = _PIPELINE_BUILDS[key] = threading.Event()
                    break
            if in_cache:
                hit = _cache_hit(key, chunk_spec, part_infos)
                if hit is not None:
                    pipe, resid_infos = _resolve_residuals(
                        planner, key, hit)
                # stale entry evicted: next iteration claims or waits
            else:
                pending.wait(timeout=300.0)
    # label the planner's enclosing "stream" span with the cache outcome
    # and feed the metrics plane (the cache-efficacy evidence the
    # parameterized plan bank is judged by: obs_live columns, rollups)
    _obs.annotate(pipelineCache="hit" if pipe is not None else "miss")
    _metrics.default().inc(_metrics.PIPE_HIT if pipe is not None
                           else _metrics.PIPE_MISS)

    degrade_reason = None
    if pipe is None:
        try:
            try:
                pipe, resid_infos = _build_pipeline(
                    planner, parts, keep, alias, join_preds,
                    where_conjuncts, masked_sources, part_infos,
                    outer_meta, first, chunk_spec, chunk_cap, n_chunks,
                    bind_slots=bind_slots)
            except _F.FaultInjected as exc:
                # pipeline-compile seam (degradable): the designed
                # recovery is the compiled->eager ladder step — record
                # the evidence and fall back, even under strict (this
                # IS the policy the fault matrix proves, not a bug
                # hiding in a fallback)
                _F.record_fault_event(exc.seam, "degrade",
                                      detail="compiled->eager: "
                                      f"{exc}")
                pipe, resid_infos = None, ()
                degrade_reason = (f"fault: {exc.seam} "
                                  "(degraded compiled->eager)")
            if pipe is not None and key is not None:
                n_evicted = 0
                with _PIPELINE_LOCK:
                    _PIPELINE_BUILD_COUNTS[key] = \
                        _PIPELINE_BUILD_COUNTS.get(key, 0) + 1
                    while len(_PIPELINE_CACHE) >= _PIPELINE_MAX:
                        evicted = next(iter(_PIPELINE_CACHE))
                        _PIPELINE_CACHE.pop(evicted)
                        # the counter follows its entry out: a long-
                        # lived serving process must not grow one
                        # counter key per shape it ever saw
                        _PIPELINE_BUILD_COUNTS.pop(evicted, None)
                        n_evicted += 1
                    _PIPELINE_CACHE[key] = pipe
                if n_evicted:            # count OFF-lock, like the feeds
                    _metrics.default().inc(_metrics.PIPE_EVICT, n_evicted)
        finally:
            if claim is not None:
                with _PIPELINE_LOCK:
                    _PIPELINE_BUILDS.pop(key, None)
                claim.set()
        if pipe is None:
            return None, degrade_reason or "not chunk-invariant"

    resid_flat = tuple(tuple(flat) for (_spec, flat) in resid_infos)
    # THIS statement's literal values for the pipe's bound slots (a hit
    # is key-guaranteed to agree on slot count/order/types — only the
    # values differ, and they ride as jit operands, not trace constants)
    params = _param_operands(bind_slots) if pipe.param_nodes else ()
    snapshot = list(E._pending_counts())
    checks_snapshot = [c for c, _f in
                       (getattr(E._sync_tls, "checks", None) or [])]
    try:
        out, ran, evidence = pipe.run(chunk_iter, first, parts_flat,
                                      resid_flat, params)
        # tracing the first call replays planner code that registers
        # DeviceCounts/deferred checks holding TRACER values; they belong
        # to the trace, not this execution — drop them before any
        # downstream resolve_counts() would device_get them
        _restore_counts(snapshot, checks_snapshot)
    except _F.StatementTimeout:
        # the statement watchdog fired inside a drive-time wait: the
        # statement is MARKED timeout (drivers map the classified error
        # to status "timeout") — degrading to an eager rerun would pay
        # the hang again. The event was recorded at the wait.
        _restore_counts(snapshot, checks_snapshot)
        raise
    except _F.FaultError as exc:
        # a drive-time fault at a degradable seam (exchange dispatch, an
        # exhausted transient retry): the designed recovery is the
        # degradation ladder — sharded/compiled -> single-device eager
        # rerun, bit-for-bit. Recorded as evidence; deliberate even
        # under strict (the fault matrix proves this path).
        _restore_counts(snapshot, checks_snapshot)
        with _PIPELINE_LOCK:
            _PIPELINE_CACHE.pop(key, None)
            _PIPELINE_BUILD_COUNTS.pop(key, None)
        _metrics.default().inc(_metrics.PIPE_EVICT)
        _F.record_fault_event(exc.seam, "degrade",
                              detail=f"drive fault -> eager rerun: {exc}")
        log.info("streamed pipeline hit fault seam %s; re-running %s "
                 "eagerly", exc.seam, alias)
        return None, f"fault: {exc.seam} (degraded to eager)"
    except (E.ReplayMismatch, E.StreamSyncError, ValueError, TypeError,
            NotImplementedError, jax.errors.TracerArrayConversionError,
            jax.errors.ConcretizationTypeError,
            jax.errors.TracerBoolConversionError) as exc:
        # first-call trace divergence: unstreamable after all. The reason
        # carries the exception CLASS so a fallback caused by a genuine
        # engine bug (ValueError/TypeError/...) is distinguishable from
        # the two legitimate routing exceptions; NDS_TPU_STREAM_STRICT=1
        # re-raises everything else outright (the diff harnesses and the
        # A/B tests run strict).
        _restore_counts(snapshot, checks_snapshot)
        with _PIPELINE_LOCK:
            _PIPELINE_CACHE.pop(key, None)
            _PIPELINE_BUILD_COUNTS.pop(key, None)
        _metrics.default().inc(_metrics.PIPE_EVICT)
        if _strict() and not isinstance(exc, (E.StreamSyncError,
                                              E.ReplayMismatch)):
            raise
        log.info("streamed pipeline fell back to eager: %s", exc)
        return None, f"trace diverged [{type(exc).__name__}]: {exc}"
    evidence = evidence or {}
    if out is None:
        # device-side overflow (partitioned: some partition's enforced
        # per-partition bucket): rows were dropped, rerun eagerly. Keep
        # the compiled program — other statements over smaller data may
        # fit.
        log.info("streamed pipeline overflowed its bound buckets; "
                 "re-running %s eagerly", alias)
        return None, "bound-bucket overflow"
    survivor_total = int(out.nrows)
    # deferred outer-build joins: emit the outer extras ONCE, from the
    # unmatched-key bitmaps the pipeline accumulated (counts rode the
    # single materializing transfer — no extra sync)
    extras = []
    nonkeep_parts = [p for i, p in enumerate(parts) if i != keep]
    for (slot, miss_mask, n_extras) in evidence.get("outer", ()):
        if not n_extras:
            continue
        from nds_tpu.sql.planner import outer_extras_table
        idx = E.compact_indices(miss_mask, n_extras)
        extras.append(outer_extras_table(nonkeep_parts[slot].table, idx,
                                         n_extras, out))
    if extras:
        out = E.concat_tables([out] + extras)
    h2d = evidence.get("h2d", -1)
    stall_ms = evidence.get("stall_ms", -1.0)
    record_stream_event(alias, ran, E.sync_count() - syncs0, "compiled",
                        rows=survivor_total,
                        partitions=evidence.get("partitions", 1),
                        part_rows=evidence.get("part_rows", ()),
                        bytes_h2d=h2d,
                        shards=evidence.get("shards", 1),
                        collectives=evidence.get("collectives", -1),
                        bytes_ici=evidence.get("bytes_ici", -1),
                        shard_rows=evidence.get("shard_rows", ()),
                        prefetch_stall_ms=stall_ms)
    _obs.annotate(path="compiled", chunks=ran,
                  prefetchStallMs=stall_ms,
                  partitions=evidence.get("partitions", 1),
                  shards=evidence.get("shards", 1),
                  collectives=evidence.get("collectives", -1),
                  bytesIci=evidence.get("bytes_ici", -1),
                  bytesH2d=h2d,
                  bytesLogical=_logical_chunk_bytes(pipe.chunk_spec,
                                                    pipe.chunk_cap, ran))
    return out, None


def _build_pipeline(planner, parts, keep, alias, join_preds,
                    where_conjuncts, masked_sources, part_infos,
                    outer_meta, first, chunk_spec, chunk_cap, n_chunks,
                    bind_slots=()):
    """RECORD the per-chunk join graph on the first padded chunk and
    compile the chunk-invariant program; ``(None, None)`` when not
    streamable. Returns ``(pipe, resid_infos)`` — the flattened subquery
    residuals the record phase pre-planned, which are THIS execution's
    residual operands."""
    from nds_tpu.engine.replay import _lift_log
    from nds_tpu.sql.planner import _OuterBuild, _OuterProbe
    # pipeline-compile seam (degradable): an injected build/compile
    # fault degrades this statement to the eager chunk loop (the
    # handler lives in stream_execute, which records the FaultEvent)
    _F.fault_point("pipeline-compile")
    snapshot = list(E._pending_counts())
    checks_snapshot = [c for c, _f in
                       (getattr(E._sync_tls, "checks", None) or [])]
    sub = list(parts)
    aliased = planner._alias_table(first, alias)
    sub[keep] = DeviceTable(
        aliased.columns,
        E.DeviceCount(jnp.asarray(E.count_int(first.nrows),
                                  dtype=jnp.int64), chunk_cap),
        plen=chunk_cap)
    pi = 0
    for i in range(len(parts)):
        if i == keep:
            continue
        t = _rebuild_part(part_infos[pi][0], part_infos[pi][1])
        meta = outer_meta[pi]
        if meta is not None:
            mk, mcond, mconjs, msrc = meta
            t = (_OuterProbe if mk == "probe" else _OuterBuild)(
                t, mcond, list(mconjs), msrc)
        sub[i] = t
        pi += 1
    # save/restore: a subquery residual planned DURING this record may
    # itself stream through a nested pipeline build on the same planner —
    # its record must not clobber the outer record's touched list
    prev_touched = planner._residuals_touched
    planner._residuals_touched = touched = []
    try:
        with _obs.span("stream.record", table=alias):
            with E.recording() as rec_log:
                with E.stream_bounds():
                    with E.outer_match_collector() as omc:
                        out0 = planner._join_parts(sub, list(join_preds),
                                                   list(where_conjuncts),
                                                   list(masked_sources))
    except E.StreamSyncError as exc:
        log.info("streamed scan %s not chunk-invariant: %s", alias, exc)
        return None, None
    finally:
        planner._residuals_touched = prev_touched
        _restore_counts(snapshot, checks_snapshot)
    # subquery residuals the record phase planned (or reused): they become
    # jit operands of the per-chunk program
    resid_infos = [_flatten_part(rt) for (_k, _p, rt) in touched]
    residuals = [(k, p) for (k, p, _rt) in touched]
    # names-only catalog snapshot: the traced planner's correlation
    # analysis (_find_correlation/_select_output_cols) must resolve
    # subquery scopes exactly like the record phase did, without closing
    # over any device-resident table
    name_cat = {}
    if residuals:
        for scope in planner.cte_stack:
            for k, t in scope.items():
                name_cat[k.lower()] = tuple(t.column_names)
        for k, t in planner.catalog.items():
            name_cat.setdefault(k.lower(), tuple(t.column_names))
    # outer-build bitmap slots: the record phase registered one matched
    # mask per deferred outer-build join, in part order
    build_slots = [i for i, m in enumerate(outer_meta)
                   if m is not None and m[0] == "build"]
    if len(omc.masks) != len(build_slots):
        log.info("streamed scan %s: outer-build mask count mismatch "
                 "(%d masks, %d builds)", alias, len(omc.masks),
                 len(build_slots))
        return None, None
    names = list(out0.column_names)
    template = (names,
                [out0[n].kind for n in names],
                [out0[n].dict_values for n in names],
                [out0[n].valid is not None for n in names],
                [out0[n].data.dtype for n in names],
                # survivors carry their narrow encodings into the
                # accumulator (decode only at materialize) — the proof-
                # sized allocation shrinks with the data
                [out0[n].enc for n in names])
    # size the survivor accumulator from the statement's proven row bound
    # (static memory model) instead of the old global guess: a statement
    # whose bound fits the capacity model can never overflow-rerun
    row_bytes = sum(out0[n].data.dtype.itemsize
                    + (1 if out0[n].valid is not None else 0)
                    for n in names)
    stream_rows = parts[keep].chunked.nrows
    proved, fan_k, part_keys = _proved_plan(parts, keep, join_preds,
                                            where_conjuncts, masked_sources,
                                            stream_rows)
    # the prefetch ring's live set (depth × one padded chunk's actual
    # upload bytes) comes off the capacity every admission decision
    # below sees — mem_audit prices the same term statically (lockstep)
    ring_bytes = _ring_bytes(sum(
        int(first[c].data.nbytes)
        + (0 if first[c].valid is None else int(first[c].valid.nbytes))
        for c in first.column_names))
    n_parts, part_bound = _partition_plan(stream_rows, fan_k, part_keys,
                                          proved, max(row_bytes, 1),
                                          n_chunks, out0.plen,
                                          ring_bytes=ring_bytes)
    key_slots = []
    if n_parts > 1:
        # map the partition keys (bare names) to the chunk's flattened
        # buffer slots (2 slots per column: data, valid)
        spec_names = [nm for (nm, _k, _dv, _en) in chunk_spec]
        for key in part_keys:
            hit = [i for i, nm in enumerate(spec_names)
                   if nm.split(".")[-1] == key]
            if not hit:
                n_parts, part_bound = 1, None    # key pruned off the scan
                break
            key_slots.append(2 * hit[0])
    if n_parts > 1:
        budget = _part_acc_budget(n_chunks, out0.plen, part_bound,
                                  max(row_bytes, 1), n_parts,
                                  ring_bytes=ring_bytes)
    else:
        budget = _acc_row_budget(n_chunks, out0.plen, proved,
                                 max(row_bytes, 1),
                                 ring_bytes=ring_bytes)
    # mesh-sharded execution: each shard accumulates its own slice, so
    # the budget re-shares over the mesh (skew-factored like the
    # partition share — mem_audit.shard_row_bound, the lockstep rule);
    # the recorded out bucket stays the floor, so a per-shard dispatch
    # can always land one full chunk output
    n_shards, mesh, axis_name = _shard_plan(chunk_cap)
    exchange, cap_ex = False, 0
    if n_shards > 1:
        from nds_tpu.analysis.mem_audit import stream_skew_factor
        budget = min(budget, -(-budget // n_shards) * stream_skew_factor())
        if n_parts > 1 and key_slots and \
                os.environ.get("NDS_TPU_STREAM_EXCHANGE", "1") != "0":
            # the partitioned graph's keys are not co-partitioned with
            # the arbitrary row split: hash-exchange rows over ICI so
            # each shard owns a key range
            exchange = True
            cap_ex = E.bucket_len(
                max((chunk_cap // n_shards) // n_shards, 1)
                * stream_skew_factor())
    acc_cap = E.bucket_len(max(budget, out0.plen))
    _obs.annotate(accRows=acc_cap, partitions=n_parts, shards=n_shards,
                  provedRows=proved if proved is not None else "unproven",
                  residuals=len(residuals), outerBuilds=len(build_slots))
    lifted, operands = _lift_log(list(rec_log))
    pipe = StreamPipeline(
        chunk_spec, chunk_cap,
        tuple(spec for (spec, _flat) in part_infos), keep, lifted,
        tuple(operands), template, acc_cap,
        [_weak(x) for (_spec, flat) in part_infos for x in flat],
        n_partitions=n_parts, key_slots=key_slots,
        outer_meta=outer_meta, residuals=residuals,
        resid_specs=tuple(spec for (spec, _flat) in resid_infos),
        build_slots=build_slots, name_catalog=name_cat,
        n_shards=n_shards, mesh=mesh, mesh_axis=axis_name or "shard",
        exchange=exchange, cap_ex=cap_ex,
        # bound slots reference where_conjuncts Literal nodes: the
        # traced replay sees those same nodes
        param_nodes=tuple(nd for (_ci, _p, _t, nd) in bind_slots),
        param_tags=tuple(t for (_ci, _p, t, _nd) in bind_slots))
    return (pipe.compile(join_preds, where_conjuncts, masked_sources),
            resid_infos)
