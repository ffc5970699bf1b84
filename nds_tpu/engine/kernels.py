# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Pallas TPU kernels for the hot aggregation path.

The reference delegates its hot operators to the RAPIDS plugin's CUDA
kernels (SURVEY.md §2.2 N4). Here the hottest device pattern — masked
grouped aggregation, the inner loop of every GROUP BY query — gets a
TPU-native Pallas kernel that rides the MXU: a segment-sum is a matmul
against a one-hot membership matrix, so each (row-tile × group-tile) grid
cell builds its one-hot block in VMEM with ``broadcasted_iota`` compares and
accumulates ``w @ onehot`` partial sums on the systolic array. For the group
counts the same trick runs with unit weights, so one kernel emits both.

This beats a scatter-add lowering when groups are modest (TPC-DS group-bys:
brands, categories, states — hundreds to tens of thousands of groups) because
the MXU does 128×128 MACs/cycle while scatter serializes on HBM.

Use :func:`segment_sum_fused` — it picks the Pallas path on TPU (or when
``NDS_TPU_PALLAS=interpret`` for tests) and falls back to
``jax.ops.segment_sum`` elsewhere. Values are accumulated in float32 on the
MXU; the engine's exact int64 decimal path keeps using the XLA fallback
(int64 matmul does not map to the MXU), mirroring the reference's
``--floats`` fast path vs exact-decimal split (ref: nds/nds_transcode.py
--floats, nds/README.md decimal notes).
"""

from __future__ import annotations

import contextlib
import functools
import os
import threading

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from nds_tpu.obs import trace as _trace

# row tile: sublane-friendly multiple; group tile: one lane width
_TR = 512
_TG = 128


def _pallas_mode() -> str:
    """'tpu' | 'interpret' | 'off'."""
    env = os.environ.get("NDS_TPU_PALLAS", "auto")
    if env == "off":
        return "off"
    if env == "interpret":
        return "interpret"
    if env in ("auto", "1", "tpu"):
        try:
            if jax.default_backend() == "tpu":
                return "tpu"
        except RuntimeError:  # pragma: no cover
            pass
        return "off"
    return "off"


def _seg_kernel(gid_ref, w_ref, sum_ref, cnt_ref):
    """One (group-tile j, row-tile i) cell: accumulate this row tile's
    contribution to this group tile's sums and counts via MXU matmuls.

    The row (reduction) dimension is the INNERMOST grid dim so each output
    block sees its row tiles on consecutive grid steps — Pallas only keeps an
    output block's VMEM buffer live across consecutive steps mapping to the
    same block, so accumulation across a non-innermost reduction dim would
    read stale buffers on real hardware."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        sum_ref[:] = jnp.zeros_like(sum_ref)
        cnt_ref[:] = jnp.zeros_like(cnt_ref)

    gid = gid_ref[:]                      # (1, TR) int32, -1 = masked row
    w = w_ref[:].astype(jnp.float32)      # (1, TR)
    j = pl.program_id(0)
    gbase = j * _TG
    # one-hot membership block (TR, TG): rows vs this tile's group ids
    groups = gbase + jax.lax.broadcasted_iota(jnp.int32, (_TR, _TG), 1)
    onehot = (gid.reshape(_TR, 1) == groups).astype(jnp.float32)
    sum_ref[:] += jnp.dot(w, onehot, preferred_element_type=jnp.float32)
    live = (gid.reshape(1, _TR) >= 0).astype(jnp.float32)
    cnt_ref[:] += jnp.dot(live, onehot, preferred_element_type=jnp.float32)


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@functools.partial(jax.jit, static_argnums=(2, 3))
@_trace.scoped("kernel.segment_sum")
def _segment_sum_pallas(gids, weights, num_segments: int, interpret: bool):
    n = gids.shape[0]
    n_pad = max(_ceil_to(n, _TR), _TR)
    g_pad = max(_ceil_to(num_segments, _TG), _TG)
    # pad rows with gid -1 (matches no group) and zero weight
    gid_p = jnp.full(n_pad, -1, dtype=jnp.int32).at[:n].set(
        gids.astype(jnp.int32))
    w_p = jnp.zeros(n_pad, dtype=jnp.float32).at[:n].set(
        weights.astype(jnp.float32))
    grid = (g_pad // _TG, n_pad // _TR)   # rows innermost (see kernel doc)
    sums, counts = pl.pallas_call(
        _seg_kernel,
        grid=grid,
        # the leading block index must stay i32: a literal 0 weak-types to
        # i64 under the engine's jax_enable_x64, and Mosaic refuses the
        # mixed (i64, i32) index-map return (seen on a v5e as
        # "failed to legalize operation 'func.return'"); j - j keeps the
        # zero in the grid index's own dtype
        in_specs=[
            pl.BlockSpec((1, _TR), lambda j, i: (j - j, i)),
            pl.BlockSpec((1, _TR), lambda j, i: (j - j, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, _TG), lambda j, i: (i - i, j)),
            pl.BlockSpec((1, _TG), lambda j, i: (i - i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, g_pad), jnp.float32),
            jax.ShapeDtypeStruct((1, g_pad), jnp.float32),
        ],
        interpret=interpret,
    )(gid_p.reshape(1, n_pad), w_p.reshape(1, n_pad))
    return sums[0, :num_segments], counts[0, :num_segments]


# interpret-mode only: the first kernel failure of a process flips every
# kernel to its XLA twin (the listener reports it, so the statement ends
# CompletedWithTaskFailures). On a chip (mode "tpu") nothing sets it: a
# kernel the compiler refuses there fails the query — see _kernel_failed.
_pallas_broken = False


def _kernel_failed(what: str, exc: Exception,
                   mode: str = "interpret") -> None:
    """A kernel call site's handler. On a chip (``mode`` "tpu") the
    failure is the query's: re-raised, no flag, no change of arm. In
    interpret mode: record it and switch the process to the XLA twins."""
    global _pallas_broken
    if mode == "tpu":
        raise exc
    _pallas_broken = True
    from nds_tpu.listener import report_task_failure
    report_task_failure(f"pallas {what} kernel (XLA fallback for the "
                        f"rest of the process)", exc)
    import sys
    print(f"# pallas kernels disabled ({type(exc).__name__}); "
          f"using XLA fallback", file=sys.stderr)

# the one-hot matmul does O(rows x groups) MACs — MXU throughput makes that
# a win over scatter only while the group tile count stays small. Measured
# on v5e (n=16M): 1.8x faster at 1k groups, 12x SLOWER at 64k groups.
# Read at USE time (not import): the ceiling picks which segment
# implementation TRACES, so it is a pipeline-cache key member
# (engine/stream.py _cache_key) and a post-import change must retrace.
def max_groups() -> int:
    return int(os.environ.get("NDS_TPU_PALLAS_MAX_GROUPS", "2048"))


def _spans_devices(*arrays) -> bool:
    """True when a concrete input lives on more than one device: the
    survivors of a sharded streamed scan, a table of an NDS_MESH_SHAPE
    session. A Mosaic kernel traced into such a program is refused —
    ``NotImplementedError: Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map`` (seen on four v5e
    chips, PR 22: query3's sum over shards=4 survivors; pinned in
    tests/test_tpu_compile.py) — so by decision those inputs take the
    XLA segment ops, which GSPMD partitions. Tracers carry no placement
    (inside a shard_map body each shard is one device)."""
    for a in arrays:
        if isinstance(a, jax.core.Tracer):
            continue
        sharding = getattr(a, "sharding", None)
        if sharding is not None and len(sharding.device_set) > 1:
            return True
    return False


def pallas_active(num_segments: int | None = None) -> bool:
    """True when :func:`segment_sum_fused` will take the Pallas path for
    this group count. Callers must gate on this (not the raw env var) so the
    exact XLA path is used whenever the kernel itself would fall back."""
    if num_segments is not None and num_segments > max_groups():
        return False
    return not _pallas_broken and _pallas_mode() != "off"


def segment_sum_fused(weights, gids, num_segments: int):
    """(sums f32[G], counts f32[G]) of ``weights`` grouped by ``gids``.

    Rows with gid < 0 are excluded (pre-masked nulls / filtered rows).
    Pallas MXU path on TPU (small group counts — see ``max_groups()``), XLA
    segment ops elsewhere. On a chip a kernel that fails to compile or run
    fails the query: the v5e compiler accepts all three segment kernels
    (tests/test_tpu_compile.py), so a refusal is a fault to see, not a
    reason to change arms in silence.
    """
    mode = _pallas_mode()
    if mode != "off" and not _pallas_broken and \
            num_segments <= max_groups() and \
            not _spans_devices(gids, weights):
        try:
            return _segment_sum_pallas(gids, weights, num_segments,
                                       mode == "interpret")
        except Exception as e:
            _kernel_failed("segment-sum", e, mode)
    live = gids >= 0
    safe = jnp.where(live, gids, 0)
    w = jnp.where(live, weights.astype(jnp.float32), 0.0)
    sums = jax.ops.segment_sum(w, safe, num_segments=num_segments)
    counts = jax.ops.segment_sum(live.astype(jnp.float32), safe,
                                 num_segments=num_segments)
    return sums, counts




# ---------------------------------------------------------------------------
# EXACT int64 segment-sum via limb-split MXU matmuls (the decimal path)
# ---------------------------------------------------------------------------
#
# The default bench runs exact decimals (scaled int64), which the f32 MXU
# kernel above cannot carry (24-bit mantissa). Two's-complement limb
# decomposition makes it exact for ANY int64 — no trust in declared value
# bounds: limbs 0-6 are unsigned bytes, the top limb is the SIGNED
# arithmetic shift (v >> 56, in [-128, 127]), so v = sum_l limb_l << 8l
# identically. All 8 limbs plus the count row ride ONE (9, TR) x (TR, TG)
# MXU matmul per grid cell (the systolic array processes the 9-row operand
# in the same tile pass as the 1-row f32 kernel's). A per-cell partial is
# <= 512*255 < 2^17 so the f32 dot is exact; cross-tile accumulation
# happens in an i32 output ref (exact while n*255 < 2^31 => n < 2^23 rows
# — the one gate), and the i64 recombination runs in XLA on the tiny
# (9, G) result, wrapping on true-sum overflow exactly like the XLA
# segment-sum it replaces. Each arithmetic claim in this paragraph (limb
# identity, f32-exact partials, the i32 gate, the f32 mantissa limit) is
# an executable check in ``analysis/num_audit.kernel_claim_checks``; the
# per-statement accumulator-range proofs that make the wrap-on-overflow
# caveat unreachable at the audited scale live in the same module.

_LIMB_BITS = 8
_N_LIMBS = 8            # full int64 coverage: 7 unsigned bytes + signed top


def _seg_exact_kernel(gid_ref, w_ref, acc_ref):
    """One (group-tile j, row-tile i) cell: (9, TR) limb rows (+count
    row) hit the one-hot membership block in a single MXU matmul; the f32
    partial (exact, < 2^17) accumulates into the i32 output ref."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    gid = gid_ref[:]                      # (1, TR) i32, -1 = masked row
    j = pl.program_id(0)
    groups = j * _TG + jax.lax.broadcasted_iota(jnp.int32, (_TR, _TG), 1)
    onehot = (gid.reshape(_TR, 1) == groups).astype(jnp.float32)
    part = jnp.dot(w_ref[:], onehot, preferred_element_type=jnp.float32)
    acc_ref[:] += part.astype(jnp.int32)


@functools.partial(jax.jit, static_argnums=(2, 3))
@_trace.scoped("kernel.segment_sum_exact")
def _segment_sum_exact_pallas(gids, values, num_segments: int,
                              interpret: bool):
    n = gids.shape[0]
    k = _N_LIMBS
    live = gids >= 0
    v = jnp.where(live, values, 0)
    n_pad = max(_ceil_to(n, _TR), _TR)
    g_pad = max(_ceil_to(num_segments, _TG), _TG)
    gid_p = jnp.full(n_pad, -1, dtype=jnp.int32).at[:n].set(
        gids.astype(jnp.int32))
    rows = []
    for l in range(k - 1):
        limb = (v >> (_LIMB_BITS * l)) & jnp.int64(255)
        rows.append(jnp.zeros(n_pad, dtype=jnp.float32).at[:n].set(
            limb.astype(jnp.float32)))
    top = v >> (_LIMB_BITS * (k - 1))              # signed, [-128, 127]
    rows.append(jnp.zeros(n_pad, dtype=jnp.float32).at[:n].set(
        top.astype(jnp.float32)))
    rows.append(jnp.zeros(n_pad, dtype=jnp.float32).at[:n].set(
        live.astype(jnp.float32)))                 # count row
    w = jnp.stack(rows)                            # (k+1, n_pad)
    grid = (g_pad // _TG, n_pad // _TR)            # rows innermost
    acc = pl.pallas_call(
        _seg_exact_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, _TR), lambda j, i: (j - j, i)),
            pl.BlockSpec((k + 1, _TR), lambda j, i: (j - j, i)),
        ],
        out_specs=pl.BlockSpec((k + 1, _TG), lambda j, i: (i - i, j)),
        out_shape=jax.ShapeDtypeStruct((k + 1, g_pad), jnp.int32),
        interpret=interpret,
    )(gid_p.reshape(1, n_pad), w)
    acc = acc[:, :num_segments].astype(jnp.int64)
    sums = jnp.zeros(num_segments, dtype=jnp.int64)
    for l in range(k):
        sums = sums + (acc[l] << (_LIMB_BITS * l))
    return sums, acc[k]


# measured crossover on v5e (min-of-5, hard device->host sync; n x G):
#   1M x 256:  pallas 60.5ms  vs XLA  83.6ms   (pallas 1.38x)
#   4M x 1024: pallas 132.5ms vs XLA 102.2ms   (XLA 1.30x)
#  16M x 1024: pallas 187.8ms vs XLA  97.4ms   (XLA 1.93x)
#  16M x 2048: pallas 352.4ms vs XLA 107.5ms   (XLA 3.28x)
# the one-hot matmul does O(n*G) MACs while XLA's scatter is O(n), so the
# exact kernel engages only below the measured n*G break-even.
# Read at USE time for the same reason as max_groups() above.
def exact_onehot_budget() -> int:
    return int(float(os.environ.get("NDS_TPU_EXACT_ONEHOT_BUDGET", "3e8")))


def exact_sum_supported(num_segments: int, n_rows: int) -> bool:
    """True when the exact limb-split kernel will engage: Pallas active
    for this group count, per-limb i32 accumulation cannot overflow, and
    the O(n*G) one-hot work sits below the measured XLA-scatter
    break-even (table above)."""
    return (pallas_active(num_segments) and n_rows < (1 << 23)
            and n_rows * max(num_segments, 1) <= exact_onehot_budget())


def segment_sum_exact(values, gids, num_segments: int):
    """EXACT (sums i64[G], counts i64[G]) of any int64 ``values`` grouped
    by ``gids`` (rows with gid < 0 excluded). MXU limb path on TPU under
    the same gates as :func:`segment_sum_fused`; XLA segment ops
    elsewhere. Unlike the f32 kernel this is bit-exact — it serves the
    DEFAULT decimal bench path."""
    mode = _pallas_mode()
    if mode != "off" and not _pallas_broken and \
            exact_sum_supported(num_segments, int(values.shape[0])) and \
            not _spans_devices(gids, values):
        try:
            sums, counts = _segment_sum_exact_pallas(
                gids, values, num_segments, mode == "interpret")
            return sums, counts.astype(jnp.int64)
        except Exception as e:
            _kernel_failed("exact segment-sum", e, mode)
    live = gids >= 0
    safe = jnp.where(live, gids, 0)
    v = jnp.where(live, values, 0)
    sums = jax.ops.segment_sum(v, safe, num_segments=num_segments)
    counts = jax.ops.segment_sum(live.astype(jnp.int64), safe,
                                 num_segments=num_segments)
    return sums, counts


# ---------------------------------------------------------------------------
# segment min/max (VPU tiled reduce over the same one-hot membership tiling)
# ---------------------------------------------------------------------------

_F32_MAX = 3.4e38


def _seg_minmax_kernel(gid_ref, v_ref, min_ref, max_ref):
    """One (group-tile j, row-tile i) cell: masked row-tile min and max per
    group. Same grid discipline as :func:`_seg_kernel` (rows innermost)."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        min_ref[:] = jnp.full_like(min_ref, _F32_MAX)
        max_ref[:] = jnp.full_like(max_ref, -_F32_MAX)

    gid = gid_ref[:]                     # (1, TR) int32, -1 = masked row
    v = v_ref[:].astype(jnp.float32)     # (1, TR)
    j = pl.program_id(0)
    gbase = j * _TG
    groups = gbase + jax.lax.broadcasted_iota(jnp.int32, (_TR, _TG), 1)
    member = gid.reshape(_TR, 1) == groups              # (TR, TG) bool
    vb = v.reshape(_TR, 1)
    # sentinels must be f32 CONSTANTS: a bare Python float weak-types to
    # f64 under jax_enable_x64 and Mosaic cannot legalize the tpu.truncf
    # the promotion would need
    big = jnp.float32(_F32_MAX)
    lo = jnp.where(member, vb, big)
    hi = jnp.where(member, vb, -big)
    min_ref[:] = jnp.minimum(min_ref[:], jnp.min(lo, axis=0).reshape(1, _TG))
    max_ref[:] = jnp.maximum(max_ref[:], jnp.max(hi, axis=0).reshape(1, _TG))


@functools.partial(jax.jit, static_argnums=(2, 3))
@_trace.scoped("kernel.segment_minmax")
def _segment_minmax_pallas(gids, values, num_segments: int, interpret: bool):
    n = gids.shape[0]
    n_pad = max(_ceil_to(n, _TR), _TR)
    g_pad = max(_ceil_to(num_segments, _TG), _TG)
    gid_p = jnp.full(n_pad, -1, dtype=jnp.int32).at[:n].set(
        gids.astype(jnp.int32))
    v_p = jnp.zeros(n_pad, dtype=jnp.float32).at[:n].set(
        values.astype(jnp.float32))
    grid = (g_pad // _TG, n_pad // _TR)
    mins, maxs = pl.pallas_call(
        _seg_minmax_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, _TR), lambda j, i: (j - j, i)),
            pl.BlockSpec((1, _TR), lambda j, i: (j - j, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, _TG), lambda j, i: (i - i, j)),
            pl.BlockSpec((1, _TG), lambda j, i: (i - i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, g_pad), jnp.float32),
            jax.ShapeDtypeStruct((1, g_pad), jnp.float32),
        ],
        interpret=interpret,
    )(gid_p.reshape(1, n_pad), v_p.reshape(1, n_pad))
    return mins[0, :num_segments], maxs[0, :num_segments]


def segment_minmax_fused(values, gids, num_segments: int):
    """(mins f32[G], maxs f32[G]) of ``values`` grouped by ``gids`` (rows
    with gid < 0 excluded; empty groups come back as +/-_F32_MAX). Pallas
    VPU path on TPU under the same small-group-count gate as
    :func:`segment_sum_fused`; XLA segment ops elsewhere.

    f32 precision note: like the sum kernel this is the opt-in float path —
    the engine's exact decimal/int64 min/max stays on XLA (f32 rounding
    would corrupt exact comparisons).
    """
    mode = _pallas_mode()
    if mode != "off" and not _pallas_broken and \
            num_segments <= max_groups() and \
            not _spans_devices(gids, values):
        try:
            return _segment_minmax_pallas(gids, values, num_segments,
                                          mode == "interpret")
        except Exception as e:
            _kernel_failed("segment-min/max", e, mode)
    live = gids >= 0
    safe = jnp.where(live, gids, 0)
    v = values.astype(jnp.float32)
    mins = jax.ops.segment_min(jnp.where(live, v, _F32_MAX), safe,
                               num_segments=num_segments)
    maxs = jax.ops.segment_max(jnp.where(live, v, -_F32_MAX), safe,
                               num_segments=num_segments)
    return mins, maxs


# ---------------------------------------------------------------------------
# fused chunk-scan pass (decode -> filter -> hash -> partition/shard ids)
# ---------------------------------------------------------------------------
#
# The streamed per-chunk program used to evaluate its chunk-local
# predicates, the _hash_mix partition hash, and the survivor mask as a
# chain of generic XLA elementwise ops — each stage re-reading the chunk
# from HBM. fused_chunk_scan makes ONE VMEM-resident pass over each
# padded chunk tile: FOR/sorted-dict decode stays IMPLICIT (ordered
# predicates are rebased into encoded space at lower time, so the kernel
# compares raw stored codes; only the float lane decodes), every lowered
# conjunct evaluates on the tile in VMEM, and the same pass folds the
# partition hash whose low bits pick the partition and next bits the
# destination shard — the ids the exchange consumes unchanged. The
# TPU-native analogue of operating directly on compressed data inside
# the kernel ("GPU Acceleration of SQL Analytics on Compressed Data",
# PAPERS.md).
#
# The spec (engine/exprs.lower_scan_spec) is extracted ONCE at pipeline
# record time from the chunk-local WHERE conjuncts, so the kernel is
# chunk-invariant and pipeline-cacheable; eligibility is the shared rule
# in analysis/kernel_spec.py (the exec_audit lockstep). The XLA op chain
# stays the always-available fallback (NDS_TPU_PALLAS=off, non-lowerable
# conjuncts fall back per-conjunct), bit-for-bit A/B'd under
# NDS_TPU_STREAM_STRICT=1.
#
# Entry opcodes (one entry per lowered conjunct; thresholds already in
# STORED space — analysis/kernel_spec.py does the exact rational math):
#
#   ("ieq"|"ine"|"ile"|"ige", ci, T)     int lane, raw stored codes
#   ("irange", ci, lo, hi)               BETWEEN (negated: "nrange")
#   ("iin"|"inotin", ci, values)         IN-list membership
#   ("isnull"|"notnull", ci)             validity only
#   ("true"|"false", ci)                 constant contribution (& valid)
#   ("feq"|"fne"|"flt"|"fle"|"fgt"|"fge", ci, L)
#                                        float lane: decode per col meta
#                                        (_as_f64 semantics), compare f64
#   ("fin"|"fnotin", ci, values)         float-lane IN-list membership
#   ("frange"|"fnrange", ci, lo, hi)     float-lane BETWEEN (f64 columns
#                                        / float bounds)


class ScanSpec:
    """Chunk-invariant description of one fused scan pass.

    ``cols`` holds per-referenced-column metadata
    ``(data_slot, valid_slot, fmode, base, tbl_idx, sdiv)`` — slots index
    the pipeline's flattened chunk buffers (valid_slot -1 = no mask);
    ``fmode``/``base``/``tbl_idx``/``sdiv`` describe the float-lane
    decode ("id" | "for" | "dict", FOR base, dict table index, the
    10**scale divisor). ``tables`` are the sorted dict value tables the
    float lane gathers (host arrays, chunk-invariant like string
    dictionaries). ``key_slots`` are the chunk buffers the partition
    hash folds (empty = no hash output)."""

    __slots__ = ("entries", "n_conjuncts", "cols", "tables", "key_slots")

    def __init__(self, entries, cols, tables=(), key_slots=(),
                 n_conjuncts=None):
        self.entries = tuple(entries)
        # a conjunct may lower to SEVERAL entries (mixed-lane BETWEEN),
        # so the stage count tracks CONJUNCTS, matching the static
        # prediction's count_eligible
        self.n_conjuncts = len(self.entries) if n_conjuncts is None \
            else n_conjuncts
        self.cols = tuple(cols)
        self.tables = tuple(tables)
        self.key_slots = tuple(key_slots)

    def stages(self) -> int:
        """Fused stage count of one launch: one per lowered conjunct
        plus the hash stage — the number ``StreamEvent.kernel_fused_
        stages`` reports and exec_audit predicts."""
        return self.n_conjuncts + (1 if self.key_slots else 0)


def hash_mix(h, data):
    """Fold one key column into the per-row partition hash (uint32) —
    THE partition/shard routing hash (moved here from engine/stream.py
    so the fused kernel and the XLA partition pass share one
    definition; any drift would route rows differently per arm).
    Dictionary codes hash as their int32 codes (the whole-table encoding
    makes them value-stable across chunks); floats hash their bit
    pattern. Multiplicative mixing — any chunk-row partitioning keeps
    the per-partition bound valid, the hash only evens the shares.
    The 32 mixed bits are split into DISJOINT route windows (low
    ``log2(P)`` bits pick the partition, the next ``log2(S)`` bits the
    shard — engine/stream.py); both env knobs are clamped so the two
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


def _eval_entries(spec: ScanSpec, datas, valids, tables):
    """Survivor mask of one tile (or whole buffer): AND of every lowered
    conjunct's contribution. Shared by the Pallas kernel body and the
    pure-jnp reference (scan_reference), so the two arms cannot drift.
    ``datas``/``valids`` are per-spec-col arrays (valids[i] None when the
    column has no mask); all boolean logic mirrors the eager engine's
    ``mask & data & valid_mask`` WHERE contract exactly."""
    shape = datas[0].shape
    m = jnp.ones(shape, dtype=bool)

    def vmask(ci):
        v = valids[ci]
        return jnp.ones(shape, dtype=bool) if v is None else v

    for e in spec.entries:
        kind, ci = e[0], e[1]
        if kind == "false":
            m = jnp.zeros(shape, dtype=bool)
            continue
        if kind == "true":
            m = m & vmask(ci)
            continue
        if kind == "isnull":
            m = m & ~vmask(ci)
            continue
        if kind == "notnull":
            m = m & vmask(ci)
            continue
        if kind[0] == "i":
            x = datas[ci].astype(jnp.int64)
            if kind == "ieq":
                c = x == e[2]
            elif kind == "ine":
                c = x != e[2]
            elif kind == "ile":
                c = x <= e[2]
            elif kind == "ige":
                c = x >= e[2]
            elif kind == "irange":
                c = (x >= e[2]) & (x <= e[3])
            elif kind == "iin":
                c = jnp.zeros(shape, dtype=bool)
                for v in e[2]:
                    c = c | (x == v)
            elif kind == "inotin":
                c = jnp.ones(shape, dtype=bool)
                for v in e[2]:
                    c = c & (x != v)
            else:
                raise ValueError(f"unknown scan entry {kind!r}")
            m = m & c & vmask(ci)
            continue
        if kind == "nrange":
            x = datas[ci].astype(jnp.int64)
            m = m & ~((x >= e[2]) & (x <= e[3])) & vmask(ci)
            continue
        if kind[0] == "f":
            _ds, _vs, fmode, base, tbl, sdiv = spec.cols[ci]
            d = datas[ci]
            if fmode == "for":
                val = (d.astype(jnp.int64) + base).astype(jnp.float64)
            elif fmode == "dict":
                val = jnp.take(tables[tbl], d, mode="clip").astype(
                    jnp.float64)
            else:
                val = d.astype(jnp.float64)
            if sdiv != 1.0:
                val = val / sdiv
            if kind == "fin" or kind == "fnotin":
                c = jnp.zeros(shape, dtype=bool)
                for v in e[2]:
                    c = c | (val == v)
                if kind == "fnotin":
                    c = ~c
                m = m & c & vmask(ci)
                continue
            if kind == "frange" or kind == "fnrange":
                c = (val >= e[2]) & (val <= e[3])
                if kind == "fnrange":
                    c = ~c
                m = m & c & vmask(ci)
                continue
            L = e[2]
            if kind == "feq":
                c = val == L
            elif kind == "fne":
                c = val != L
            elif kind == "flt":
                c = val < L
            elif kind == "fle":
                c = val <= L
            elif kind == "fgt":
                c = val > L
            else:
                c = val >= L
            m = m & c & vmask(ci)
            continue
        raise ValueError(f"unknown scan entry {kind!r}")
    return m


def _fold_hash(keybufs):
    h = jnp.full(keybufs[0].shape, 2166136261, dtype=jnp.uint32)
    for kb in keybufs:
        h = hash_mix(h, kb)
    return h


# scan-pass row tile (lane-width multiple; the pass is pure VPU)
_TR_SCAN = 512


def _scan_inputs(chunk_flat, spec: ScanSpec):
    """(datas, valids, keybufs, tables) pulled from the pipeline's
    flattened chunk buffers per the spec's slots."""
    datas = [chunk_flat[c[0]] for c in spec.cols]
    valids = [None if c[1] < 0 else chunk_flat[c[1]] for c in spec.cols]
    keybufs = [chunk_flat[s] for s in spec.key_slots]
    import numpy as np
    tables = [jnp.asarray(np.asarray(t)) for t in spec.tables]
    return datas, valids, keybufs, tables


def scan_reference(chunk_flat, n_dev, spec: ScanSpec):
    """Pure-jnp twin of :func:`fused_chunk_scan` (same shared entry
    evaluation, no Pallas): the parity oracle the kernel unit tests pin,
    and the documentation of exactly what the kernel computes."""
    datas, valids, keybufs, tables = _scan_inputs(chunk_flat, spec)
    plen = datas[0].shape[0]
    mask = _eval_entries(spec, datas, valids, tables)
    mask = mask & (jnp.arange(plen) < n_dev)
    h = _fold_hash(keybufs) if keybufs else None
    return mask, h


@_trace.scoped("kernel.chunk_scan")
def fused_chunk_scan(chunk_flat, n_dev, spec: ScanSpec, interpret: bool):
    """ONE Pallas pass over the padded chunk: every referenced buffer
    crosses HBM->VMEM once, the lowered conjuncts and the partition hash
    evaluate on the resident tile, and the survivor mask (+ uint32 hash
    when the graph partitions/exchanges) come back for the compaction
    scatter. Traced inside the pipeline's jitted pre-pass — zero host
    syncs by construction (the `host-read-in-pallas` lint rule polices
    the kernel bodies).

    Mosaic refuses this kernel as written (int64 lanes, the 64-bit casts
    of the hash fold, the 1-D dict gather — see ``scan_kernels_active``),
    so it runs in interpret mode only."""
    datas, valids, keybufs, tables = _scan_inputs(chunk_flat, spec)
    plen = datas[0].shape[0]
    n_pad = max(_ceil_to(plen, _TR_SCAN), _TR_SCAN)

    def pad(x):
        if x is None:
            return None
        y = jnp.zeros(n_pad, dtype=x.dtype).at[:plen].set(x)
        return y.reshape(1, n_pad)

    datas_p = [pad(d) for d in datas]
    valids_p = [pad(v) for v in valids if v is not None]
    valid_pos = {}
    j = 0
    for i, v in enumerate(valids):
        if v is not None:
            valid_pos[i] = j
            j += 1
    keybufs_p = [pad(k) for k in keybufs]
    tabs_p = []
    for t in tables:
        t_pad = max(_ceil_to(t.shape[0], 128), 128)
        tabs_p.append(jnp.zeros(t_pad, dtype=t.dtype).at[:t.shape[0]]
                      .set(t).reshape(1, t_pad))
    emit_hash = bool(keybufs)
    nd, nv, nk, nt = (len(datas_p), len(valids_p), len(keybufs_p),
                      len(tabs_p))

    def kernel(*refs):
        ins = refs[:nd + nv + nk + nt]
        outs = refs[nd + nv + nk + nt:]
        d_tiles = [ins[i][:] for i in range(nd)]
        v_tiles = [None if i not in valid_pos
                   else ins[nd + valid_pos[i]][:] for i in range(nd)]
        k_tiles = [ins[nd + nv + i][:] for i in range(nk)]
        t_full = [ins[nd + nv + nk + i][:].reshape(-1) for i in range(nt)]
        outs[0][:] = _eval_entries(spec, d_tiles, v_tiles, t_full)
        if emit_hash:
            outs[1][:] = _fold_hash(k_tiles)

    grid = (n_pad // _TR_SCAN,)
    tile = lambda i: (i - i, i)          # noqa: E731 — i32 grid index
    whole = lambda i: (i - i, i - i)     # noqa: E731
    in_specs = [pl.BlockSpec((1, _TR_SCAN), tile)
                for _ in range(nd + nv + nk)]
    in_specs += [pl.BlockSpec((1, int(t.shape[1])), whole) for t in tabs_p]
    out_specs = [pl.BlockSpec((1, _TR_SCAN), tile)]
    out_shape = [jax.ShapeDtypeStruct((1, n_pad), jnp.bool_)]
    if emit_hash:
        out_specs.append(pl.BlockSpec((1, _TR_SCAN), tile))
        out_shape.append(jax.ShapeDtypeStruct((1, n_pad), jnp.uint32))
    got = pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret,
    )(*datas_p, *valids_p, *keybufs_p, *tabs_p)
    mask = got[0][0, :plen] & (jnp.arange(plen) < n_dev)
    h = got[1][0, :plen] if emit_hash else None
    note_launch(spec.stages())
    return mask, h


def scan_kernels_active() -> bool:
    """True when pipeline builds should extract a scan spec and route
    the per-chunk hot path through :func:`fused_chunk_scan`. Callers
    gate on this (and :func:`probe_kernel_active` builds on it).

    Interpret mode only: the fused scan and the fused probe are OFF on
    the chip by decision, not by a caught exception. Asked at a 1 Mi-row
    shape (x64 on, jax 0.9.0 / libtpu 0.0.34), the v5e compiler refuses
    both as written:

    * ``fused_chunk_scan``, int64 lane: ``UNIMPLEMENTED: While rewriting
      computation to not contain X64 element types ...
      custom_call_target="tpu_custom_call",
      operand_layout_constraints={s64[1,1048576]}``;
    * int32 lane + validity + ``key_slots``: ``NotImplementedError:
      64-bit types are not supported`` (the ``_fold_hash``/``n_dev`` lanes);
    * int16 dict codes on the float lane: ``NotImplementedError: Only 2D
      gather is supported``;
    * ``fused_probe``, int64 keys over a uint64 hash table:
      ``NotImplementedError: 64-bit types are not supported``.

    tests/test_tpu_compile.py pins the four refusals; the day one of them
    compiles, that test fails and this gate is where the kernel comes
    back (ROADMAP Queue 1 item 6 / Queue 3 item 4: 32-bit lanes).
    ``NDS_TPU_PALLAS=interpret`` keeps both reachable for the parity
    tests and the diff harnesses."""
    return not _pallas_broken and _pallas_mode() == "interpret"


def scan_spec_ready(spec: ScanSpec, chunk_flat, plen: int) -> bool:
    """Smoke-run one fused scan over zeroed buffers of the real chunk
    shapes at pipeline-BUILD time (eager, one tile's work, result
    discarded — no host read), so a spec the interpreter cannot run
    degrades to the XLA chain BEFORE any compiled pipeline bakes the
    kernel in, never mid-drive."""
    if not scan_kernels_active():
        return False
    try:
        dummy = tuple(
            None if x is None else jnp.zeros((plen,), dtype=x.dtype)
            for x in chunk_flat)
        fused_chunk_scan(dummy, jnp.asarray(plen, dtype=jnp.int64), spec,
                         interpret=True)
        return True
    except Exception as e:
        _kernel_failed("fused chunk-scan", e)
        return False


# ---------------------------------------------------------------------------
# fused bound-bucket join probe
# ---------------------------------------------------------------------------
#
# The stream-bounds join's probe phase hashes the chunk side's key
# columns and binary-searches the hash-sorted dimension side — under XLA
# that is one HBM pass per key column plus one per searchsorted. The
# fused probe replicates ops._key_hash_impl BITWISE on the resident tile
# (same _mix64 constants, same null/pad/exclusion sentinels) and runs
# both searchsorted sides against the dimension hash table held whole in
# VMEM, emitting the (lo, counts) pair the bound-bucket expansion
# consumes unchanged — candidate counts are identical to the XLA path's
# by construction, so overflow accounting cannot move between arms.

# dimension buckets past this stay on XLA: the whole sorted hash table
# rides VMEM per grid cell (8B/row)
_PROBE_MAX_R = 1 << 15


def _probe_hash_tile(views, valids, excluded, rows, n_valid):
    """uint64 key hash of one tile — ops._key_hash_impl, restated on
    resident arrays (int views only; f64 keys stay on XLA). ``rows`` are
    the tile's global row indices (pad/side sentinels must be per-ROW
    unique exactly like the XLA hash so nothing collides)."""
    import numpy as np
    _C1 = jnp.uint64(0x9E3779B97F4A7C15)
    _C2 = jnp.uint64(0xBF58476D1CE4E5B9)
    _C3 = jnp.uint64(0x94D049BB133111EB)

    def mix64(x):
        x = x.astype(jnp.uint64)
        x = (x ^ (x >> 30)) * _C2
        x = (x ^ (x >> 27)) * _C3
        return x ^ (x >> 31)

    shape = views[0].shape
    h = jnp.full(shape, jnp.uint64(0x243F6A8885A308D3), dtype=jnp.uint64)
    any_null = jnp.zeros(shape, dtype=bool)
    for v, valid in zip(views, valids):
        w = v.astype(jnp.uint64)
        if valid is not None:
            w = jnp.where(valid, w, jnp.uint64(0))
            marker = jnp.where(valid, jnp.uint64(0),
                               jnp.uint64(0xA5A5A5A5A5A5A5A5))
            any_null = any_null | ~valid
        else:
            marker = jnp.zeros(shape, dtype=jnp.uint64)
        h = mix64(h ^ marker)
        h = mix64(h ^ w * _C1)
    unmatchable = any_null | (rows >= n_valid)
    if excluded is not None:
        unmatchable = unmatchable | excluded
    # side_salt 0 (probe side): sentinel = 2 + (row << 3); REAL bit 4
    sentinel = jnp.uint64(2) + (rows.astype(jnp.uint64) << jnp.uint64(3))
    return jnp.where(unmatchable, sentinel, h | jnp.uint64(4))


def probe_reference(views, valids, n_valid, excluded, rh_sorted):
    """Pure-jnp twin of :func:`fused_probe` (parity oracle)."""
    n = views[0].shape[0]
    rows = jnp.arange(n)
    lh = _probe_hash_tile(views, valids, excluded, rows, n_valid)
    lo = jnp.searchsorted(rh_sorted, lh, side="left")
    hi = jnp.searchsorted(rh_sorted, lh, side="right")
    return hi - lo, lo


def probe_kernel_active(views, valids, plen_r: int) -> bool:
    """Gate for the fused probe: interpret mode only (Mosaic refuses it,
    see :func:`scan_kernels_active`), int key views only, and the
    dimension hash table small enough to hold whole in VMEM. Callers
    fall back to the XLA probe whenever this says no."""
    if not scan_kernels_active():
        return False
    if plen_r > _PROBE_MAX_R:
        return False
    return all(v.dtype != jnp.float64 for v in views)


@_trace.scoped("kernel.probe")
def fused_probe(views, valids, n_valid, excluded, rh_sorted,
                interpret: bool):
    """(counts, lo) of the bound-bucket probe in ONE VMEM pass per chunk
    tile: key hash (bitwise ops._key_hash_impl) + both binary-search
    sides against the resident dimension hash table."""
    n = views[0].shape[0]
    n_pad = max(_ceil_to(n, _TR_SCAN), _TR_SCAN)
    r = rh_sorted.shape[0]
    r_pad = max(_ceil_to(r, 128), 128)
    # pads sort above every real hash (max uint64): searchsorted of any
    # real probe value lands below them
    rh_p = jnp.full(r_pad, jnp.uint64(0xFFFFFFFFFFFFFFFF),
                    dtype=jnp.uint64).at[:r].set(rh_sorted).reshape(
        1, r_pad)

    def pad(x, fill=0):
        return jnp.full(n_pad, fill, dtype=x.dtype).at[:n].set(x).reshape(
            1, n_pad)

    views_p = [pad(v) for v in views]
    valid_list = [v for v in valids if v is not None]
    valids_p = [pad(v) for v in valid_list]
    vpos = {}
    j = 0
    for i, v in enumerate(valids):
        if v is not None:
            vpos[i] = j
            j += 1
    excl_p = None if excluded is None else pad(excluded, True)
    nviews, nvalid = len(views_p), len(valids_p)
    nv_arr = jnp.asarray(n_valid, dtype=jnp.int64).reshape(1, 1)

    def kernel(*refs):
        i = pl.program_id(0)
        k = 0
        v_tiles = [refs[k + j][:] for j in range(nviews)]
        k += nviews
        valid_tiles = [None if j not in vpos
                       else refs[k + vpos[j]][:] for j in range(nviews)]
        k += nvalid
        if excl_p is not None:
            excl_tile = refs[k][:]
            k += 1
        else:
            excl_tile = None
        rh_full = refs[k][:].reshape(-1)
        k += 1
        nv = refs[k][0, 0]
        k += 1
        cnt_ref, lo_ref = refs[k], refs[k + 1]
        rows = i * _TR_SCAN + jax.lax.broadcasted_iota(
            jnp.int64, (1, _TR_SCAN), 1)
        lh = _probe_hash_tile(v_tiles, valid_tiles, excl_tile, rows, nv)
        lo = jnp.searchsorted(rh_full, lh.reshape(-1), side="left")
        hi = jnp.searchsorted(rh_full, lh.reshape(-1), side="right")
        cnt_ref[:] = (hi - lo).reshape(1, _TR_SCAN).astype(jnp.int64)
        lo_ref[:] = lo.reshape(1, _TR_SCAN).astype(jnp.int64)

    grid = (n_pad // _TR_SCAN,)
    tile = lambda i: (i - i, i)          # noqa: E731
    whole = lambda i: (i - i, i - i)     # noqa: E731
    in_specs = [pl.BlockSpec((1, _TR_SCAN), tile)
                for _ in range(nviews + nvalid)]
    if excl_p is not None:
        in_specs.append(pl.BlockSpec((1, _TR_SCAN), tile))
    in_specs.append(pl.BlockSpec((1, r_pad), whole))
    in_specs.append(pl.BlockSpec((1, 1), whole))
    args = [*views_p, *valids_p]
    if excl_p is not None:
        args.append(excl_p)
    args += [rh_p, nv_arr]
    counts, lo = pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, _TR_SCAN), tile),
                   pl.BlockSpec((1, _TR_SCAN), tile)],
        out_shape=[jax.ShapeDtypeStruct((1, n_pad), jnp.int64),
                   jax.ShapeDtypeStruct((1, n_pad), jnp.int64)],
        interpret=interpret,
    )(*args)
    note_probe()
    return counts[0, :n], lo[0, :n]


_probe_smoke_ok: bool | None = None


def try_fused_probe(left_keys, lviews, lvalids, n_valid, excluded,
                    rh_sorted):
    """The ops.py seam: (counts, lo) through the fused probe, or None
    when the gate declines (always, outside interpret mode) or a
    one-time eager smoke run fails, so an error can never surface
    mid-pipeline-drive."""
    global _probe_smoke_ok
    if not probe_kernel_active(lviews, lvalids, int(rh_sorted.shape[0])):
        return None
    if any(lk.kind == "f64" for lk in left_keys):
        return None
    if _probe_smoke_ok is None:
        try:
            v = jnp.zeros(4, dtype=jnp.int64)
            rh = jnp.zeros(4, dtype=jnp.uint64)
            fused_probe((v,), (None,), jnp.asarray(4, dtype=jnp.int64),
                        None, rh, interpret=True)
            _probe_smoke_ok = True
        except Exception as e:
            _probe_smoke_ok = False
            _kernel_failed("fused join-probe", e)
    if not _probe_smoke_ok:
        return None
    return fused_probe(lviews, lvalids, n_valid, excluded, rh_sorted,
                       interpret=True)


# ---------------------------------------------------------------------------
# trace-time kernel accounting + the Pallas-vs-XLA arm surface
# ---------------------------------------------------------------------------

_kern_tls = threading.local()


@contextlib.contextmanager
def kernel_trace():
    """Count fused-kernel launches while tracing one compiled program —
    the same trace-time pattern as parallel.exchange.collective_trace:
    a kernel traced into a jit program launches once per dispatch, so
    counting at trace time gives exact per-dispatch evidence at zero
    runtime cost. ``counts``: {"launches", "stages", "probes"}."""
    prev = getattr(_kern_tls, "counts", None)
    _kern_tls.counts = {"launches": 0, "stages": 0, "probes": 0}
    try:
        yield _kern_tls.counts
    finally:
        _kern_tls.counts = prev


def note_launch(stages: int) -> None:
    c = getattr(_kern_tls, "counts", None)
    if c is not None:
        c["launches"] += 1
        c["stages"] += stages


def note_probe() -> None:
    c = getattr(_kern_tls, "counts", None)
    if c is not None:
        c["launches"] += 1
        c["probes"] += 1


def active_arm() -> str:
    """"pallas" | "xla": the arm the FUSED chunk kernels (scan pass, join
    probe) take for this process right now — "xla" on a chip, where
    Mosaic refuses them (:func:`scan_kernels_active`), "pallas" only in
    interpret mode. Surfaced as the ``kernelArm`` annotation on every
    ``stream`` span so tools/trace_report.py can attribute fused-kernel
    coverage (and price fused-vs-XLA) per query. The segment kernels'
    arm is :func:`_pallas_mode` itself (the Power ledger's ``pallas``
    meta field)."""
    return "pallas" if scan_kernels_active() else "xla"
