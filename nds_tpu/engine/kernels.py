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

import functools
import os

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
