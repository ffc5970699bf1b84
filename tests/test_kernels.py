# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Pallas kernel parity tests (interpret mode on the CPU mesh)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nds_tpu.engine import kernels


@pytest.fixture()
def interpret_mode(monkeypatch):
    monkeypatch.setenv("NDS_TPU_PALLAS", "interpret")


def _ref_segment(weights, gids, num_segments):
    sums = np.zeros(num_segments, dtype=np.float64)
    counts = np.zeros(num_segments, dtype=np.float64)
    for w, g in zip(weights, gids):
        if g >= 0:
            sums[g] += w
            counts[g] += 1
    return sums, counts


@pytest.mark.parametrize("n,groups", [(0, 7), (1, 1), (1000, 130), (5000, 513)])
def test_segment_sum_fused_interpret(interpret_mode, n, groups):
    rng = np.random.default_rng(3)
    gids = rng.integers(-1, groups, size=n).astype(np.int32)
    w = rng.integers(0, 100, size=n).astype(np.float32)
    sums, counts = kernels.segment_sum_fused(
        jnp.asarray(w), jnp.asarray(gids), groups)
    rs, rc = _ref_segment(w, gids, groups)
    np.testing.assert_allclose(np.asarray(sums), rs, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(counts), rc)


def test_segment_sum_fused_fallback_matches(monkeypatch):
    monkeypatch.setenv("NDS_TPU_PALLAS", "off")
    rng = np.random.default_rng(4)
    gids = rng.integers(-1, 50, size=777).astype(np.int32)
    w = rng.normal(size=777).astype(np.float32)
    sums, counts = kernels.segment_sum_fused(
        jnp.asarray(w), jnp.asarray(gids), 50)
    rs, rc = _ref_segment(w, gids, 50)
    np.testing.assert_allclose(np.asarray(sums), rs, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(counts), rc)


def test_agg_sum_pallas_path_matches_exact(interpret_mode):
    """The integrated ops.agg_sum fast path vs the exact default path."""
    from nds_tpu.engine.column import Column
    from nds_tpu.engine import ops
    rng = np.random.default_rng(6)
    n, g = 3000, 200
    gids = jnp.asarray(rng.integers(0, g, size=n).astype(np.int64))
    vals = rng.normal(scale=100.0, size=n)
    valid = rng.random(n) > 0.1
    col = Column("f64", jnp.asarray(np.where(valid, vals, 0.0)),
                 jnp.asarray(valid))
    fast = ops.agg_sum(col, gids, g)
    import os
    os.environ["NDS_TPU_PALLAS"] = "off"
    exact = ops.agg_sum(col, gids, g)
    np.testing.assert_allclose(np.asarray(fast.data), np.asarray(exact.data),
                               rtol=1e-4, atol=1e-3)
    np.testing.assert_array_equal(np.asarray(fast.valid_mask()),
                                  np.asarray(exact.valid_mask()))


def test_pallas_mode_off_without_tpu(monkeypatch):
    monkeypatch.setenv("NDS_TPU_PALLAS", "auto")
    if jax.default_backend() != "tpu":
        assert kernels._pallas_mode() == "off"


@pytest.mark.parametrize("n,groups", [(1, 1), (1000, 130), (5000, 513)])
def test_segment_minmax_fused_interpret(interpret_mode, n, groups):
    rng = np.random.default_rng(11)
    gids = jnp.asarray(rng.integers(-1, groups, n).astype(np.int32))
    vals = jnp.asarray((rng.random(n) * 200 - 100).astype(np.float32))
    mins, maxs = kernels.segment_minmax_fused(vals, gids, groups)
    g_np, v_np = np.asarray(gids), np.asarray(vals)
    for g in range(groups):
        sel = v_np[g_np == g]
        if len(sel):
            assert np.isclose(float(mins[g]), sel.min(), rtol=1e-6)
            assert np.isclose(float(maxs[g]), sel.max(), rtol=1e-6)
        else:
            assert float(mins[g]) == float(np.float32(kernels._F32_MAX))
            assert float(maxs[g]) == float(np.float32(-kernels._F32_MAX))


def test_segment_minmax_group_gate(monkeypatch):
    """Above the group-count gate the XLA path must be taken (and agree)."""
    monkeypatch.setenv("NDS_TPU_PALLAS", "interpret")
    monkeypatch.setenv("NDS_TPU_PALLAS_MAX_GROUPS", "4")
    gids = jnp.asarray(np.array([0, 1, 5, 5, 3], dtype=np.int32))
    vals = jnp.asarray(np.array([1.0, -2.0, 7.0, 3.0, 0.5], dtype=np.float32))
    mins, maxs = kernels.segment_minmax_fused(vals, gids, 6)
    assert float(mins[5]) == 3.0 and float(maxs[5]) == 7.0
    assert not kernels.pallas_active(6)
    assert kernels.pallas_active(4)


# ---------------------------------------------------------------------------
# exact limb-split segment sum (the DEFAULT decimal bench path)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,groups", [(1, 1), (1000, 130), (5000, 513),
                                      (4096, 2048)])
def test_segment_sum_exact_interpret(interpret_mode, n, groups):
    """Bit-exact parity with a host int accumulation, including negative
    values at the full dec(7,2) domain and masked rows."""
    rng = np.random.default_rng(5)
    gids = rng.integers(-1, groups, size=n).astype(np.int32)
    v = rng.integers(-(10 ** 7 - 1), 10 ** 7, size=n).astype(np.int64)
    sums, counts = kernels.segment_sum_exact(
        jnp.asarray(v), jnp.asarray(gids), groups)
    ref_s = np.zeros(groups, dtype=np.int64)
    ref_c = np.zeros(groups, dtype=np.int64)
    for x, g in zip(v, gids):
        if g >= 0:
            ref_s[g] += x
            ref_c[g] += 1
    np.testing.assert_array_equal(np.asarray(sums), ref_s)
    np.testing.assert_array_equal(np.asarray(counts), ref_c)


def test_segment_sum_exact_extremes(interpret_mode):
    """Every row at the domain extreme, one group: the worst case for
    limb-accumulator width (n * 255 per limb) must stay exact."""
    n = 8192
    # far past any decimal precision: exactness must not depend on any
    # declared value bound (two's-complement limbs cover all of int64)
    v = np.full(n, (1 << 52) + 12345, dtype=np.int64)
    v[::2] = -(1 << 52) - 99999
    gids = np.zeros(n, dtype=np.int32)
    sums, counts = kernels.segment_sum_exact(
        jnp.asarray(v), jnp.asarray(gids), 1)
    assert int(sums[0]) == int(v.sum())
    assert int(counts[0]) == n


def test_exact_gate_declines_out_of_bounds(interpret_mode):
    assert not kernels.exact_sum_supported(kernels.max_groups() + 1, 100)
    assert not kernels.exact_sum_supported(100, 1 << 23)     # too many rows
    assert kernels.exact_sum_supported(100, 100)


def test_agg_sum_decimal_rides_exact_kernel(interpret_mode):
    """The engine's DEFAULT (exact decimal) aggregation must produce
    bit-identical results through the kernel and the XLA path."""
    import os

    from nds_tpu.engine import ops as E
    from nds_tpu.engine.column import Column

    rng = np.random.default_rng(9)
    n, groups = 3000, 40
    gids = jnp.asarray(rng.integers(0, groups, n))
    data = jnp.asarray(rng.integers(-10 ** 6, 10 ** 6, n), dtype=jnp.int64)
    valid = jnp.asarray(rng.random(n) < 0.9)
    col = Column("dec(7,2)", jnp.where(valid, data, 0), valid)
    via_kernel = E.agg_sum(col, gids, groups)
    os.environ["NDS_TPU_PALLAS"] = "off"
    try:
        via_xla = E.agg_sum(col, gids, groups)
    finally:
        os.environ["NDS_TPU_PALLAS"] = "interpret"
    np.testing.assert_array_equal(np.asarray(via_kernel.data),
                                  np.asarray(via_xla.data))
    np.testing.assert_array_equal(np.asarray(via_kernel.valid),
                                  np.asarray(via_xla.valid))
    via_avg = E.agg_avg(col, gids, groups)
    os.environ["NDS_TPU_PALLAS"] = "off"
    try:
        via_avg_xla = E.agg_avg(col, gids, groups)
    finally:
        os.environ["NDS_TPU_PALLAS"] = "interpret"
    np.testing.assert_allclose(np.asarray(via_avg.data),
                               np.asarray(via_avg_xla.data), rtol=1e-12)
