# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""``ops.compact_indices``: its sort arm returns ``jnp.nonzero``'s indices,
value for value and in its dtype, builds one sort program a bucket, keeps
``jnp.nonzero`` inside a stream-bounds region, and states ``sortedRows``."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nds_tpu.engine import ops as E
from nds_tpu.engine.column import Column
from nds_tpu.engine.table import DeviceTable
from nds_tpu.obs import export as obs_export
from nds_tpu.obs import trace as obs_trace

_PLENS = (1, 7, 256, 4096, 1 << 20)
_MASKS = ("empty", "first", "last", "one_percent", "half", "all")


def _mask(plen: int, kind: str) -> np.ndarray:
    m = np.zeros(plen, dtype=bool)
    if kind == "first":
        m[0] = True
    elif kind == "last":
        m[-1] = True
    elif kind == "all":
        m[:] = True
    elif kind in ("one_percent", "half"):
        rng = np.random.default_rng(plen)
        m = rng.random(plen) < (0.01 if kind == "one_percent" else 0.5)
    return m


def _cap(count: int, plen: int, where: str) -> int:
    if where == "below":
        return max(count // 2, 1)
    if where == "at":
        return max(count, 1)
    return E.bucket_len(2 * plen)           # past the input: padded


def _expect(mask, cap):
    plen = int(mask.shape[0])
    return jnp.nonzero(mask, size=cap, fill_value=max(plen, 1))[0]


def _same(got, want):
    assert got.dtype == want.dtype == jnp.int64
    assert got.shape == want.shape
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("where", ["below", "at", "above"])
@pytest.mark.parametrize("kind", _MASKS)
@pytest.mark.parametrize("plen", _PLENS)
def test_both_arms_return_nonzeros_indices(plen, kind, where):
    """The sort arm (called directly, and as ``compact_indices``) and the
    stream-bounds arm (``compact_indices`` inside a stream-bounds region)
    against ``jnp.nonzero``, eagerly and under ``jax.jit``, with the output
    cut below, at and past the count."""
    host = _mask(plen, kind)
    mask = jnp.asarray(host)
    cap = _cap(int(host.sum()), plen, where)
    want = _expect(mask, cap)
    _same(E._compact_sorted(mask, cap), want)
    _same(jax.jit(lambda m: E._compact_sorted(m, cap))(mask), want)
    # compact_indices sizes its output as bucket_len(n)
    want_b = _expect(mask, E.bucket_len(cap))
    _same(E.compact_indices(mask, cap), want_b)
    _same(jax.jit(lambda m: E.compact_indices(m, cap))(mask), want_b)
    with E.stream_bounds():
        _same(E.compact_indices(mask, cap), want_b)
        _same(jax.jit(lambda m: E.compact_indices(m, cap))(mask), want_b)


def test_one_sort_program_a_bucket():
    """Two output widths over one input bucket: one sort program, two of
    the small program that cuts it to width."""
    plen = 3001                            # a length no other test uses
    mask = jnp.arange(plen) % 3 == 0
    sorts = E._compact_sort_impl._cache_size()
    caps = E._compact_cap_impl._cache_size()
    for cap in (512, 4096):
        _same(E._compact_sorted(mask, cap), _expect(mask, cap))
    assert E._compact_sort_impl._cache_size() == sorts + 1
    assert E._compact_cap_impl._cache_size() == caps + 2


@pytest.mark.parametrize("region", ["stream_bounds", "eager"])
def test_stream_bounds_keeps_nonzero(region):
    """A chunk program's compaction stays ``jnp.nonzero``'s scatter form
    (its programs stay byte-identical); outside a stream-bounds region the
    same call is the sort."""
    plen = 1 << 20
    mask = jax.ShapeDtypeStruct((plen,), jnp.bool_)
    lower = jax.jit(lambda m: E.compact_indices(m, plen // 4)).lower
    if region == "stream_bounds":
        with E.stream_bounds():
            text = lower(mask).as_text()
        assert "stablehlo.scatter" in text
        assert "stablehlo.sort" not in text
    else:
        text = lower(mask).as_text()
        assert "stablehlo.sort" in text
        assert "stablehlo.scatter" not in text


@pytest.mark.parametrize("plen,key", [(1 << 20, "xi32>"),
                                      (1 << 31, "xi64>")])
def test_sort_keys_widen_past_int32(plen, key):
    """The sort's keys are int32 while every position and the fill fit,
    int64 from 2**31 rows (lowered only: no such array is made)."""
    mask = jax.ShapeDtypeStruct((plen,), jnp.bool_)
    text = E._compact_sort_impl.lower(mask).as_text()
    assert f"tensor<{plen}{key}" in text


def _int_table(plen):
    return DeviceTable({"k": Column("int", jnp.arange(plen, dtype=jnp.int64))})


def test_sorted_rows_stated_on_compact():
    """``compact_table`` states the bucket its sort sorted as
    ``sortedRows`` on ``op.compact``; the rollup sums the phase's spans."""
    wide, narrow = _int_table(1 << 20), _int_table(1 << 12)
    E.resolve_counts()
    obs_trace.drain_spans()
    for t in (wide, narrow, wide):
        out = E.compact_table(t, t["k"].data % 7 == 0)
        E.count_int(out.nrows)
    spans = [r for r in obs_trace.drain_spans()
             if isinstance(r, obs_trace.SpanRecord) and r.name == "op.compact"]
    assert [s.attrs.get("sortedRows") for s in spans] == \
        [1 << 20, 1 << 12, 1 << 20]
    assert obs_export.rollup(spans)["phases"]["op.compact"]["sortedRows"] \
        == 2 * (1 << 20) + (1 << 12)


def test_sorted_rows_not_stated_from_a_trace():
    """Traced into a caller's program the sort states nothing (the trace
    runs once, the program many times); inside a stream-bounds region
    there is no sort to state; the same call made eagerly states it."""
    mask = jnp.arange(4096) % 5 == 0
    with obs_trace.span("op.compact") as sp:
        jax.jit(lambda m: E.compact_indices(m, 1024))(mask)
        with E.stream_bounds():
            E.compact_indices(mask, 1024)
        assert "sortedRows" not in sp.attrs
        E.compact_indices(mask, 1024)
        assert sp.attrs["sortedRows"] == 4096
