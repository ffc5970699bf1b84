# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""What the v5e compiler says about the engine's Pallas kernels.

The TPU compiler is installed where the tests run; it compiles for a chip
that is DESCRIBED, not attached (no chip time, nothing runs). These cases
keep its answers for the three segment kernels of
``nds_tpu/engine/kernels.py`` at the shapes ``chip_smoke.py`` really
produces at scale factor 1, so every later PR is held to them: they
compile, with a ``tpu_custom_call`` in the program — on a chip a refusal
fails the query (no XLA fallback there) — and are refused over a mesh.

The topology is described inside a module-scoped fixture — never at
import, never in a ``skipif`` or a ``parametrize`` argument: only one
process may load the TPU library at a time, the suite runs under several
xdist workers that each import every test file, and only the worker that
is handed this file may load it. Keep these cases in this one file, and
compile in the test's own process (a child could not load the library).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import nds_tpu  # noqa: F401  (turns x64 on, as the engine runs)
from nds_tpu.engine import kernels as K
from nds_tpu.engine import ops as E

# the streamed phase's chunk capacity at SF1 (NDS_TPU_STREAM_CHUNK_ROWS
# in chip_smoke.py) and a 1 Mi-row resident bucket
CHUNK = 131072
MI = 1 << 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip (the next run would warn
    # and compile again): keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    return jax.jit(fn).lower(*args).compile()


# (kernel, value dtype, rows, groups): where each shape comes from
SEGMENT_CASES = [
    # agg_count rides the f32 sum kernel: query96's count(*) over the
    # streamed survivors, a chunk-capacity input, the 1 Mi issue shape,
    # and the widest admitted corner (rows < 2^24, max_groups() = 2048)
    ("sum", jnp.float32, 16, 16),
    ("sum", jnp.float32, CHUNK, 1024),
    ("sum", jnp.float32, MI, 1024),
    ("sum", jnp.float32, 1 << 23, 2048),
    # the multi-fact mix at SF1 (PR 28): query10's six count(*) over the
    # customers that pass its EXISTS, at the survivors' bucket (32 Ki for
    # seed 4242; the neighbouring buckets for other seeds) under the group
    # ceiling. It is the mix's only segment-kernel call: query25 (4096
    # groups), query51 (256 Ki and 1 Mi) and query97 are past max_groups()
    # and past exact_sum_supported, and take the XLA segment ops
    ("sum", jnp.float32, 1 << 14, 2048),
    ("sum", jnp.float32, 1 << 15, 2048),
    ("sum", jnp.float32, 1 << 16, 2048),
    # the exact int64 decimal sum: query56's streamed union (1024 x 1024)
    # and resident scan (1 Mi x 256), and both corners of
    # exact_sum_supported (rows * groups <= 3e8, rows < 2^23) — the most
    # groups and the most rows it admits at power-of-two buckets
    ("exact", jnp.int64, 1024, 1024),
    ("exact", jnp.int64, MI, 256),
    ("exact", jnp.int64, CHUNK, 2048),
    ("exact", jnp.int64, 1 << 22, 64),
    # float min/max (the --floats path): chunk capacity and the corner
    ("minmax", jnp.float64, CHUNK, 1024),
    ("minmax", jnp.float64, MI, 2048),
]
_SEGMENT_FN = {"sum": K._segment_sum_pallas,
               "exact": K._segment_sum_exact_pallas,
               "minmax": K._segment_minmax_pallas}


@pytest.mark.parametrize("kernel,dtype,rows,groups", SEGMENT_CASES)
def test_segment_kernel_compiles_for_v5e(one_chip, kernel, dtype, rows,
                                         groups):
    """Mosaic accepts the three segment kernels at the shapes the smoke
    produces and at the corners of their gates (fast-memory limits show
    at the edges)."""
    if kernel == "exact":
        assert rows * groups <= K.exact_onehot_budget() and rows < (1 << 23)
    assert groups <= K.max_groups()
    fn = _SEGMENT_FN[kernel]
    compiled = _compile(
        one_chip, lambda g, v: fn(g, v, groups, False),
        ((rows,), jnp.int32), ((rows,), dtype))
    assert "tpu_custom_call" in compiled.as_text()


# the narrowed join probe at the multi-fact cell's shapes: store_sales'
# 4 Mi probe bucket against store_returns' 512 Ki build bucket (a 4 Mi
# bitmap), survivors at 64 Ki (the REAL bit alone, query25) and 8 Ki; and
# the narrowed PK probe at the star cell's (query93): the same two buckets
# (packed int64 keys with their combined validity, an 8 Mi bitmap), 375 k
# candidates in a 512 Ki bucket, and _pk_gather_impl itself at its second
# shape, the candidates' bucket
_I64, _BOOL = jnp.int64, jnp.bool_
PROBE_CASES = [
    ("mask", E._probe_mask_impl, "nds.join.candidates",
     [((1 << 19,), jnp.uint64), ((1 << 22,), jnp.uint64)], {"bits": 22}),
    ("narrow", E._probe_narrow_impl, "nds.join.candidates",
     [((1 << 22,), jnp.uint64), ((1 << 16,), _I64)], {}),
    ("widen", E._probe_widen_impl, "nds.join.candidates",
     [((1 << 13,), _I64), ((1 << 13,), jnp.int32),
      ((1 << 13,), jnp.int32)], {"plen": 1 << 22}),
    ("pk_mask", E._pk_mask_impl, "nds.pk_gather.candidates",
     [((1 << 22,), _I64), ((1 << 22,), _BOOL), ((1 << 19,), _I64),
      ((1 << 19,), _BOOL), ((), _I64), ((), _I64), None, None],
     {"bits": 23}),
    ("pk_narrow", E._pk_narrow_impl, "nds.pk_gather.candidates",
     [((1 << 22,), _I64), ((1 << 19,), _I64)], {}),
    ("pk_widen", E._pk_widen_impl, "nds.pk_gather.candidates",
     [((1 << 19,), _I64), ((1 << 19,), _I64), ((1 << 19,), _BOOL)],
     {"plen": 1 << 22}),
    ("pk_search", E._pk_gather_impl, "nds.pk_gather",
     [((1 << 19,), _I64), None, ((1 << 19,), _I64), ((1 << 19,), _BOOL),
      ((), _I64), ((), _I64), None, None], {}),
]


@pytest.mark.parametrize("fn,scope,shapes,static",
                         [c[1:] for c in PROBE_CASES],
                         ids=[c[0] for c in PROBE_CASES])
def test_narrowed_probe_compiles_for_v5e(one_chip, fn, scope, shapes,
                                         static):
    """The three jitted bodies the join probe adds around its searches, the
    three the sorted PK probe adds around its search, and that search at
    the candidates' bucket, compile for a v5e at SF1's buckets, under their
    scope names."""
    args = [None if x is None else
            jax.ShapeDtypeStruct(x[0], x[1], sharding=one_chip)
            for x in shapes]
    lowered = fn.lower(*args, **static)
    assert scope in lowered.as_text(debug_info=True)
    lowered.compile()


# the deferred dimension columns at the resident cells' shapes: a PK
# gather's row index at store_sales' 4 Mi bucket composed with the
# survivors' index (query10: 256 Ki; query93's LEFT arm with its match
# mask: 8 Ki), and the null-extension of three gathered validity masks
COMPOSE_CASES = [
    ("compose", E._compose_impl,
     [((1 << 22,), jnp.int64), None, ((1 << 18,), jnp.int64)]),
    ("compose_left", E._compose_impl,
     [((1 << 22,), jnp.int64), ((1 << 22,), jnp.bool_),
      ((1 << 13,), jnp.int64)]),
    ("null_extend", E._null_extend_impl,
     [(((1 << 13,), jnp.bool_), None, ((1 << 13,), jnp.bool_)),
      ((1 << 13,), jnp.bool_)]),
]


@pytest.mark.parametrize("fn,shapes", [c[1:] for c in COMPOSE_CASES],
                         ids=[c[0] for c in COMPOSE_CASES])
def test_composed_gather_compiles_for_v5e(one_chip, fn, shapes):
    """The two jitted bodies a deferred column group adds to a row gather
    compile for a v5e at SF1's buckets, under the gather's scope name."""
    def struct(x):
        """``(shape, dtype)`` -> a described array; None stays; a tuple of
        them (validity masks) maps through."""
        if x is None:
            return None
        if isinstance(x[0][0], int):
            return jax.ShapeDtypeStruct(x[0], x[1], sharding=one_chip)
        return tuple(struct(y) for y in x)

    lowered = fn.lower(*[struct(x) for x in shapes])
    assert "nds.gather" in lowered.as_text(debug_info=True)
    lowered.compile()


def test_segment_kernel_over_a_mesh_is_refused(topo):
    """REFUSED: ``NotImplementedError: Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map`` —
    what four real v5e chips answered when query3 summed the survivors of
    a ``shards=4`` streamed scan (PR 22). ``kernels._spans_devices`` keeps
    such inputs on the XLA segment ops."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    rows = NamedSharding(Mesh(np.asarray(topo.devices), ("shard",)),
                         P("shard"))
    args = [jax.ShapeDtypeStruct((1024,), dt, sharding=rows)
            for dt in (jnp.int32, jnp.int64)]
    with pytest.raises(NotImplementedError,
                       match="cannot be automatically partitioned"):
        jax.jit(lambda g, v: K._segment_sum_exact_pallas(
            g, v, 64, False)).lower(*args).compile()


def test_inputs_spanning_devices_take_the_xla_segment_ops(monkeypatch):
    """The decision that follows: rows placed over several devices (here
    two of the CPU's virtual ones) never reach a Pallas kernel, in any
    mode, and the XLA twins give the same answer."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    def boom(*a, **k):
        raise AssertionError("a Pallas kernel was handed sharded inputs")
    for name in ("_segment_sum_pallas", "_segment_sum_exact_pallas",
                 "_segment_minmax_pallas"):
        monkeypatch.setattr(K, name, boom)
    monkeypatch.setattr(K, "_pallas_broken", False)
    monkeypatch.setattr(K, "_pallas_mode", lambda: "tpu")
    rows = NamedSharding(Mesh(np.asarray(jax.devices()[:2]), ("shard",)),
                         P("shard"))
    gids = jax.device_put(jnp.arange(8, dtype=jnp.int32) % 4, rows)
    vals = jax.device_put(jnp.arange(8, dtype=jnp.int64), rows)
    assert K._spans_devices(gids, vals)
    assert not K._spans_devices(jnp.arange(8))
    sums, counts = K.segment_sum_exact(vals, gids, 4)
    assert sums.tolist() == [4, 6, 8, 10] and counts.tolist() == [2] * 4
    fsums, fcounts = K.segment_sum_fused(vals.astype(jnp.float32), gids, 4)
    assert fsums.tolist() == [4.0, 6.0, 8.0, 10.0]
    assert fcounts.tolist() == [2.0] * 4
    mins, maxs = K.segment_minmax_fused(vals.astype(jnp.float64), gids, 4)
    assert mins.tolist() == [0.0, 1.0, 2.0, 3.0]
    assert maxs.tolist() == [4.0, 5.0, 6.0, 7.0]


def test_segment_kernel_failure_on_chip_fails_the_query(monkeypatch):
    """Mode ``tpu``: a segment kernel that fails raises — it does not set
    the process-wide flag and carry on with ``jax.ops.segment_*``."""
    def boom(*a, **k):
        raise RuntimeError("injected Mosaic refusal")
    monkeypatch.setattr(K, "_pallas_broken", False)
    monkeypatch.setattr(K, "_pallas_mode", lambda: "tpu")
    for name in ("_segment_sum_pallas", "_segment_sum_exact_pallas",
                 "_segment_minmax_pallas"):
        monkeypatch.setattr(K, name, boom)
    gids = jnp.zeros(8, dtype=jnp.int32)
    with pytest.raises(RuntimeError, match="injected"):
        K.segment_sum_fused(jnp.ones(8, dtype=jnp.float32), gids, 4)
    with pytest.raises(RuntimeError, match="injected"):
        K.segment_sum_exact(jnp.ones(8, dtype=jnp.int64), gids, 4)
    with pytest.raises(RuntimeError, match="injected"):
        K.segment_minmax_fused(jnp.ones(8, dtype=jnp.float64), gids, 4)
    assert K._pallas_broken is False
