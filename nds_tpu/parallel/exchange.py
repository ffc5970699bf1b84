# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""ICI exchange: the TPU-native replacement for the network shuffle.

The reference's accelerated stack moves shuffle data through the RAPIDS
UCX shuffle manager between Spark executors (SURVEY.md §2.2 N4, §5.8). On a
TPU pod the same role is played by XLA collectives over ICI: a fixed-capacity
``all_to_all`` repartitions rows by key hash between chips (hash-exchange
joins / aggregations), ``psum`` reduces partial aggregates (pre-aggregated
group-by), and ``all_gather`` broadcasts build sides (broadcast joins).

XLA requires static shapes, so the exchange uses capacity-bucketed send
buffers: each device packs its rows into a ``(P, capacity)`` buffer slotted
by destination device, with a validity plane marking real rows. Capacity is a
planner choice (rows_per_device / P × slack); overflow is detectable via
``bucket_overflow`` so the planner can re-run with a bigger capacity — the
static-shape analog of a shuffle spill.
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# ---------------------------------------------------------------------------
# collective accounting: the runtime half of the static collective budget
# (analysis/exec_audit.py). Every explicit ICI collective this module (or
# the sharded streamed pipeline, engine/stream.py) issues notes itself at
# TRACE time — the note runs once per compiled program, so a program's
# collective count is captured when its first dispatch traces and is then
# exact for every later dispatch. tools/exec_audit_diff.py checks the
# resulting ``StreamEvent.collectives``/``bytes_ici`` evidence against the
# audit's per-statement budget. GSPMD-inserted data-placement copies
# (replicated operand broadcast) are not collectives of the pipeline's
# programs and are out of scope by definition.
# ---------------------------------------------------------------------------

_coll_tls = threading.local()


class _CollectiveTrace:
    def __enter__(self):
        self._prev = getattr(_coll_tls, "counts", None)
        self.counts = {"a2a": 0, "psum": 0, "all_gather": 0, "bytes": 0}
        _coll_tls.counts = self.counts
        return self

    def __exit__(self, *exc):
        _coll_tls.counts = self._prev


def collective_trace():
    """Context collecting (at trace time) the explicit collective ops and
    their wire bytes issued while tracing one jitted program."""
    return _CollectiveTrace()


def _note_collective(kind: str, n: int = 1, nbytes: int = 0) -> None:
    c = getattr(_coll_tls, "counts", None)
    if c is not None:
        c[kind] += n
        c["bytes"] += int(nbytes)


def _aval_bytes(x) -> int:
    """Static byte size of a (traced or concrete) array — the wire bytes
    one collective moves, readable at trace time from shape metadata."""
    try:
        return int(np.prod(x.shape)) * x.dtype.itemsize
    except Exception:
        return 0


def psum_counted(x, axis: str):
    """``jax.lax.psum`` with collective accounting (use inside shard_map
    bodies the streamed pipeline compiles)."""
    _note_collective("psum", 1, _aval_bytes(x))
    return jax.lax.psum(x, axis)


def all_gather_counted(x, axis: str, tiled: bool = True):
    """``jax.lax.all_gather`` with collective accounting."""
    _note_collective("all_gather", 1, _aval_bytes(x))
    return jax.lax.all_gather(x, axis, tiled=tiled)


def shard_map_compat(fn, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication check off — the engine's
    bodies are manual SPMD by design."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(n_devices: int | None = None, axis: str = "part") -> Mesh:
    """1-D device mesh over the row-partition axis.

    Intra-query parallelism in the reference is Spark tasks over file splits
    (SURVEY.md §2.4.1); here it is row shards over mesh devices, with ICI
    collectives where Spark would shuffle.
    """
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), (axis,))


def hash_partition_dest(key: jnp.ndarray, n_parts: int) -> jnp.ndarray:
    """Destination partition of each row: mix the key then mod P (the hash
    exchange's partitioning function)."""
    x = key.astype(jnp.uint64)
    x = (x ^ (x >> 30)) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> 27)) * jnp.uint64(0x94D049BB133111EB)
    x = x ^ (x >> 31)
    return (x % jnp.uint64(n_parts)).astype(jnp.int32)


def bucketize(dest: jnp.ndarray, cols: dict, n_parts: int, capacity: int):
    """Pack rows into per-destination send buffers.

    Returns (buffers, valid, overflow): ``buffers[name]`` is ``(P, capacity)``
    with rows grouped by destination, ``valid`` marks occupied slots, and
    ``overflow`` counts rows dropped because a destination bucket was full
    (0 on a correctly-capacity-planned run).
    """
    n = dest.shape[0]
    order = jnp.argsort(dest)
    sd = jnp.take(dest, order)
    # slot of each row within its destination bucket
    first = jnp.searchsorted(sd, sd, side="left")
    pos = jnp.arange(n) - first
    fits = pos < capacity
    overflow = jnp.sum(~fits)
    valid = jnp.zeros((n_parts, capacity), dtype=bool).at[sd, pos].set(
        fits, mode="drop")
    bufs = {}
    for name, arr in cols.items():
        v = jnp.take(arr, order)
        buf = jnp.zeros((n_parts, capacity), dtype=arr.dtype).at[sd, pos].set(
            jnp.where(fits, v, jnp.zeros((), dtype=arr.dtype)), mode="drop")
        bufs[name] = buf
    return bufs, valid, overflow


def all_to_all_exchange(bufs: dict, valid: jnp.ndarray, axis: str = "part"):
    """The ICI all-to-all: bucket j of every device lands on device j.

    Inside ``shard_map`` only. After the exchange each device holds
    ``(P, capacity)`` rows — one bucket from every peer — all sharing its key
    range.
    """
    _note_collective("a2a", len(bufs) + 1,
                     sum(_aval_bytes(b) for b in bufs.values())
                     + _aval_bytes(valid))
    out = {name: jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=0)
           for name, buf in bufs.items()}
    vout = jax.lax.all_to_all(valid, axis, split_axis=0, concat_axis=0)
    return out, vout


def sharded_filter_agg_step(mesh: Mesh, num_groups: int, capacity: int,
                            axis: str = "part"):
    """Build the jitted partitioned filter→exchange→aggregate step.

    The flagship distributed query step (the TPU analog of one Spark stage
    pair around a hash exchange, ref: nds/power_run_gpu.template:29-30 shuffle
    partition knobs): each device filters its row shard, repartitions
    surviving rows by group-key hash over ICI, locally segment-aggregates its
    key range, and a final ``psum`` of the group counts cross-checks that no
    row was lost. Returns a function of sharded columns:

        (group_key i32[N], qty i64[N], sold i32[N], lo, hi)
            -> (sums i64[G_local per device], counts i64[G], total i64)
    """
    n_parts = mesh.devices.size

    def local_step(group_key, qty, sold, lo, hi):
        # filter: NULL-free predicate on the date column (masked rows keep
        # slot but zero weight — static shapes, no compaction)
        keep = (sold >= lo) & (sold <= hi)
        dest = hash_partition_dest(group_key.astype(jnp.uint64), n_parts)
        # dead rows all route to bucket of key 0 with zero weight; cheaper is
        # keeping them in place with weight 0 so buckets stay balanced
        w = jnp.where(keep, qty, jnp.zeros((), dtype=qty.dtype))
        bufs, valid, _ = bucketize(
            dest, {"key": group_key, "w": w}, n_parts, capacity)
        ex, vex = all_to_all_exchange(bufs, valid, axis)
        keys = ex["key"].reshape(-1)
        wts = ex["w"].reshape(-1)
        vflat = vex.reshape(-1)
        # this device owns group ids g with hash(g)%P == my index; segment-sum
        # over the full group-id space, zero elsewhere
        gids = jnp.clip(keys, 0, num_groups - 1)
        w_live = jnp.where(vflat, wts, jnp.zeros((), dtype=wts.dtype))
        sums = jax.ops.segment_sum(w_live, gids, num_segments=num_groups)
        ones = jnp.where(vflat, jnp.ones_like(wts), jnp.zeros_like(wts))
        counts_local = jax.ops.segment_sum(ones, gids, num_segments=num_groups)
        counts = jax.lax.psum(counts_local, axis)
        total = jax.lax.psum(jnp.sum(w_live), axis)
        return sums, counts, total

    sharded = shard_map_compat(
        local_step, mesh,
        in_specs=(P(axis), P(axis), P(axis), P(), P()),
        out_specs=(P(axis), P(), P()))
    in_shardings = (
        NamedSharding(mesh, P(axis)), NamedSharding(mesh, P(axis)),
        NamedSharding(mesh, P(axis)), NamedSharding(mesh, P()),
        NamedSharding(mesh, P()))
    return jax.jit(sharded, in_shardings=in_shardings)


def stream_mesh_axis() -> str:
    """``NDS_TPU_STREAM_MESH_AXIS``: name of the streamed pipeline's mesh
    axis (default ``shard``; must differ from the session mesh's ``part``
    axis when both are active)."""
    import os
    return os.environ.get("NDS_TPU_STREAM_MESH_AXIS", "shard")


# mesh cache: concurrent Throughput streams building sharded pipelines
# share it, so mutations take the dedicated lock (double-checked insert —
# the Mesh constructor is pure host object construction, legal under the
# lock; no host read or jit compile ever runs here)
_STREAM_MESHES: dict = {}
_MESH_LOCK = threading.Lock()


def stream_mesh(n_shards: int, axis: str | None = None) -> Mesh | None:
    """LOCAL-device 1-D mesh the sharded streamed pipeline runs over, or
    None when this process has fewer than ``n_shards`` local devices
    (the pipeline then builds unsharded). Local by design: chunk sharding
    is an ICI-level optimization of one host's scan; cross-host (DCN)
    distribution stays the loader's ``host_shard_range`` split, so a
    federated Power Run shards its local chunk pipelines under the
    multi-controller runtime without any cross-host collective."""
    axis = axis or stream_mesh_axis()
    key = (int(n_shards), axis)
    m = _STREAM_MESHES.get(key)
    if m is None:
        devs = jax.local_devices()
        if len(devs) < n_shards:
            return None
        with _MESH_LOCK:
            m = _STREAM_MESHES.get(key)
            if m is None:
                m = _STREAM_MESHES[key] = Mesh(np.asarray(devs[:n_shards]),
                                               (axis,))
    return m


def mesh_of(*arrays):
    """The >1-device mesh a set of arrays is row-sharded over, or None.
    Arrays are self-describing (their NamedSharding carries the mesh), so
    the engine needs no session plumbing to detect distributed inputs."""
    for a in arrays:
        sh = getattr(a, "sharding", None)
        if isinstance(sh, NamedSharding) and sh.mesh.devices.size > 1 and \
                any(s is not None for s in sh.spec):
            return sh.mesh
    return None


def _pow2(n: int) -> int:
    p = 16
    while p < n:
        p *= 2
    return p


def _exchange_join_step(mesh, cap_in: int, pair_cap: int, axis: str):
    """Jitted shard_map step of the repartition join: hash-bucketize both
    sides' (hash, global row id) pairs, all_to_all them so equal hashes
    co-locate, then locally sort/probe and emit matched row-id pairs at
    fixed capacity. Overflow counts come back host-visible so the caller
    can retry with doubled capacities (the static-shape analog of a
    shuffle spill; SURVEY.md §5.8)."""
    n_parts = mesh.devices.size

    def local(lh, lrow, rh, rrow):
        out = []
        for h, row in ((lh, lrow), (rh, rrow)):
            # bit 2 marks a REAL (matchable) hash (_key_hash_impl tags
            # unmatchable rows with per-row sentinels); dead rows are
            # dropped before the exchange so they never consume capacity
            real = (h & jnp.uint64(4)) != 0
            # dead rows route to bucket n_parts — past the last real bucket,
            # so the argsort key IS dest and the sorted ``sd`` stays a valid
            # searchsorted haystack (taking the raw dest, with dead rows at
            # 0, left sd unsorted whenever dead rows existed and the binary
            # search then misplaced real rows). Out-of-range sd drops out of
            # both the scatter (mode="drop") and the segment_sum below.
            dest = jnp.where(real, hash_partition_dest(h, n_parts),
                             jnp.int32(n_parts))
            n = h.shape[0]
            order = jnp.argsort(dest)
            sd = jnp.take(dest, order)
            sreal = jnp.take(real, order)
            first = jnp.searchsorted(sd, sd, side="left")
            pos = jnp.arange(n) - first
            fits = (pos < cap_in) & sreal
            # deficit (not count): the retry sizes capacity in ONE step
            # even under quadratic key skew
            bucket_counts = jax.ops.segment_sum(
                sreal.astype(jnp.int64), sd, num_segments=n_parts)
            over = jnp.maximum(jnp.max(bucket_counts) - cap_in, 0)
            valid = jnp.zeros((n_parts, cap_in), dtype=bool).at[
                sd, pos].set(fits, mode="drop")
            bufs = {}
            for name, arr in (("h", jnp.take(h, order)),
                              ("row", jnp.take(row, order))):
                bufs[name] = jnp.zeros(
                    (n_parts, cap_in), dtype=arr.dtype).at[sd, pos].set(
                    jnp.where(fits, arr, jnp.zeros((), dtype=arr.dtype)),
                    mode="drop")
            ex, vex = all_to_all_exchange(bufs, valid, axis)
            out.append((ex["h"].reshape(-1), ex["row"].reshape(-1),
                        vex.reshape(-1), over))
        (lhx, lrx, lvx, lover), (rhx, rrx, rvx, rover) = out
        # local probe: equal hashes are now co-resident on this device
        m = rhx.shape[0]
        rh_key = jnp.where(rvx, rhx, jnp.uint64(0))     # invalid -> hash 0
        rorder = jnp.argsort(rh_key)
        rh_sorted = jnp.take(rh_key, rorder)
        lh_key = jnp.where(lvx, lhx, jnp.uint64(1))     # never matches 0
        lo = jnp.searchsorted(rh_sorted, lh_key, side="left")
        hi = jnp.searchsorted(rh_sorted, lh_key, side="right")
        counts = jnp.where(lvx, hi - lo, 0)
        total = jnp.sum(counts)
        l_pos = jnp.repeat(jnp.arange(m), counts,
                           total_repeat_length=pair_cap)
        starts = jnp.cumsum(counts) - counts
        pos = jnp.arange(pair_cap) - jnp.repeat(starts, counts,
                                                total_repeat_length=pair_cap)
        r_pos = jnp.repeat(lo, counts, total_repeat_length=pair_cap) + pos
        pair_live = jnp.arange(pair_cap) < jnp.minimum(total, pair_cap)
        l_out = jnp.take(lrx, l_pos, mode="clip")
        r_out = jnp.take(rrx, jnp.take(rorder, jnp.clip(r_pos, 0, m - 1)),
                         mode="clip")
        p_over = jnp.maximum(total - pair_cap, 0)
        overs = jax.lax.pmax(
            jnp.stack([lover.astype(jnp.int64), rover.astype(jnp.int64),
                       p_over.astype(jnp.int64)]), axis)
        return l_out, r_out, pair_live, overs

    sharded = shard_map_compat(
        local, mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis), P(axis), P()))
    return jax.jit(sharded)


# jitted exchange-step cache: building the jax.jit WRAPPER is lazy and
# cheap (the underlying compile happens at first dispatch, off-lock);
# setdefault-under-lock keeps one winner per key so concurrent streams
# dispatch the same wrapper and XLA compiles each shape exactly once
_exchange_step_cache: dict = {}
_EXCHANGE_STEP_LOCK = threading.Lock()


def exchange_join_pairs(lh, lrow, rh, rrow, mesh, axis: str = "part"):
    """Repartition (all-to-all) join of two row-sharded hash columns.

    Returns ``(l_idx, r_idx, pair_live)`` — global row-id pairs whose
    hashes matched, at a fixed capacity with a validity mask — after
    retrying with doubled capacities whenever a bucket or the pair buffer
    overflowed (detected via the psum'd overflow counters; the implemented
    overflow recovery the capacity-bucket design calls for)."""
    n_parts = mesh.devices.size
    n_l, n_r = int(lh.shape[0]), int(rh.shape[0])
    # expected rows per (device, destination) bucket with 2x slack
    cap_in = _pow2(max(n_l, n_r) * 2 // (n_parts * n_parts) + 16)
    pair_cap = _pow2(max(n_l, n_r) * 2 // n_parts + 16)
    for _ in range(5):
        key = (id(mesh), cap_in, pair_cap, axis)
        step = _exchange_step_cache.get(key)
        if step is None:
            built = _exchange_join_step(mesh, cap_in, pair_cap, axis)
            with _EXCHANGE_STEP_LOCK:
                step = _exchange_step_cache.setdefault(key, built)
        l_idx, r_idx, live, overs = step(lh, lrow, rh, rrow)
        from nds_tpu.engine.ops import timed_read
        lo, ro, po = timed_read(
            "exch_overs", lambda: tuple(int(x) for x in overs))
        if lo == 0 and ro == 0 and po == 0:
            return l_idx, r_idx, live
        # overs carry the max DEFICIT, so one retry reaches a sufficient
        # capacity even under quadratic key skew. A retry is a recovered
        # task failure in the reference's taxonomy (a shuffle spill/retry):
        # surface it to the run's failure listener.
        if lo or ro:
            cap_in = _pow2(cap_in + max(lo, ro))
        if po:
            pair_cap = _pow2(pair_cap + po)
        from nds_tpu.listener import report_task_failure
        report_task_failure(
            "exchange join capacity retry",
            f"bucket deficit l={lo} r={ro}, pair deficit {po}; "
            f"retrying with cap_in={cap_in}, pair_cap={pair_cap}")
    raise RuntimeError("exchange join: capacity retry limit exceeded")
