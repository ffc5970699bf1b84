# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""One process of a real multi-controller federation smoke run.

Launched N times (one per "host") by tests/test_multihost.py or by hand:

    NDS_TPU_MULTIHOST=1 NDS_COORDINATOR=localhost:<port> \
    NDS_NUM_PROCESSES=2 NDS_PROCESS_ID=<i> \
    JAX_PLATFORMS=cpu JAX_CPU_COLLECTIVES_IMPLEMENTATION=gloo \
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    python tools/multihost_worker.py

Each process contributes 4 virtual CPU devices; after
``jax.distributed.initialize`` the global mesh spans 8 devices across the
two processes, the engine row-shards its tables over it, and GSPMD
inserts cross-process (gloo, standing in for DCN) collectives where the
plan needs them — SURVEY.md §5.8 actually executing, where the
reference's analog is a real Spark/MR cluster run (GenTable.java:120-141).

Three arms:

1. a full SQL aggregation (scan -> filter -> group -> sort) through the
   Session over ROW-SHARDED tables — argsort re-coding, segment sums and
   the result gather all cross the process boundary;
2. the ICI/DCN exchange join (`exchange_join_pairs`) driven directly —
   hash bucketize, cross-process all_to_all, local probe, psum'd
   overflow counters — asserting the exact expected pair count;
3. a real STREAMED template through the federation: a >HBM-style
   ChunkedTable scan drives the compiled chunk pipeline
   (engine/stream.py) SHARDED over each host's local device mesh
   (NDS_TPU_STREAM_SHARDS=2) while the multi-controller runtime is
   live — the per-host ICI split of the sharded-streaming design, with
   DCN federation handling cross-host placement. The launcher asserts
   the compiled path, the forced shard count, and bit-for-bit rows
   against a single-process run.

(The full join MATERIALIZATION path is exercised on the single-controller
8-device mesh instead: XLA:CPU+gloo wedges on the very large
sharded-by-sharded gathers it needs, a test-backend limitation — on a TPU
runtime those gathers are ordinary ICI/DCN collectives.)

Process 0 prints one JSON line with both arms' results; the launcher
compares against a single-process run.
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# federation must precede backend CLIENT creation (not the jax import);
# force CPU via config AFTER importing jax, BEFORE initialize, whatever
# set jax_platforms before this file ran
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

from nds_tpu.parallel.multihost import maybe_initialize  # noqa: E402

maybe_initialize()

import numpy as np  # noqa: E402

SQL = ("select a_k, count(*) c, sum(a_v) s from a "
       "where a_v < 500 group by a_k order by a_k")


def make_tables():
    """Deterministic tables, identical on every process (the multi-host
    loader contract: every process must present the same global data)."""
    import pyarrow as pa
    rng = np.random.default_rng(11)
    n = 4096
    a = pa.table({
        "a_k": pa.array(rng.integers(0, 40, n), pa.int64()),
        "a_v": pa.array(rng.integers(0, 1000, n), pa.int64()),
    })
    return a


# the exchange arm's key distribution — single source of truth shared
# with the launcher's ground-truth computation (tests/test_multihost.py)
EXCHANGE_SEED, EXCHANGE_N, EXCHANGE_KEYS = 3, 4096, 200


def exchange_keys():
    rng = np.random.default_rng(EXCHANGE_SEED)
    return rng.integers(0, EXCHANGE_KEYS, EXCHANGE_N)


STREAM_SQL = ("select f_k, count(*) c, sum(f_v) s from f "
              "where f_v > 100 group by f_k order by f_k")

STREAM_CHUNK_ROWS, STREAM_SHARDS = 2048, 2


def make_stream_tables():
    """Deterministic chunked fact for the streamed arm (4 chunks), built
    identically on every process and by the launcher's ground truth."""
    import pyarrow as pa
    rng = np.random.default_rng(7)
    n = 8192
    return pa.table({
        "f_k": pa.array(rng.integers(0, 25, n), pa.int64()),
        "f_v": pa.array(rng.integers(0, 1000, n), pa.int64()),
    })


def streamed_arm():
    """Drive a real streamed template through the compiled chunk
    pipeline, sharded over this host's local mesh, under the live
    federation. Returns (rows, stream event) for the launcher to check
    path/shards/bit-for-bit correctness."""
    from nds_tpu.engine.session import Session
    from nds_tpu.engine.table import ChunkedTable
    from nds_tpu.listener import drain_stream_events
    os.environ["NDS_TPU_STREAM_SHARDS"] = str(STREAM_SHARDS)
    os.environ["NDS_TPU_STREAM_STRICT"] = "1"
    try:
        sess = Session()
        sess.create_temp_view(
            "f", ChunkedTable(make_stream_tables(),
                              chunk_rows=STREAM_CHUNK_ROWS), base=True)
        drain_stream_events()
        rows = sess.sql(STREAM_SQL).collect()
        events = drain_stream_events()
        ev = events[0] if events else None
        return rows, ({"path": ev.path, "shards": ev.shards,
                       "chunks": ev.chunks, "collectives": ev.collectives}
                      if ev else None)
    finally:
        del os.environ["NDS_TPU_STREAM_SHARDS"]
        del os.environ["NDS_TPU_STREAM_STRICT"]


def exchange_arm(mesh):
    """Direct cross-process exchange join; returns the verified pair
    count (launcher asserts it against the host-side expectation)."""
    import jax.numpy as jnp

    from jax.sharding import NamedSharding, PartitionSpec as P
    from nds_tpu.parallel.exchange import exchange_join_pairs
    sh = NamedSharding(mesh, P("part"))
    n = EXCHANGE_N
    keys = exchange_keys()
    h = jax.device_put(jnp.asarray((keys.astype(np.uint64) << 3) | 4), sh)
    rows = jax.device_put(jnp.arange(n, dtype=jnp.int64), sh)
    li, ri, live = exchange_join_pairs(h, rows, h, rows, mesh)
    return int(jnp.sum(live))


def main():
    import faulthandler
    wd = float(os.environ.get("NDS_MULTIHOST_WATCHDOG_S", "0"))
    if wd:
        faulthandler.dump_traceback_later(wd, exit=True)
    assert jax.process_count() == int(os.environ["NDS_NUM_PROCESSES"]), \
        f"federation failed: {jax.process_count()} processes"
    n_dev = len(jax.devices())
    from nds_tpu.engine.session import Session
    # broadcast threshold forced tiny so the table ROW-SHARDS over the
    # cross-process mesh — the query's collectives must cross processes
    sess = Session(conf={"mesh_shape": n_dev, "broadcast_bytes": 2048})
    sess.create_temp_view("a", make_tables())
    rows = sess.sql(SQL).collect()
    pairs = exchange_arm(sess.mesh)
    stream_rows, stream_ev = streamed_arm()
    if jax.process_index() == 0:
        print(json.dumps({"n_devices": n_dev, "pairs": pairs,
                          "rows": [list(r) for r in rows],
                          "streamRows": [list(r) for r in stream_rows],
                          "streamEvent": stream_ev}), flush=True)
    # every process must reach the barrier or the others hang in a
    # collective; sync before exit
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices("nds-multihost-smoke-done")


if __name__ == "__main__":
    main()
