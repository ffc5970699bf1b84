# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Distributed (mesh) execution vs the single-device engine: same query,
same data, results must agree — the validation-against-baseline idea
(SURVEY.md §4.1) applied to the sharded path."""

import numpy as np
import pytest

import jax

from nds_tpu.parallel import make_mesh
from nds_tpu.parallel.distributed import (
    broadcast_join_agg, dim_probe_map, replicate, run_distributed_q3,
    shard_fact_columns)

import jax.numpy as jnp

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs the virtual multi-device mesh")


def _q3_data(rng, n_fact=10_000, n_items=200, n_dates=400):
    item = {
        "i_item_sk": np.arange(1, n_items + 1, dtype=np.int64),
        "i_manufact_id": rng.integers(1, 10, n_items).astype(np.int64),
        "i_brand_id": rng.integers(1000, 1020, n_items).astype(np.int64),
    }
    date_dim = {
        "d_date_sk": np.arange(1, n_dates + 1, dtype=np.int64),
        "d_moy": rng.integers(1, 13, n_dates).astype(np.int64),
        "d_year": 1998 + (np.arange(n_dates, dtype=np.int64) // 100),
    }
    store_sales = {
        # some keys miss the dimensions (null-ish fk -> inner-join drop)
        "ss_item_sk": rng.integers(1, n_items + 50, n_fact).astype(np.int64),
        "ss_sold_date_sk": rng.integers(1, n_dates + 30, n_fact).astype(np.int64),
        "ss_ext_sales_price": rng.integers(1, 10_000, n_fact).astype(np.int64),
    }
    return store_sales, date_dim, item


def _q3_reference(store_sales, date_dim, item, manufact, moy):
    """Plain numpy evaluation of the q3 aggregation."""
    i_by_sk = {int(sk): i for i, sk in enumerate(item["i_item_sk"])}
    d_by_sk = {int(sk): i for i, sk in enumerate(date_dim["d_date_sk"])}
    sums = {}
    for fk, dk, w in zip(store_sales["ss_item_sk"],
                         store_sales["ss_sold_date_sk"],
                         store_sales["ss_ext_sales_price"]):
        ii = i_by_sk.get(int(fk))
        di = d_by_sk.get(int(dk))
        if ii is None or di is None:
            continue
        if item["i_manufact_id"][ii] != manufact or date_dim["d_moy"][di] != moy:
            continue
        key = (int(date_dim["d_year"][di]), ii)
        sums[key] = sums.get(key, 0) + int(w)
    return sums


@pytest.mark.parametrize("n_fact", [8_000, 8_001])  # even and uneven shards
def test_distributed_q3_matches_reference(n_fact):
    rng = np.random.default_rng(11)
    store_sales, date_dim, item = _q3_data(rng, n_fact=n_fact)
    manufact, moy = 3, 11
    mesh = make_mesh(min(8, len(jax.devices())))

    out = run_distributed_q3(mesh, store_sales, date_dim, item,
                             manufact_id=manufact, moy=moy)
    ref = _q3_reference(store_sales, date_dim, item, manufact, moy)

    got = {(int(y), int(ii)): float(s)
           for y, ii, s in zip(out["d_year"], out["item_index"], out["sum_agg"])}
    assert set(got) == set(ref)
    for k, v in ref.items():
        assert got[k] == pytest.approx(float(v))


def test_broadcast_join_agg_counts_rows():
    rng = np.random.default_rng(12)
    mesh = make_mesh(min(8, len(jax.devices())))
    n = 4096
    fact_key = rng.integers(1, 100, n).astype(np.int64)
    weights = rng.integers(1, 5, n).astype(np.int64)
    dim_key = np.arange(1, 101, dtype=np.int64)
    codes = (dim_key % 7).astype(np.int64)

    fact, alive = shard_fact_columns(
        mesh, {"k": jnp.asarray(fact_key), "w": jnp.asarray(weights)}, n)
    dks, dorder = dim_probe_map(replicate(mesh, jnp.asarray(dim_key)))
    sums, counts = broadcast_join_agg(
        mesh, {"k": fact["k"], "w": fact["w"]}, alive,
        dks, dorder, replicate(mesh, jnp.asarray(codes)), 7,
        weight_name="w", fact_key_name="k")
    assert int(np.asarray(counts).sum()) == n          # every key matches
    ref = np.zeros(7)
    for k, w in zip(fact_key, weights):
        ref[k % 7] += w
    np.testing.assert_allclose(np.asarray(sums), ref)


def _sql_fixture_tables():
    import pyarrow as pa
    rng = np.random.default_rng(11)
    n = 5000
    sales = pa.table({
        "s_item": pa.array(rng.integers(1, 80, n), pa.int64()),
        "s_date": pa.array(rng.integers(1, 300, n), pa.int64()),
        "s_qty": pa.array(rng.integers(1, 50, n), pa.int64()),
        "s_price": pa.array([None if x % 17 == 0 else int(x)
                             for x in rng.integers(1, 9000, n)], pa.int64()),
        "s_tag": pa.array(rng.choice(["a", "b", "c", None], n)),
    })
    items = pa.table({
        "i_item": pa.array(np.arange(1, 81), pa.int64()),
        "i_cat": pa.array([f"cat{k % 7}" for k in range(80)]),
    })
    dates = pa.table({
        "d_date": pa.array(np.arange(1, 301), pa.int64()),
        "d_year": pa.array(1998 + np.arange(300) // 100, pa.int64()),
    })
    return {"sales": sales, "items": items, "dates": dates}


SQL_CASES = [
    # join + group + order: the flagship shape
    """select d_year, i_cat, sum(s_qty) qty, count(*) cnt, avg(s_price)
       from sales, items, dates
       where s_item = i_item and s_date = d_date and s_qty > 5
       group by d_year, i_cat order by d_year, i_cat""",
    # windows over a join
    """select i_cat, s_qty, rank() over (partition by i_cat order by s_qty desc) r
       from sales, items where s_item = i_item and s_qty > 45
       order by i_cat, r, s_qty limit 50""",
    # semi-join + distinct
    """select distinct s_tag from sales
       where s_item in (select i_item from items where i_cat = 'cat3')
       order by s_tag""",
]


@pytest.mark.parametrize("case", range(len(SQL_CASES)))
def test_spmd_session_matches_single_device(case):
    """The generic engine under a GSPMD mesh (Session mesh_shape) must
    produce exactly the single-device results on every query shape."""
    from nds_tpu.engine.session import Session

    tables = _sql_fixture_tables()
    single = Session()
    meshed = Session(conf={"mesh_shape": 8})
    assert meshed.mesh is not None and meshed.mesh.devices.size == 8
    for name, t in tables.items():
        single.create_temp_view(name, t)
        meshed.create_temp_view(name, t)
    sql = SQL_CASES[case]
    a = single.sql(sql).collect()
    b = meshed.sql(sql).collect()
    assert a == b


def test_exchange_repartition_join_matches_single_device(monkeypatch):
    """Two row-sharded (over-threshold) sides must join through the ICI
    all-to-all exchange and agree with the single-device engine — the
    repartition arm of the broadcast/repartition planner choice."""
    import pyarrow as pa
    from nds_tpu.engine import ops as E
    from nds_tpu.engine.session import Session

    monkeypatch.setenv("NDS_TPU_BROADCAST_BYTES", "64")   # shard everything
    rng = np.random.default_rng(5)
    n = 4096
    a = pa.table({
        "a_k": pa.array(rng.integers(1, 300, n), pa.int64()),
        "a_v": pa.array(rng.integers(1, 1000, n), pa.int64()),
    })
    b = pa.table({
        "b_k": pa.array(rng.integers(1, 300, n), pa.int64()),
        "b_v": pa.array(rng.integers(1, 1000, n), pa.int64()),
    })
    sql = ("select a_k, count(*) c, sum(a_v + b_v) s from a, b "
           "where a_k = b_k and a_v < b_v group by a_k order by a_k")
    single = Session()
    meshed = Session(conf={"mesh_shape": 8})
    for name, t in (("a", a), ("b", b)):
        single.create_temp_view(name, t)
        meshed.create_temp_view(name, t)
    # the meshed run must actually take the exchange path
    calls = []
    orig = E._exchange_inner_join
    monkeypatch.setattr(
        E, "_exchange_inner_join",
        lambda *args, **kw: (calls.append(1), orig(*args, **kw))[1])
    got = meshed.sql(sql).collect()
    assert calls, "repartition join did not engage on sharded inputs"
    assert got == single.sql(sql).collect()


def test_exchange_join_compacts_a_sharded_mask_by_sort(monkeypatch):
    """The exchange join's pair compaction sorts a mask sharded over the
    mesh (a global sort under GSPMD) and keeps exactly the pairs the
    single-device engine keeps."""
    import pyarrow as pa
    from nds_tpu.engine import ops as E
    from nds_tpu.engine.session import Session

    monkeypatch.setenv("NDS_TPU_BROADCAST_BYTES", "64")   # shard everything
    rng = np.random.default_rng(11)
    n = 4096
    a = pa.table({"a_k": pa.array(rng.integers(1, 500, n), pa.int64()),
                  "a_v": pa.array(rng.integers(1, 1000, n), pa.int64())})
    b = pa.table({"b_k": pa.array(rng.integers(1, 500, n), pa.int64()),
                  "b_v": pa.array(rng.integers(1, 1000, n), pa.int64())})
    sql = ("select a_k, a_v, b_v from a, b where a_k = b_k and a_v < b_v "
           "order by a_k, a_v, b_v")
    single = Session()
    meshed = Session(conf={"mesh_shape": 8})
    for name, t in (("a", a), ("b", b)):
        single.create_temp_view(name, t)
        meshed.create_temp_view(name, t)
    in_exchange, sharded = [], []
    orig_join, orig_sorted = E._exchange_inner_join, E._compact_sorted

    def exchange(*args, **kw):
        in_exchange.append(1)
        try:
            return orig_join(*args, **kw)
        finally:
            in_exchange.pop()

    def compact_sorted(mask, cap):
        if in_exchange:
            sharded.append(len(mask.sharding.device_set))
        return orig_sorted(mask, cap)

    monkeypatch.setattr(E, "_exchange_inner_join", exchange)
    monkeypatch.setattr(E, "_compact_sorted", compact_sorted)
    got = meshed.sql(sql).collect()
    assert sharded and max(sharded) == 8, sharded
    assert got == single.sql(sql).collect()


def test_exchange_join_dead_rows():
    """Sentinel (dead) rows — null keys, pad rows, deferred-filter exclusions
    — must not corrupt the bucketize: regression for the unsorted-haystack
    bug where dead rows kept dest=0 while the argsort key sent them to the
    end, so searchsorted misplaced every real row once dead rows dominated
    the binary-search midpoints (silently losing most join pairs)."""
    from nds_tpu.parallel import exchange as X

    mesh = make_mesh(8)
    n = 4096
    for frac in (0.5, 0.95):
        rng_ = np.random.default_rng(3)
        keys = rng_.integers(0, 200, n)
        dead = rng_.random(n) < frac
        row_ids = np.arange(n, dtype=np.uint64)
        # _key_hash_impl sentinel layout: bits 0-1 side tag, bit 2 CLEAR,
        # row id from bit 3; real hashes carry bit 2
        lh = np.where(dead, (row_ids << 3) | 2,
                      (keys.astype(np.uint64) << 3) | 4)
        rh = np.where(dead, (row_ids << 3) | 1,
                      (keys.astype(np.uint64) << 3) | 4)
        rows = jnp.arange(n, dtype=jnp.int64)
        li, ri, live = X.exchange_join_pairs(
            jnp.asarray(lh), rows, jnp.asarray(rh), rows, mesh)
        alive = keys[~dead]
        expect = sum(int(c) * int(c) for c in np.bincount(alive))
        assert int(jnp.sum(live)) == expect
        # every returned pair must be a genuine key match between live rows
        li_n = np.asarray(li)[np.asarray(live)]
        ri_n = np.asarray(ri)[np.asarray(live)]
        assert not dead[li_n].any() and not dead[ri_n].any()
        assert (keys[li_n] == keys[ri_n]).all()


def test_exchange_join_overflow_retry(monkeypatch):
    """Undersized initial capacities must be healed by the doubled-capacity
    retry, not lose rows."""
    from nds_tpu.parallel import exchange as X

    mesh = make_mesh(8)
    rng = np.random.default_rng(9)
    n = 1024
    # skewed keys: most rows share one key -> one destination bucket
    # overflows any per-destination capacity sized for the uniform case
    keys = np.where(rng.random(n) < 0.8, 7, rng.integers(0, 50, n))
    # real hashes always carry bit 2 (_key_hash_impl ors in 4); shift keys
    # past the tag bits so distinct keys stay distinct
    lh = jnp.asarray(((keys.astype(np.uint64) << 3) | 4))
    rh = lh
    rows = jnp.arange(n, dtype=jnp.int64)
    li, ri, live = X.exchange_join_pairs(lh, rows, rh, rows, mesh)
    n_pairs = int(jnp.sum(live))
    expect = sum(int(c) * int(c) for c in np.bincount(keys))
    assert n_pairs == expect
