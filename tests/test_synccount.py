# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Host-sync budget tests (DESIGN.md reduction items 1+3).

Every device->host scalar read flushes the dispatch queue, costs a round
trip to the chip, and is a full-mesh barrier under GSPMD
— the reference's Spark driver pays ONE round trip per query
(ref: nds/nds_power.py:125-135, spark.sql(q).collect()). These tests pin
the engine's per-query budget so a regression back to per-operator syncs
fails loudly, and verify the lazy/batched machinery is exact.
"""

import contextlib

import numpy as np
import pyarrow as pa
import pytest

import jax.numpy as jnp

from nds_tpu.engine import ops as E
from nds_tpu.engine.session import Session
from nds_tpu.obs import export as obs_export


def _syncs():
    return E.sync_count()


@pytest.fixture
def star_session(rng):
    n_fact, n_dim = 20_000, 365
    s = Session()
    s.create_temp_view("date_dim", pa.table({
        "d_date_sk": pa.array(np.arange(1, n_dim + 1), pa.int64()),
        "d_year": pa.array(1998 + np.arange(n_dim) // 120, pa.int64()),
        "d_moy": pa.array(1 + (np.arange(n_dim) // 30) % 12, pa.int64()),
    }), base=True)
    s.create_temp_view("item", pa.table({
        "i_item_sk": pa.array(np.arange(1, 201), pa.int64()),
        "i_brand_id": pa.array(rng.integers(1000, 1020, 200), pa.int64()),
    }), base=True)
    s.create_temp_view("store_sales", pa.table({
        "ss_sold_date_sk": pa.array(
            rng.integers(1, n_dim + 40, n_fact), pa.int64()),
        "ss_item_sk": pa.array(rng.integers(1, 230, n_fact), pa.int64()),
        "ss_ext_sales_price": pa.array(
            rng.integers(1, 10_000, n_fact), pa.int64()),
    }), base=True)
    return s


_STAR_Q = """
        select d_year, i_brand_id, sum(ss_ext_sales_price) s
        from store_sales, date_dim, item
        where ss_sold_date_sk = d_date_sk and ss_item_sk = i_item_sk
          and d_moy = 11
        group by d_year, i_brand_id
        order by d_year, s desc
    """


def test_star_join_sync_budget(star_session):
    """Filter + star join + group + order by on base tables: the PK-gather
    star fold is sync-free, filters defer or compact lazily, and the
    aggregation/output resolves batched — the whole query must fit the
    <=3-sync budget DESIGN.md targets (vs 10-25 before lazy counts)."""
    before = _syncs()
    rows = star_session.sql(_STAR_Q).collect()
    used = _syncs() - before
    assert rows, "query unexpectedly empty"
    assert used <= 3, f"star query used {used} host syncs (budget 3)"


def test_lazy_compact_exact(rng):
    """Lazy (no-sync) compaction must keep live rows, in order, at the
    prefix, and resolve to the exact count."""
    n = 5_000
    vals = rng.integers(0, 100, n)
    t = Session()
    t.create_temp_view("t", pa.table({"v": pa.array(vals, pa.int64())}))
    dt = t.catalog["t"]
    mask = dt["v"].data < 30
    before = _syncs()
    out = E.compact_table(dt, mask)
    assert _syncs() == before, "lazy compact must not sync"
    assert isinstance(out.nrows, E.DeviceCount)
    expect = vals[vals < 30]
    got = np.asarray(out["v"].data)[:E.count_int(out.nrows)]
    np.testing.assert_array_equal(got, expect)
    # resolve_table shrinks to the tight bucket
    res = E.resolve_table(out)
    assert res.plen == E.bucket_len(len(expect))
    np.testing.assert_array_equal(np.asarray(res["v"].data)[:res.nrows],
                                  expect)


_COMPACT_ROWS = 5_000                     # input bucket 8192


def _compact_fixture(rng):
    """A device table with a plain, a nullable and a string column, its
    Arrow twin, and the plain column's values."""
    n = _COMPACT_ROWS
    v = rng.integers(0, 100, n)
    w = rng.integers(0, 1000, n)
    w_null = rng.random(n) < 0.2
    arrow = pa.table({
        "v": pa.array(v, pa.int64()),
        "w": pa.array([None if z else int(x) for x, z in zip(w, w_null)],
                      pa.int64()),
        "s": pa.array([f"name{i % 37}" for i in range(n)]),
    })
    s = Session()
    s.create_temp_view("t", arrow)
    return s.catalog["t"], arrow, v


@pytest.mark.parametrize("keep", ["none", "few", "half", "all"])
@pytest.mark.parametrize("arm", ["count_first", "lazy", "stream_bounds"])
def test_compact_counts_before_it_gathers(rng, monkeypatch, arm, keep):
    """Past NDS_TPU_LAZY_SHRINK_ROWS compact_table reads the count it was
    going to read anyway BEFORE it builds indices, so the row gather runs
    at the survivors' bucket: one counted sync, a host count, and the
    ``op.gather`` span's ``cells`` follow ``bucket_len(n)``, not the input
    bucket. Under the threshold, and inside a stream-bounds region whatever
    the threshold, the lazy arm is what it was: no read, a DeviceCount, the
    producer's bucket. Rows and their order equal NumPy's on every arm."""
    from nds_tpu.obs import trace as obs_trace
    dt, arrow, v = _compact_fixture(rng)
    in_bucket = E.bucket_len(_COMPACT_ROWS)
    monkeypatch.setenv(
        "NDS_TPU_LAZY_SHRINK_ROWS",
        str(in_bucket if arm == "lazy" else in_bucket // 2))
    cut = {"none": -1, "few": 0, "half": 49, "all": 100}[keep]
    mask = dt["v"].data <= cut
    expect = arrow.filter(pa.array(v <= cut))
    n = expect.num_rows
    E.resolve_counts()                    # start from a drained thread
    obs_trace.drain_spans()
    before = _syncs()
    with (E.stream_bounds() if arm == "stream_bounds"
          else contextlib.nullcontext()):
        out = E.compact_table(dt, mask)
    used = _syncs() - before
    gathers = [r for r in obs_trace.drain_spans()
               if isinstance(r, obs_trace.SpanRecord)
               and r.name == "op.gather"]
    arrays = sum(1 + (c.valid is not None) for c in dt.columns.values())
    assert len(gathers) == 1
    if arm == "count_first":
        assert used == 1, f"count-first compact made {used} syncs"
        assert out.nrows == n and isinstance(out.nrows, int)
        assert out.plen == E.bucket_len(n)
    else:
        assert used == 0, "lazy compact must not sync"
        assert isinstance(out.nrows, E.DeviceCount)
        assert out.plen == in_bucket
    assert gathers[0].attrs["cells"] == out.plen * arrays
    assert obs_export.rollup(gathers)["phases"]["op.gather"]["cells"] \
        == out.plen * arrays
    res = E.resolve_table(out)
    assert res.nrows == n and res.plen == E.bucket_len(n)
    got = res.to_arrow()
    assert got.equals(expect.cast(got.schema))


def test_count_first_compact_replays_with_sync_parity(star_session,
                                                      monkeypatch):
    """The star statement with the threshold under the fact's bucket, so
    the chain's compaction takes the count-first arm: eager, recorded and
    replayed executions give equal rows; the eager and the recorded one
    make the same reads outside the recorder's own (``dense_dim``), one of
    them at the compaction, inside the star budget; the recording compiles
    (the index shape follows the logged count, no ReplayMismatch) and the
    replayed executions make the one result read."""
    from nds_tpu.obs import trace as obs_trace
    monkeypatch.setenv("NDS_TPU_REPLAY", "force")
    monkeypatch.setenv("NDS_TPU_LAZY_SHRINK_ROWS", "1024")
    s = star_session
    fact = s.catalog["store_sales"]
    E.resolve_counts()
    obs_trace.drain_spans()
    runs = []
    for _ in range(4):                    # eager, record + compile, replay x2
        before = _syncs()
        rows = s.sql(_STAR_Q).collect()
        roll = obs_export.rollup(obs_trace.drain_spans(), top_sites=20)
        own = {(x["site"], x["tag"]): x["syncs"] for x in roll["syncSites"]
               if x["tag"] != "dense_dim"}
        runs.append((rows, _syncs() - before, own, roll["phases"]))
    (r0, n0, own0, ph0), (r1, _n1, own1, ph1), (r2, n2, _, ph2), \
        (r3, n3, _, _) = runs
    assert r0 and r0 == r1 == r2 == r3
    assert n0 <= 3, f"star query used {n0} host syncs (budget 3)"
    assert own0 == own1 and sum(own0.values()) == n0
    assert [n for (site, _t), n in own0.items() if "_join_parts" in site] \
        == [1], own0
    assert "replay.compile" in ph1 and s._replay_cache
    assert "replay.drive" in ph2 and n2 == n3 <= 1
    # the compaction gathered at the survivors' bucket in both tiers
    assert ph0["op.gather"]["cells"] == ph1["op.gather"]["cells"]
    assert ph0["op.gather"]["cells"] < fact.plen * 2 * len(fact.columns)


def test_narrowed_probe_replays_with_sync_parity(star_session, monkeypatch):
    """A fact-to-fact hash join with the threshold under the probe side's
    bucket, so the probe reads its candidates' count and searches at their
    bucket: eager, recorded and replayed executions give equal rows, the
    rows of the full-width search; the eager and the recorded one make the
    same reads; the recording compiles (the narrowed shapes follow the
    logged count, no ReplayMismatch)."""
    from nds_tpu.obs import trace as obs_trace
    q = """
        select a.ss_item_sk, count(*) c, sum(b.ss_ext_sales_price) s
        from store_sales a, store_sales b
        where a.ss_sold_date_sk = b.ss_sold_date_sk
          and a.ss_item_sk = b.ss_item_sk and a.ss_ext_sales_price < 500
        group by a.ss_item_sk order by a.ss_item_sk
    """
    s = star_session
    fact = s.catalog["store_sales"]
    want = s.sql(q).collect()             # full width: default threshold
    monkeypatch.setenv("NDS_TPU_REPLAY", "force")
    monkeypatch.setenv("NDS_TPU_LAZY_SHRINK_ROWS", "1024")
    E.resolve_counts()
    obs_trace.drain_spans()
    runs = []
    for _ in range(4):                    # eager, record + compile, replay x2
        before = _syncs()
        rows = s.sql(q).collect()
        roll = obs_export.rollup(obs_trace.drain_spans(), top_sites=20)
        own = {(x["site"], x["tag"]): x["syncs"] for x in roll["syncSites"]
               if x["tag"] != "dense_dim"}
        runs.append((rows, _syncs() - before, own, roll["phases"]))
    (r0, n0, own0, ph0), (r1, _n1, own1, ph1), (r2, n2, _, ph2), \
        (r3, n3, _, _) = runs
    assert want and want == r0 == r1 == r2 == r3
    assert own0 == own1 and sum(own0.values()) == n0
    # the join's site: the candidates' count, then the candidate total
    assert [n for (site, _t), n in own0.items() if "_join_parts" in site] \
        == [2], own0
    assert "replay.compile" in ph1 and s._replay_cache
    assert "replay.drive" in ph2 and n2 == n3 <= 1
    # the two searches ran under the fact's bucket in both tiers
    assert ph0["op.join"]["probeRows"] == ph1["op.join"]["probeRows"] \
        < fact.plen


_PROBE_ROWS, _BUILD_ROWS = 5_000, 700     # buckets 8192 and 1024


def _probe_tables(rng, keys, dense, build_rows):
    """A probe and a build table with duplicate keys on both sides (device
    tables, and the probe side's bucket). ``keys``: ``int``, ``nullable``
    (a fifth of each side's keys null) or ``int+str`` (a two-column key
    whose second column is a string pair over different dictionaries).
    ``dense``: every probe key is one of the build side's 300, so no
    candidate bucket is under the probe's; else one in about sixty is."""
    n, m = _PROBE_ROWS, build_rows
    lk = rng.integers(0, 300 if dense else 20_000, n)
    rk = rng.integers(0, 300, m)

    def key(v):
        if keys != "nullable":
            return pa.array(v, pa.int64())
        return pa.array([None if z else int(x) for x, z in
                         zip(v, rng.random(len(v)) < 0.2)], pa.int64())
    left = {"lk": key(lk), "lv": pa.array(np.arange(n), pa.int64())}
    right = {"rk": key(rk), "rv": pa.array(np.arange(m), pa.int64())}
    if keys == "int+str":
        left["ls"] = pa.array([f"s{x % 7}" for x in lk])
        right["rs"] = pa.array([f"s{x % 5}" for x in rk])
    s = Session()
    s.create_temp_view("l", pa.table(left))
    s.create_temp_view("r", pa.table(right))
    return s.catalog["l"], s.catalog["r"]


def _excl(rng, plen, which):
    return {"none": None, "all": jnp.ones(plen, dtype=bool),
            "some": jnp.asarray(rng.random(plen) < 0.4)}[which]


# (id, how, null_safe, keys, l_excl, r_excl, dense, build rows, what runs)
_PROBE_CASES = [
    ("inner", "inner", False, "int", "none", "none", False, _BUILD_ROWS, ""),
    ("left", "left", False, "int", "some", "none", False, _BUILD_ROWS, ""),
    ("right", "right", False, "int", "none", "some", False, _BUILD_ROWS, ""),
    ("full", "full", False, "int", "some", "some", False, _BUILD_ROWS, ""),
    ("nulls", "full", False, "nullable", "none", "none", False, _BUILD_ROWS,
     ""),
    ("null_safe", "inner", True, "nullable", "none", "none", False,
     _BUILD_ROWS, ""),
    ("l_excl_all", "left", False, "int", "all", "none", False, _BUILD_ROWS,
     ""),
    ("r_excl_all", "full", False, "int", "none", "all", False, _BUILD_ROWS,
     ""),
    ("empty_build", "left", False, "int", "none", "none", False, 0, ""),
    ("string_pair", "inner", False, "int+str", "some", "none", False,
     _BUILD_ROWS, "semi"),
    ("chunked", "inner", False, "int", "none", "none", False, _BUILD_ROWS,
     "chunked"),
    # every probe row is a candidate: bucket_len(n_cand) == plen_l, so the
    # searches stay at full width and the read is the only cost
    ("full_width", "inner", False, "int", "none", "none", True, _BUILD_ROWS,
     ""),
    ("full_width_outer", "full", False, "int", "none", "none", True,
     _BUILD_ROWS, ""),
]


@pytest.mark.parametrize(
    "how,null_safe,keys,l_excl,r_excl,dense,build_rows,extra",
    [c[1:] for c in _PROBE_CASES], ids=[c[0] for c in _PROBE_CASES])
def test_probe_searches_only_its_candidates(
        rng, monkeypatch, how, null_safe, keys, l_excl, r_excl, dense,
        build_rows, extra):
    """With NDS_TPU_LAZY_SHRINK_ROWS under the probe side's bucket
    ``_probe_candidates`` reads its candidates' count first and runs its two
    searches at their bucket (at full width where that is no smaller): one
    more counted sync a probe, and ``counts`` / ``total`` / ``order``, the
    pair indices of ``join_indices`` in their order, the rows of
    ``join_tables`` and ``semi_join_mask``'s hash arm are what the full
    search gives with the threshold over the bucket. No read at all inside
    a stream-bounds region. ``op.join`` states ``probeRows``, the bucket
    searched, and the rollup sums it."""
    from nds_tpu.obs import trace as obs_trace
    left, right = _probe_tables(rng, keys, dense, max(build_rows, 1))
    names = {"int": ("lk",), "nullable": ("lk",),
             "int+str": ("lk", "ls")}[keys]
    l_on, r_on = list(names), ["r" + n[1:] for n in names]
    lkeys, rkeys = [left[n] for n in l_on], [right[n] for n in r_on]
    plen_l = left.plen
    lx, rx = _excl(rng, plen_l, l_excl), _excl(rng, right.plen, r_excl)
    if extra == "chunked":
        monkeypatch.setenv("NDS_TPU_PAIR_BUDGET", "64")
    kw = dict(n_left=left.nrows, n_right=build_rows, l_excl=lx, r_excl=rx)

    def counted(fn):
        E.resolve_counts()                # start from a drained thread
        obs_trace.drain_spans()
        before = _syncs()
        out = fn()
        used = _syncs() - before
        spans = [r for r in obs_trace.drain_spans()
                 if isinstance(r, obs_trace.SpanRecord)
                 and r.name == "op.join"]
        return out, used, spans

    def everything():
        probe, s_probe, _ = counted(lambda: E._probe_candidates(
            lkeys, rkeys, null_safe, **kw))
        idx, s_idx, sp_idx = counted(lambda: E.join_indices(
            lkeys, rkeys, how, null_safe, **kw))
        pairs = [None if x is None else
                 np.asarray(x) if hasattr(x, "shape") else E.count_int(x)
                 for x in idx]
        got = {"counts": np.asarray(probe[0]), "lo": np.asarray(probe[1]),
               "order": np.asarray(probe[2]), "total": probe[3],
               "pairs": pairs, "syncs": [s_probe, s_idx],
               "probeRows": [sp_idx[0].attrs["probeRows"]],
               "spans": list(sp_idx)}
        if not null_safe:
            right_t = type(right)(right.columns, build_rows,
                                  plen=right.plen)
            tab, s_tab, sp_tab = counted(lambda: E.resolve_table(
                E.join_tables(left, right_t, l_on, r_on, how,
                              l_excl=lx, r_excl=rx)))
            got["rows"] = tab.to_arrow()
            got["syncs"].append(s_tab)
            got["probeRows"].append(
                sum(r.attrs.get("probeRows", 0) for r in sp_tab))
            got["spans"] += sp_tab
        if extra == "semi":
            mask, s_semi, _ = counted(lambda: E.semi_join_mask(
                lkeys, rkeys, n_left=left.nrows, n_right=build_rows))
            got["semi"] = np.asarray(mask)
            got["syncs"].append(s_semi)
        return got

    monkeypatch.setenv("NDS_TPU_LAZY_SHRINK_ROWS", str(plen_l))
    full = everything()
    monkeypatch.setenv("NDS_TPU_LAZY_SHRINK_ROWS", str(plen_l // 2))
    narrow = everything()
    with E.stream_bounds():
        bound, s_bound, _ = counted(lambda: E._probe_candidates(
            lkeys, rkeys, null_safe, **kw))

    live = full["counts"] > 0
    assert np.array_equal(narrow["counts"], full["counts"])
    assert np.array_equal(narrow["lo"][live], full["lo"][live])
    assert np.array_equal(narrow["order"], full["order"])
    assert narrow["total"] == full["total"] == int(full["counts"].sum())
    assert narrow["counts"].dtype == full["counts"].dtype
    assert narrow["lo"].dtype == full["lo"].dtype
    for a, b in zip(narrow["pairs"], full["pairs"]):
        assert np.array_equal(a, b)       # pairs, extras: bit for bit
    if "rows" in full:
        assert narrow["rows"].equals(full["rows"])
        if extra == "chunked":
            assert full["total"] > E.pair_budget()
    if "semi" in full:
        assert np.array_equal(narrow["semi"], full["semi"])
    # one more counted read a probe past the threshold, none under it,
    # none inside a stream-bounds region
    assert [n - f for n, f in zip(narrow["syncs"], full["syncs"])] \
        == [1] * len(full["syncs"])
    assert full["syncs"][0] == 1 and s_bound == 0 and bound[3] is None
    assert np.array_equal(np.asarray(bound[0]), full["counts"])
    # probeRows: the bucket the two searches ran at
    assert set(full["probeRows"]) == {plen_l}
    n_cand = int((full["counts"] > 0).sum())
    if dense:
        assert set(narrow["probeRows"]) == {plen_l}
    else:
        (searched,) = set(narrow["probeRows"])
        assert E.bucket_len(n_cand) <= searched < plen_l
        assert not searched & (searched - 1)
    assert obs_export.rollup(narrow["spans"])["phases"]["op.join"][
        "probeRows"] == sum(narrow["probeRows"])


_PK_FACT_ROWS, _PK_DIM_ROWS = 5_000, 700    # buckets 8192 and 1024


def _pk_case(rng, case):
    """``(call, plen_f, dim bucket)`` of one PK probe: ``call()`` runs
    ``pk_gather_join`` / ``pk_gather_join_multi`` over device columns made
    here. Dimension keys are unique and SPARSE (one in about a thousand of
    their range, so no dense position map) unless the case says dense;
    one fact row in about seven has a match, every row in the two
    full-width cases."""
    from nds_tpu.engine.column import Column
    n, m = _PK_FACT_ROWS, _PK_DIM_ROWS
    n_dim = 0 if case == "empty_dim" else m
    wide = case.startswith("full_width")
    dk = rng.permutation(1_000_000)[:m] + 7
    fk = np.where(rng.random(n) < (1.0 if wide else 0.15),
                  rng.choice(dk, n), rng.integers(0, 1_000_000, n))
    if case == "dense":
        dk = np.arange(m) + 7
        fk = rng.integers(0, 2 * m, n)
    # a second column ((dk, dk2) stays unique): a fact row whose first key
    # is a dimension row's carries that row's second key half the time
    # (always in the full-width cases)
    dk2 = rng.integers(0, 50, m)
    pos = {int(k): i for i, k in enumerate(dk)}
    fk2 = np.array([dk2[pos[int(k)]] if int(k) in pos and
                    (wide or rng.random() < 0.5) else rng.integers(0, 50)
                    for k in fk])

    def table(name, cols):
        s = Session()
        s.create_temp_view(name, pa.table(cols))
        return s.catalog[name]

    def ints(v, nulls=0.0):
        return pa.array([None if z else int(x) for x, z in
                         zip(v, rng.random(len(v)) < nulls)], pa.int64())
    if case == "string_pair":
        fact = table("f", {"k": pa.array([f"s{x}" for x in fk])})
        dim = table("d", {"k": pa.array([f"s{x}" for x in dk])})
    else:
        fact = table("f", {"k": ints(fk, 0.2 * (case == "nullable_fact")),
                           "k2": ints(fk2)})
        dim = table("d", {"k": ints(dk), "k2": ints(dk2)})
    fcols, dcols = [fact["k"]], [dim["k"]]
    if case in ("composite", "full_width_composite"):
        fcols, dcols = [fact["k"], fact["k2"]], [dim["k"], dim["k2"]]
    if case in ("sentinel", "dead_twin"):
        # hand-made columns: a dead dimension row at a LOWER physical index
        # than the live row it shares a slot with. sentinel: the dead row is
        # null-keyed (it takes _PK_SENTINEL) and a live row really holds
        # 2^63-1; dead_twin: the dead row holds a live row's own key
        big = int(E._PK_SENTINEL)
        dkey = np.array(dk, dtype=np.int64)
        dkey[1] = big if case == "sentinel" else dkey[0]
        dvalid = np.ones(dim.plen, dtype=bool)
        dvalid[0] = False
        fkey = np.array(fk, dtype=np.int64)
        fkey[:3] = [dkey[1], dkey[2], dkey[1]]
        fcols = [Column("int", jnp.asarray(
            np.pad(fkey, (0, fact.plen - n))))]
        dcols = [Column("int", jnp.asarray(np.pad(dkey, (0, dim.plen - m))),
                        jnp.asarray(dvalid))]
    side, _, which = case.partition("_excl_")     # "f_excl_some" -> f, some
    f_excl = _excl(rng, fact.plen, which if side == "f" else "none")
    d_excl = _excl(rng, dim.plen, which if side == "d" else "none")

    def call():
        return E.pk_gather_join_multi(fcols, dcols, n, n_dim,
                                      f_excl=f_excl, d_excl=d_excl)
    return call, fact.plen, dim.plen


_PK_CASES = ["sparse_int", "composite", "string_pair", "nullable_fact",
             "f_excl_some", "f_excl_all", "d_excl_some", "d_excl_all",
             "empty_dim", "sentinel", "dead_twin", "full_width",
             "full_width_composite", "dense"]


@pytest.mark.parametrize("case", _PK_CASES)
def test_pk_probe_searches_only_its_candidates(rng, monkeypatch, case):
    """With NDS_TPU_LAZY_SHRINK_ROWS under the fact bucket the sorted arm of
    ``pk_gather_join`` / ``pk_gather_join_multi`` reads its candidates'
    count first and runs ``_pk_gather_impl`` at their bucket (at full width
    where that is no smaller): exactly one more counted sync a probe, and
    ``matched`` bit for bit, ``r_idx`` on every matched row, what the full
    search gives with the threshold over the bucket. No read under the
    threshold, on the dense arm, or inside a stream-bounds region.
    ``op.pk_gather`` states ``probeRows``, the bucket searched (nothing on
    the dense arm), and the rollup sums it."""
    from nds_tpu.obs import trace as obs_trace
    call, plen_f, plen_d = _pk_case(rng, case)

    def counted():
        E.resolve_counts()                # start from a drained thread
        obs_trace.drain_spans()
        before = _syncs()
        r_idx, matched = call()
        used = _syncs() - before
        phase = obs_export.rollup(obs_trace.drain_spans())["phases"][
            "op.pk_gather"]
        return np.asarray(r_idx), np.asarray(matched), used, phase

    monkeypatch.setenv("NDS_TPU_LAZY_SHRINK_ROWS", str(plen_f))
    call()                                # the dimension's cached host plans
    full_r, full_m, full_s, full_p = counted()
    monkeypatch.setenv("NDS_TPU_LAZY_SHRINK_ROWS", str(plen_f // 2))
    r, m, s, p = counted()
    with E.stream_bounds():
        bound_r, bound_m, bound_s, _ = counted()

    assert m.dtype == full_m.dtype == np.bool_ and r.dtype == full_r.dtype
    assert np.array_equal(m, full_m) and np.array_equal(bound_m, full_m)
    assert np.array_equal(r[m], full_r[m])
    assert np.array_equal(bound_r, full_r)
    assert r.shape == (plen_f,) and r.min() >= 0 and r.max() < plen_d
    hits = int(full_m.sum())
    assert hits == 0 if case in ("f_excl_all", "d_excl_all", "empty_dim") \
        else hits > 0
    assert full_s == 0 and bound_s == 0
    if case in ("sentinel", "dead_twin"):
        # rows 0 and 2 hold the shared slot's key: the LIVE row answers
        assert m[:3].tolist() == [True] * 3 and r[:3].tolist() == [1, 2, 1]
    if case == "dense":
        assert s == 0 and "probeRows" not in p and "probeRows" not in full_p
        return
    assert s == 1 and full_p["probeRows"] == plen_f
    searched = p["probeRows"]
    if case.startswith("full_width"):
        assert hits == _PK_FACT_ROWS and searched == plen_f
    else:
        assert E.bucket_len(hits) <= searched < plen_f
        assert not searched & (searched - 1)


_PK_SQL = {
    # Planner._binary_join's LEFT JOIN on the right side's composite PK:
    # misses null-extended, the IS NULL idiom reads them
    "left_join": """
        select ss_item_sk, count(*) c, sum(ss_q) q, sum(sr_amt) a,
               sum(case when sr_ticket_number is null then 1 else 0 end) z
        from store_sales left join store_returns
          on sr_ticket_number = ss_ticket_number and ss_item_sk = sr_item_sk
        group by ss_item_sk order by ss_item_sk""",
    # _join_parts' PK edge on the same composite key, under a deferred
    # filter mask of each side (f_excl / d_excl)
    "join_parts": """
        select ss_item_sk, count(*) c, sum(ss_q) q, sum(sr_amt) a
        from store_sales, store_returns
        where sr_ticket_number = ss_ticket_number and ss_item_sk = sr_item_sk
          and ss_q < 700 and sr_amt > 100
        group by ss_item_sk order by ss_item_sk""",
}


@pytest.mark.parametrize("edge", list(_PK_SQL))
def test_narrowed_pk_probe_through_sql_and_replay(rng, monkeypatch, edge):
    """store_sales against store_returns on the declared composite PK
    (item, ticket), through SQL, with the threshold under the fact's
    bucket: the PK probe reads its candidates' count and searches at their
    bucket, and the rows are the full-width search's; eager, recorded and
    replayed executions give equal rows, the eager and the recorded one
    make the same reads, the recording compiles (the narrowed shape
    follows the logged count, no ReplayMismatch)."""
    from nds_tpu.obs import trace as obs_trace
    n, m = 6_000, 500                     # buckets 8192 and 512
    item, ticket = rng.integers(1, 40, n), np.arange(n) // 3
    back = rng.permutation(n)[:m]         # the sales rows that came back
    s = Session()
    s.create_temp_view("store_sales", pa.table({
        "ss_item_sk": pa.array(item + 40 * (np.arange(n) % 3), pa.int64()),
        "ss_ticket_number": pa.array(ticket, pa.int64()),
        "ss_q": pa.array(rng.integers(1, 1000, n), pa.int64())}), base=True)
    s.create_temp_view("store_returns", pa.table({
        "sr_item_sk": pa.array((item + 40 * (np.arange(n) % 3))[back],
                               pa.int64()),
        "sr_ticket_number": pa.array(ticket[back], pa.int64()),
        "sr_amt": pa.array(rng.integers(1, 1000, m), pa.int64())}),
        base=True)
    fact = s.catalog["store_sales"]
    q = _PK_SQL[edge]
    monkeypatch.setenv("NDS_TPU_REPLAY", "force")
    monkeypatch.setenv("NDS_TPU_LAZY_SHRINK_ROWS", "1024")
    E.resolve_counts()
    obs_trace.drain_spans()
    runs = []
    for _ in range(4):                    # eager, record + compile, replay x2
        before = _syncs()
        rows = s.sql(q).collect()
        roll = obs_export.rollup(obs_trace.drain_spans(), top_sites=20)
        own = {x["site"]: x["syncs"] for x in roll["syncSites"]}
        runs.append((rows, _syncs() - before, own, roll["phases"]))
    (r0, n0, own0, ph0), (r1, _n1, own1, ph1), (r2, n2, _, ph2), \
        (r3, n3, _, _) = runs
    monkeypatch.setenv("NDS_TPU_REPLAY", "off")
    monkeypatch.delenv("NDS_TPU_LAZY_SHRINK_ROWS")
    want = s.sql(q).collect()             # full width: default threshold
    assert want and want == r0 == r1 == r2 == r3
    # the key ranges' plan (read by the first sight and by the recording)
    # and the candidates' count, both at the probe's site
    assert own0 == own1 and sum(own0.values()) == n0
    assert max(n for site, n in own0.items()
               if "_binary_join" in site or "_join_parts" in site) == 2, own0
    assert "replay.compile" in ph1 and s._replay_cache
    assert "replay.drive" in ph2 and n2 == n3 <= 1
    # the sorted search ran under the fact's bucket in both tiers
    assert ph0["op.pk_gather"]["probeRows"] == \
        ph1["op.pk_gather"]["probeRows"] < fact.plen


def test_batched_resolution_is_one_sync():
    """N pending DeviceCounts resolve in ONE counted transfer."""
    a = E.DeviceCount(jnp.asarray(3), 10)
    b = E.DeviceCount(jnp.asarray(7), 10)
    c = E.DeviceCount(jnp.asarray(9), 10)
    before = _syncs()
    assert a.to_int() == 3
    assert _syncs() - before == 1
    # b and c were drained by the same transfer: no further syncs
    assert b.to_int() == 7 and c.to_int() == 9
    assert _syncs() - before == 1


def test_device_count_refuses_implicit_host_use():
    d = E.DeviceCount(jnp.asarray(1), 4)
    with pytest.raises(TypeError):
        bool(d)
    with pytest.raises(TypeError):
        int(d)
    with pytest.raises(TypeError):
        _ = d == 1
    assert d.to_int() == 1


def test_scalar_subquery_aggregates_sync_free(star_session):
    """q9-class queries run 15 scalar subqueries, each a GLOBAL aggregate:
    the keyless-aggregate arm must never resolve the input count (empty-
    input semantics ride the aggregates' device-side validity), so the
    whole query costs only the final output resolution."""
    before = _syncs()
    rows = star_session.sql("""
        select case when (select count(*) from store_sales
                          where ss_ext_sales_price < 100) > 100
               then (select avg(ss_ext_sales_price) from store_sales
                     where ss_item_sk < 120)
               else (select avg(ss_ext_sales_price) from store_sales
                     where ss_item_sk >= 120) end x,
               (select sum(ss_ext_sales_price) from store_sales
                where ss_sold_date_sk < 100) y
        from date_dim where d_date_sk = 1
    """).collect()
    used = _syncs() - before
    assert rows
    assert used <= 2, \
        f"4 scalar subqueries used {used} host syncs (budget 2)"


def test_in_subquery_sync_free(star_session):
    """Single-key IN (subquery) must take the sort-probe path: existence
    is answered on device with no candidate-pair sizing sync."""
    before = _syncs()
    rows = star_session.sql("""
        select count(*) c from store_sales
        where ss_sold_date_sk in
              (select d_date_sk from date_dim where d_moy = 11)
          and ss_item_sk not in
              (select i_item_sk from item where i_brand_id = 1001)
    """).collect()
    used = _syncs() - before
    assert rows and rows[0][0] > 0
    assert used <= 1, f"IN-subquery query used {used} host syncs (budget 1)"


def test_lazy_scalar_subquery_semantics(star_session):
    """The lazy (sync-free) scalar-subquery arm must keep SQL semantics:
    empty subquery -> NULL, multi-row subquery -> runtime error (raised at
    the deferred batched resolution, still inside the same statement)."""
    from nds_tpu.sql.planner import ExecError
    rows = star_session.sql("""
        select d_year, (select i_brand_id from item where i_item_sk = -5) b
        from date_dim where d_date_sk = 1
    """).collect()
    assert rows and rows[0][1] is None
    with pytest.raises(ExecError, match="more than one row"):
        star_session.sql("""
            select d_year, (select i_brand_id from item
                            where i_item_sk < 10) b
            from date_dim where d_date_sk = 1
        """).collect()


def test_outer_join_sync_budget(rng):
    """A left join's pair + outer-extra counts must resolve in one batched
    transfer: probe sync + one batch = 2, vs 4 pre-batching."""
    n = 4_096
    s = Session()
    s.create_temp_view("l", pa.table({
        "k": pa.array(rng.integers(0, 500, n), pa.int64()),
        "v": pa.array(rng.integers(0, 10, n), pa.int64())}))
    s.create_temp_view("r", pa.table({
        "k2": pa.array(rng.integers(0, 700, n), pa.int64()),
        "w": pa.array(rng.integers(0, 10, n), pa.int64())}))
    lt, rt = s.catalog["l"], s.catalog["r"]
    before = _syncs()
    out = E.join_tables(lt, rt, ["k"], ["k2"], "left")
    used = _syncs() - before
    assert used <= 2, f"left join used {used} syncs (budget 2)"
    # row-level parity against numpy
    lk, lv = np.asarray(lt["k"].data), np.asarray(lt["v"].data)
    rk = np.asarray(rt["k2"].data)
    n_match = sum(int((rk == k).sum()) or 1 for k in lk)
    assert E.count_int(out.nrows) == n_match


def _chunked_star_session(rng, chunk_rows=2048):
    """star_session's tables with store_sales bound as a >HBM-style
    ChunkedTable (tiny chunk_rows forces a many-chunk pipeline), plus a
    store_returns dimension whose join key does NOT cover its declared
    primary key (sr_item_sk, sr_ticket_number) — the fan-out (k=1) join
    shape the partitioned-accumulation templates exercise. 3 rows per
    item keeps the per-chunk pair bucket inside the stream-fanout
    allowance (default 4), so the fan-out joins stay compiled.
    ss_ticket_number makes (ss_item_sk, ss_ticket_number) a usable
    composite join target for the multi-pass outer-join templates
    (store_returns' composite PK on one side, store_sales' on the
    other)."""
    from nds_tpu.engine.table import ChunkedTable
    n_fact, n_dim = 20_000, 365
    s = Session()
    s.create_temp_view("date_dim", pa.table({
        "d_date_sk": pa.array(np.arange(1, n_dim + 1), pa.int64()),
        "d_year": pa.array(1998 + np.arange(n_dim) // 120, pa.int64()),
        "d_moy": pa.array(1 + (np.arange(n_dim) // 30) % 12, pa.int64()),
    }), base=True)
    s.create_temp_view("item", pa.table({
        "i_item_sk": pa.array(np.arange(1, 201), pa.int64()),
        "i_brand_id": pa.array(rng.integers(1000, 1020, 200), pa.int64()),
    }), base=True)
    s.create_temp_view("store_returns", pa.table({
        "sr_item_sk": pa.array(np.repeat(np.arange(1, 201), 3), pa.int64()),
        "sr_ticket_number": pa.array(np.arange(600), pa.int64()),
        "sr_return_amt": pa.array(rng.integers(1, 100, 600), pa.int64()),
    }), base=True)
    s.create_temp_view("store_sales", ChunkedTable(pa.table({
        "ss_sold_date_sk": pa.array(
            rng.integers(1, n_dim + 40, n_fact), pa.int64()),
        "ss_item_sk": pa.array(rng.integers(1, 230, n_fact), pa.int64()),
        "ss_ticket_number": pa.array(
            np.arange(n_fact) % 1200, pa.int64()),
        "ss_ext_sales_price": pa.array(
            rng.integers(1, 10_000, n_fact), pa.int64()),
    }), chunk_rows=chunk_rows), base=True)
    return s


# (query, must_stream): must_stream pins the compiled pipeline; the
# subquery template documents the automatic eager fallback (its residual
# needs the catalog, which the chunk-invariant program must not close
# over) staying CORRECT — path is a performance property, never results.
_STREAM_AB_QUERIES = [
    # star join + group + order (the flagship >HBM shape)
    ("""select d_year, i_brand_id, sum(ss_ext_sales_price) s
        from store_sales, date_dim, item
        where ss_sold_date_sk = d_date_sk and ss_item_sk = i_item_sk
          and d_moy = 11
        group by d_year, i_brand_id order by d_year, s desc, i_brand_id""",
     True),
    # filter + projection on the streamed fact alone
    ("""select ss_item_sk, ss_ext_sales_price from store_sales
        where ss_ext_sales_price > 9900 and ss_item_sk < 40
        order by ss_item_sk, ss_ext_sales_price""", True),
    # grouped aggregate over the streamed fact alone
    ("""select ss_item_sk, count(*) c, sum(ss_ext_sales_price) s
        from store_sales where ss_ext_sales_price > 5000
        group by ss_item_sk order by ss_item_sk""", True),
    # IN-subquery residual (mechanism a): the inner query pre-plans into
    # a device-resident residual, so the statement streams COMPILED
    # (formerly the canonical eager fallback)
    ("""select count(*) c, sum(ss_ext_sales_price) s from store_sales
        where ss_sold_date_sk in
              (select d_date_sk from date_dim where d_moy = 11)""", True),
    # --- bare scans (no filter, no join: the survivor accumulator keeps
    # every chunk row). Formerly `accumulator-overflow` eager fallbacks;
    # the static memory proof (analysis/mem_audit.py) now sizes the
    # accumulator from the statement's row bound, so they stream compiled
    # and exec_audit reclassifies them in lockstep.
    ("""select ss_item_sk, ss_ext_sales_price from store_sales
        order by ss_item_sk, ss_ext_sales_price""", True),
    # bare keyless aggregate over the whole streamed fact
    ("""select count(*) c, sum(ss_ext_sales_price) s, min(ss_item_sk) m
        from store_sales""", True),
    # bare grouped aggregate, no WHERE
    ("""select ss_sold_date_sk, count(*) c from store_sales
        group by ss_sold_date_sk order by ss_sold_date_sk""", True),
    # --- partitioned fan-out joins (grace-style accumulation). The
    # ss->sr edge covers only part of store_returns' composite PK, so
    # k=1: the shape whose SF10 accumulator bound forced partitioning
    # (q17/q25/q29-class). The A/B harnesses run the whole set under
    # NDS_TPU_STREAM_PARTITIONS=2, which drives these through the
    # partitioned pipeline — bit-for-bit equal to eager, still one
    # materializing sync.
    ("""select ss_item_sk, count(*) c, sum(sr_return_amt) r
        from store_sales, store_returns
        where ss_item_sk = sr_item_sk and ss_ext_sales_price > 5000
        group by ss_item_sk order by ss_item_sk""", True),
    # fan-out + PK dimension in one graph (partition key rides the
    # fan-out batch; the item gather stays whole on every partition)
    ("""select i_brand_id, sum(sr_return_amt) r, count(*) c
        from store_sales, store_returns, item
        where ss_item_sk = sr_item_sk and ss_item_sk = i_item_sk
          and sr_return_amt > 50
        group by i_brand_id order by i_brand_id""", True),
    # --- multi-pass streaming (PR 8): the three eager-fallback
    # conversions, each run bit-for-bit vs eager and under the forced
    # partition count like everything above.
    # (b1) outer-gather: LEFT join with the chunked scan PRESERVED, ON
    # keys = store_returns' composite PK, plus the q78-class IS NULL
    # post filter — the join rides INTO the per-chunk program as a
    # sync-free gather
    ("""select ss_item_sk, count(*) c from store_sales
        left join store_returns on ss_item_sk = sr_item_sk
            and ss_ticket_number = sr_ticket_number
        where sr_ticket_number is null
        group by ss_item_sk order by ss_item_sk""", True),
    # (b2) outer-build: LEFT join with the chunked scan on the
    # NULL-INTRODUCING side (q5 shape) — matched pairs stream per chunk,
    # an on-device unmatched-key bitmap accumulates, and the outer
    # extras emit once at materialize time
    ("""select sr_item_sk, sr_return_amt, ss_ext_sales_price
        from store_returns
        left join store_sales on sr_item_sk = ss_item_sk
            and sr_ticket_number = ss_ticket_number
        order by sr_item_sk, sr_return_amt, ss_ext_sales_price""", True),
    # (a) streamed-subquery CHAIN: the scalar subquery's inner plan scans
    # the chunked table itself — TWO compiled pipelines, the inner's
    # residual threading into the outer as a device operand
    ("""select ss_item_sk, count(*) c from store_sales
        where ss_sold_date_sk in
              (select d_date_sk from date_dim where d_moy = 11)
          and ss_ext_sales_price >
              (select avg(ss_ext_sales_price) from store_sales)
        group by ss_item_sk order by ss_item_sk""", True),
    # (c) recorded chunk-scalar: ANSI NOT IN consults the residual's
    # null count — a recorded scalar replayed per chunk under a
    # device-side staleness guard
    ("""select count(*) c, sum(ss_ext_sales_price) s from store_sales
        where ss_item_sk not in
              (select i_item_sk from item where i_brand_id = 1001)""",
     True),
    # correlated EXISTS with a non-equality residual (q16/q94 class):
    # the stripped inner graph pre-plans as an exists_inner residual,
    # the pair probe runs per chunk under stream bounds
    ("""select count(*) c from store_sales ss1 where exists (
            select * from store_returns sr
            where ss1.ss_item_sk = sr.sr_item_sk
              and ss1.ss_ticket_number <> sr.sr_ticket_number)""", True),
]

# indexes of the templates above that must stream through the
# PARTITIONED compiled pipeline under a forced partition count (the A/B
# harnesses and test_streamed_compiled_matches_eager assert it): any
# graph joining store_returns ON the streamed scan directly. The EXISTS
# template's store_returns lives inside the subquery residual — its
# outer graph has no equi edge to hash on, so it stays unpartitioned.
_STREAM_AB_PARTITIONED = tuple(
    i for i, (q, _must) in enumerate(_STREAM_AB_QUERIES)
    if "store_returns" in q and "exists" not in q)

# the partition count every A/B partitioned sweep forces (the toy
# session's bounds all fit 16 GiB, so auto mode would never partition)
_STREAM_AB_PARTITION_COUNT = 2

# indexes of the templates the SHARDED A/B sweep drives over a forced
# 2-shard device mesh (NDS_TPU_STREAM_SHARDS, conftest's virtual
# 8-device CPU mesh): the flagship star join, the psum'd grouped
# aggregate, and one fan-out partitioned join — the template whose
# per-chunk hash-EXCHANGE pass crosses shards through the
# parallel/exchange.py all-to-alls. Shared with both differential
# harnesses (tools/exec_audit_diff.py, tools/mem_audit_diff.py), which
# verify the static collective budget and per-shard memory bound
# against the StreamEvent evidence these runs produce.
_STREAM_AB_SHARDED = (0, 2, 7)

# the shard count every sharded A/B sweep forces
_STREAM_AB_SHARD_COUNT = 2


@contextlib.contextmanager
def _forced_stream_shards(n=_STREAM_AB_SHARD_COUNT):
    """Pin NDS_TPU_STREAM_SHARDS — and STRICT stream failures — for one
    sharded A/B sweep: the ONE save/set/restore shared by
    test_sharded_compiled_matches_single_device_eager and both
    differential harnesses, so the forced mesh shape can never drift
    between the fixtures and their checkers."""
    import os
    old = {k: os.environ.get(k) for k in ("NDS_TPU_STREAM_SHARDS",
                                          "NDS_TPU_STREAM_STRICT")}
    os.environ["NDS_TPU_STREAM_SHARDS"] = str(n)
    os.environ["NDS_TPU_STREAM_STRICT"] = "1"
    try:
        yield n
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


@contextlib.contextmanager
def _forced_stream_partitions(n=_STREAM_AB_PARTITION_COUNT):
    """Pin NDS_TPU_STREAM_PARTITIONS — and STRICT stream failures — for
    one A/B sweep: the ONE save/set/restore shared by
    test_streamed_compiled_matches_eager and both differential harnesses
    (tools/exec_audit_diff.py, tools/mem_audit_diff.py), so the forced
    count can never drift between the fixtures and their checkers.
    NDS_TPU_STREAM_STRICT=1 re-raises any record/trace failure that is
    not a StreamSyncError/ReplayMismatch: a genuine engine bug must fail
    the sweep, never hide inside an eager fallback."""
    import os
    old = {k: os.environ.get(k) for k in ("NDS_TPU_STREAM_PARTITIONS",
                                          "NDS_TPU_STREAM_STRICT")}
    os.environ["NDS_TPU_STREAM_PARTITIONS"] = str(n)
    os.environ["NDS_TPU_STREAM_STRICT"] = "1"
    try:
        yield n
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def test_streamed_chunked_sync_budget(rng):
    """The acceptance bar for the compiled streaming executor
    (engine/stream.py): a query bound to a >HBM ChunkedTable — 10 chunks
    here — must run through the compiled chunk pipeline (not the eager
    per-chunk loop) within the <=6 host-sync budget that device-resident
    queries hold. Pre-pipeline the eager loop charged O(chunks) syncs
    (query37 at SF10: 128)."""
    from nds_tpu.listener import drain_stream_events
    s = _chunked_star_session(rng)
    drain_stream_events()
    before = _syncs()
    rows = s.sql("""
        select d_year, i_brand_id, sum(ss_ext_sales_price) s
        from store_sales, date_dim, item
        where ss_sold_date_sk = d_date_sk and ss_item_sk = i_item_sk
          and d_moy = 11
        group by d_year, i_brand_id
        order by d_year, s desc
    """).collect()
    used = _syncs() - before
    events = drain_stream_events()
    assert rows, "query unexpectedly empty"
    assert used <= 6, f"streamed query used {used} host syncs (budget 6)"
    assert [e.path for e in events] == ["compiled"], \
        f"expected the compiled chunk pipeline, got {events}"
    assert events[0].chunks == 10


def test_streamed_compiled_matches_eager():
    """A/B correctness: every template must produce bit-identical rows
    through the compiled chunk pipeline and through the eager chunk loop
    (NDS_TPU_STREAM_EXEC=eager escape hatch). The compiled arm runs under
    NDS_TPU_STREAM_PARTITIONS=2 so the fan-out templates
    (_STREAM_AB_PARTITIONED) take the grace-style PARTITIONED pipeline —
    per-partition survivor counts must sum to the scan total and the
    whole set must stay within the <=6-sync budget. Both arms rebuild
    their session from the same fresh seed (the shared rng fixture is
    session-scoped: its stream position depends on test order)."""
    import os
    from nds_tpu.listener import drain_stream_events
    compiled_rows, eager_rows = [], []
    with _forced_stream_partitions() as n_parts:
        s = _chunked_star_session(np.random.default_rng(42))
        drain_stream_events()
        for i, (q, must_stream) in enumerate(_STREAM_AB_QUERIES):
            before = _syncs()
            compiled_rows.append(s.sql(q).collect())
            used = _syncs() - before
            events = drain_stream_events()
            paths = [e.path for e in events]
            if must_stream:
                # a multi-pass statement may chain SEVERAL compiled
                # pipelines (the inner subquery's + the outer scan's);
                # every one of them must have compiled
                assert paths and all(p == "compiled" for p in paths), \
                    f"compiled arm fell back ({paths}) on: {q}"
                assert used <= 6, \
                    f"streamed template used {used} syncs (budget 6): {q}"
            if i in _STREAM_AB_PARTITIONED:
                (e,) = events
                assert e.partitions == n_parts, (q, e)
                assert len(e.part_rows) == n_parts
                assert sum(e.part_rows) == e.rows
    old = os.environ.get("NDS_TPU_STREAM_EXEC")
    os.environ["NDS_TPU_STREAM_EXEC"] = "eager"
    try:
        # identical data in both arms: rebuild from the fixture's seed
        s2 = _chunked_star_session(np.random.default_rng(42))
        for q, _ in _STREAM_AB_QUERIES:
            eager_rows.append(s2.sql(q).collect())
    finally:
        if old is None:
            del os.environ["NDS_TPU_STREAM_EXEC"]
        else:
            os.environ["NDS_TPU_STREAM_EXEC"] = old
    paths = {e.path for e in drain_stream_events()}
    assert paths == {"eager"}, f"escape hatch ignored: {paths}"
    for (q, _), a, b in zip(_STREAM_AB_QUERIES, compiled_rows, eager_rows):
        assert a == b, f"compiled/eager divergence on: {q}"
        assert a, f"A/B template unexpectedly empty: {q}"


def test_sharded_compiled_matches_single_device_eager():
    """A/B correctness of SHARDED streamed execution: the sharded subset
    (star join, psum'd grouped aggregate, fan-out partitioned join) must
    produce bit-identical rows through the shard_map'd compiled pipeline
    over a forced 2-shard mesh and through the single-device eager loop.
    Every event must report the forced shard count, per-shard survivor
    counts summing to the scan total, non-negative collective/ICI-byte
    evidence, and the <=6-host-sync budget must hold unchanged — the one
    cross-shard reduce rides the single materializing transfer. The
    partitioned template must drive the hash-EXCHANGE pass: its
    collective count covers at least one all-to-all per chunk."""
    import os

    import jax

    from nds_tpu.listener import drain_stream_events
    if len(jax.local_devices()) < _STREAM_AB_SHARD_COUNT:
        pytest.skip("needs a multi-device (virtual) mesh")
    compiled_rows = {}
    with _forced_stream_partitions():
        with _forced_stream_shards() as n_shards:
            s = _chunked_star_session(np.random.default_rng(42))
            drain_stream_events()
            for i in _STREAM_AB_SHARDED:
                q, _must = _STREAM_AB_QUERIES[i]
                before = _syncs()
                compiled_rows[i] = s.sql(q).collect()
                used = _syncs() - before
                events = drain_stream_events()
                assert events and all(e.path == "compiled"
                                      for e in events), \
                    f"sharded arm fell back on: {q}"
                assert used <= 6, \
                    f"sharded template used {used} syncs (budget 6): {q}"
                for e in events:
                    assert e.shards == n_shards, (q, e)
                    assert len(e.shard_rows) == n_shards
                    assert sum(e.shard_rows) == e.rows
                    assert e.collectives >= 0 and e.bytes_ici >= 0
                if i in _STREAM_AB_PARTITIONED:
                    (e,) = events
                    assert e.partitions == _STREAM_AB_PARTITION_COUNT
                    assert sum(e.part_rows) == e.rows
                    # the exchange pass's all-to-alls ran every chunk
                    assert e.collectives >= e.chunks, (q, e)
    old = os.environ.get("NDS_TPU_STREAM_EXEC")
    os.environ["NDS_TPU_STREAM_EXEC"] = "eager"
    try:
        s2 = _chunked_star_session(np.random.default_rng(42))
        for i in _STREAM_AB_SHARDED:
            q, _ = _STREAM_AB_QUERIES[i]
            eager = s2.sql(q).collect()
            assert eager == compiled_rows[i], \
                f"sharded-compiled/eager divergence on: {q}"
            assert eager, f"sharded A/B template unexpectedly empty: {q}"
    finally:
        if old is None:
            del os.environ["NDS_TPU_STREAM_EXEC"]
        else:
            os.environ["NDS_TPU_STREAM_EXEC"] = old
    drain_stream_events()


def test_hybrid_auto_delivers_sync_ceiling(star_session, monkeypatch):
    """Round-4 verdict #4's contract: under the default hybrid policy a
    query whose eager run exceeds the sync threshold converges to the
    replayed one-round-trip budget (<=1 sync steady state), while the
    threshold itself is environment-tunable."""
    monkeypatch.setenv("NDS_TPU_REPLAY", "auto")
    monkeypatch.setenv("NDS_TPU_REPLAY_SYNC_THR", "0")
    q = """
        select d_year, i_brand_id, sum(ss_ext_sales_price) s
        from store_sales, date_dim, item
        where ss_sold_date_sk = d_date_sk and ss_item_sk = i_item_sk
        group by d_year, i_brand_id order by s desc, i_brand_id limit 10
    """
    s = star_session
    r1 = s.sql(q).collect()          # sight 1: eager, counts syncs
    key = (q, s._data_version)
    assert s._replay_syncs[key] > 0
    s.sql(q).collect()               # sight 2: record + compile
    assert s._replay_cache, "auto should have recorded above threshold"
    s.sql(q).collect()               # sight 3: first replay (traces)
    before = _syncs()
    r4 = s.sql(q).collect()          # steady state
    assert _syncs() - before <= 1, "replayed steady state must be <=1 sync"
    assert r4 == r1
