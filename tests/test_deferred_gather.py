# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Late materialisation of PK-gathered dimension columns.

Past ``NDS_TPU_LAZY_SHRINK_ROWS`` (and outside a stream-bounds region) a
PK-gather join leaves the dimension's columns a deferred group of the joined
table: ``gather_table_rows`` gathers the source through the composed index
``take(group.index, idx)``, so the columns first exist at the bucket of the
compaction or join that consumes the table. These tests hold the deferred
form to the eager one (the same group, materialised at once: the parent's
operations) bit for bit, count what each gathers, and pin where it engages.

Rows past ``nrows`` are garbage pads in both forms and no operator reads
them, so tables are compared on their live rows; the composed gather in fact
equals the gather of a gather on the pads too (a pad index clips to the same
row of the group's index in both), which the ops-level cases check on the
whole physical arrays.
"""

import contextlib

import numpy as np
import pyarrow as pa
import pytest

import jax
import jax.numpy as jnp

from nds_tpu.engine import ops as E
from nds_tpu.engine.session import Session
from nds_tpu.obs import export as obs_export
from nds_tpu.obs import trace as obs_trace
from nds_tpu.sql.planner import Planner

N_FACT = 5_000                            # bucket 8192
FACT_BUCKET = E.bucket_len(N_FACT)
ENGAGED = str(FACT_BUCKET // 2)           # threshold under the fact's bucket


def _tables(seed=7, n_fact=N_FACT, all_match=False):
    """A small star with a snowflake arm: store_sales -> date_dim, item,
    and (LEFT, on its composite PK) store_returns -> reason. Some fact keys
    miss their dimension unless ``all_match``; nullable and string columns
    on both sides."""
    rng = np.random.default_rng(seed)
    n_dim, n_item, n_ret = 365, 200, 600
    over = 0 if all_match else 40
    item = rng.integers(1, n_item + 1 + (over and 30), n_fact)
    ticket = np.arange(n_fact)
    price = rng.integers(1, 10_000, n_fact)
    ret = rng.choice(n_fact, n_ret, replace=False)
    reason = rng.integers(1, 11, n_ret)
    return {
        "date_dim": pa.table({
            "d_date_sk": pa.array(np.arange(1, n_dim + 1), pa.int64()),
            "d_year": pa.array(1998 + np.arange(n_dim) // 120, pa.int64()),
            "d_moy": pa.array(1 + (np.arange(n_dim) // 30) % 12, pa.int64()),
        }),
        "item": pa.table({
            "i_item_sk": pa.array(np.arange(1, n_item + 1), pa.int64()),
            "i_brand_id": pa.array(rng.integers(1000, 1020, n_item),
                                   pa.int64()),
            "i_category": pa.array([None if k % 11 == 0 else f"cat{k % 7}"
                                    for k in range(n_item)]),
        }),
        "reason": pa.table({
            "r_reason_sk": pa.array(np.arange(1, 11), pa.int64()),
            "r_reason_desc": pa.array([f"reason {k}" for k in range(10)]),
        }),
        "store_returns": pa.table({
            "sr_item_sk": pa.array(item[ret], pa.int64()),
            "sr_ticket_number": pa.array(ticket[ret], pa.int64()),
            "sr_reason_sk": pa.array(
                [None if k % 9 == 0 else int(r)
                 for k, r in enumerate(reason)], pa.int64()),
            "sr_return_quantity": pa.array(rng.integers(1, 20, n_ret),
                                           pa.int64()),
        }),
        "store_sales": pa.table({
            "ss_sold_date_sk": pa.array(
                rng.integers(1, n_dim + 1 + over, n_fact), pa.int64()),
            "ss_item_sk": pa.array(item, pa.int64()),
            "ss_ticket_number": pa.array(ticket, pa.int64()),
            "ss_quantity": pa.array(rng.integers(1, 100, n_fact), pa.int64()),
            "ss_ext_sales_price": pa.array(
                [None if p % 13 == 0 else int(p) for p in price], pa.int64()),
        }),
    }


def _session(conf=None, **kw):
    s = Session(conf=conf) if conf else Session()
    for name, t in _tables(**kw).items():
        s.create_temp_view(name, t, base=True)
    return s


def _eager_pk_joined(fact, dim, r_idx, match=None):
    """The parent's PK-gather join: the dimension's columns gathered at
    once, at the fact's width."""
    return fact.with_deferred(dim, r_idx, match).materialize()


@contextlib.contextmanager
def _arm(monkeypatch, arm):
    """``deferred``: the threshold under the fact's bucket, the planner as
    it is. ``eager``: the same threshold (so compaction and probe read
    their counts first alike), every PK-gather join materialised at once.
    ``under``: the default threshold, nothing engages."""
    with monkeypatch.context() as m:
        if arm != "under":
            m.setenv("NDS_TPU_LAZY_SHRINK_ROWS", ENGAGED)
        if arm == "eager":
            m.setattr(Planner, "_pk_joined", staticmethod(_eager_pk_joined))
        yield


def _run(s, q):
    """Rows, counted host reads and the ``op.gather`` spans of one
    execution."""
    E.resolve_counts()
    obs_trace.drain_spans()
    before = E.sync_count()
    rows = s.sql(q).collect()
    used = E.sync_count() - before
    return rows, used, _gather_spans()


def _gather_spans():
    return [r for r in obs_trace.drain_spans()
            if isinstance(r, obs_trace.SpanRecord) and r.name == "op.gather"]


def _spy_gathers(monkeypatch):
    """Every fused column gather from here on, as ``(index width, arrays
    gathered)``: what ran at which bucket."""
    seen = []
    inner = E._gather_cols_impl

    def spy(idx, datas, valids):
        seen.append((int(idx.shape[0]),
                     len(datas) + sum(v is not None for v in valids)))
        return inner(idx, datas, valids)

    monkeypatch.setattr(E, "_gather_cols_impl", spy)
    return seen


# ---------------------------------------------------------------------------
# (a) ops level: the deferred group through every consumer of
# gather_table_rows, against the group materialised at once
# ---------------------------------------------------------------------------

_KEEP = {"none": 0, "one": 1, "bucket_edge": 1024, "past_edge": 1025,
         "all": N_FACT}


def _joined_pair(left_join):
    """``(deferred, eager, fact, dim)``: store_sales with store_returns'
    columns at a random row index (out-of-range entries included: gathers
    clip), as a deferred group and materialised at once; with a match mask
    (a LEFT join's misses) or without."""
    s = _session()
    fact, dim = s.catalog["store_sales"], s.catalog["store_returns"]
    rng = np.random.default_rng(3)
    r_idx = jnp.asarray(rng.integers(0, dim.plen + 5, fact.plen))
    match = jnp.asarray(rng.random(fact.plen) < 0.6) if left_join else None
    deferred = fact.with_deferred(dim, r_idx, match)
    eager = fact.with_deferred(dim, r_idx, match).materialize()
    assert deferred.split()[1] and not eager.split()[1]
    return deferred, eager, fact, dim


def _assert_tables_equal(got, want):
    assert got.column_names == want.column_names
    assert got.plen == want.plen
    assert E.count_int(got.nrows) == E.count_int(want.nrows)
    for n in want.column_names:
        g, w = got[n], want[n]
        assert (g.kind, g.valid is None) == (w.kind, w.valid is None), n
        assert g.dict_values is w.dict_values
        # the whole physical arrays, pads included
        np.testing.assert_array_equal(np.asarray(g.data), np.asarray(w.data),
                                      err_msg=n)
        if w.valid is not None:
            np.testing.assert_array_equal(np.asarray(g.valid),
                                          np.asarray(w.valid), err_msg=n)
    a = E.resolve_table(got).to_arrow()
    assert a.equals(E.resolve_table(want).to_arrow())


@pytest.mark.parametrize("keep", list(_KEEP))
@pytest.mark.parametrize("left_join", [False, True], ids=["inner", "left"])
@pytest.mark.parametrize("consumer", ["compact", "join_l_excl", "take"])
def test_deferred_group_equals_the_gather_of_a_gather(monkeypatch, consumer,
                                                      left_join, keep):
    """``take(take(src, r_idx), idx) == take(src, take(r_idx, idx))``: a
    table that carries a deferred group gives, through the count-first
    compaction, through ``join_tables`` (inner, the filter folded in as
    ``l_excl``) and through ``DeviceTable.take``, the table the eager form
    gives, element for element, the LEFT form's misses NULL; and it gathers
    every column ONCE, at the consumer's width."""
    monkeypatch.setenv("NDS_TPU_LAZY_SHRINK_ROWS", ENGAGED)
    deferred, eager, fact, dim = _joined_pair(left_join)
    k = _KEEP[keep]
    mask = jnp.arange(fact.plen) % 3 == 0 if keep == "all" else None
    live = np.zeros(fact.plen, dtype=bool)
    live[np.random.default_rng(5).permutation(N_FACT)[:k]] = True
    live = jnp.asarray(live)

    def consume(t):
        if consumer == "compact":
            return E.compact_table(t, live)
        if consumer == "take":
            return t.take(E.compact_indices(live, k), k)
        right = Session()
        right.create_temp_view("r", pa.table({
            "k": pa.array(np.arange(1, 120), pa.int64()),
            "w": pa.array(np.arange(1, 120) * 7, pa.int64())}))
        return E.join_tables(t, right.catalog["r"], ["ss_item_sk"], ["k"],
                             "inner", l_excl=~live if mask is None
                             else ~(live & mask))

    obs_trace.drain_spans()
    want = consume(eager)
    obs_trace.drain_spans()
    widths = _spy_gathers(monkeypatch)
    got = consume(deferred)
    gathers = _gather_spans()
    _assert_tables_equal(got, want)
    # the group's arrays (store_returns: 4 data + 1 validity) went through
    # the composed index, in the one gather of the fact side
    dim_arrays = sum(1 + (c.valid is not None) for c in dim.columns.values())
    fact_arrays = sum(1 + (c.valid is not None)
                      for c in fact.columns.values())
    side = [r for r in gathers if "deferredArrays" in r.attrs]
    assert [r.attrs["deferredArrays"] for r in side] == [dim_arrays]
    width = got.plen
    assert side[0].attrs["cells"] == width * (
        fact_arrays + dim_arrays + 1 + left_join)
    # every column was gathered once, at the consumer's width: nothing at
    # the fact's bucket on the way (unless every row survives), and the
    # table handed in still holds its group
    assert (width, fact_arrays) in widths and (width, dim_arrays) in widths
    assert {w for w, _ in widths} <= {width, E.bucket_len(119)}
    assert not deferred.split()[0].keys() & set(dim.column_names)


def test_deferred_column_read_alone_is_gathered_alone(monkeypatch):
    """``table[name]`` on a deferred column gathers THAT column (data and
    validity) at the table's width, once, and keeps it; names, membership,
    kinds, ``select``, ``rename`` and ``with_column`` answer from the
    source's metadata and gather nothing; ``.columns`` materialises the
    rest (today's dict)."""
    deferred, eager, fact, dim = _joined_pair(left_join=True)
    obs_trace.drain_spans()
    assert deferred.column_names == eager.column_names
    assert "sr_reason_sk" in deferred and "nope" not in deferred
    assert [deferred.kind(n) for n in deferred.column_names] == \
        [eager[n].kind for n in eager.column_names]
    assert deferred.plen == eager.plen == fact.plen
    view = deferred.select(["ss_item_sk", "sr_reason_sk"]).rename(
        {"sr_reason_sk": "x.reason"}).with_column("one", fact["ss_item_sk"])
    assert view.column_names == ["ss_item_sk", "x.reason", "one"]
    assert view.kind("x.reason") == "i64" and "sr_item_sk" in repr(deferred)
    assert not obs_trace.drain_spans(), "metadata must gather nothing"

    def gathers():
        return [r.attrs for r in _gather_spans()]

    got = view["x.reason"]
    assert gathers() == [{"cells": 2 * fact.plen}]
    assert view["x.reason"] is got and gathers() == []
    for g, w in ((got, eager["sr_reason_sk"]),
                 (deferred["sr_reason_sk"], eager["sr_reason_sk"])):
        np.testing.assert_array_equal(np.asarray(g.data), np.asarray(w.data))
        np.testing.assert_array_equal(np.asarray(g.valid),
                                      np.asarray(w.valid))
    assert gathers() == [{"cells": 2 * fact.plen}]      # deferred's own read
    cols = deferred.columns                            # the rest, at once
    assert list(cols) == eager.column_names
    assert gathers() == [{"cells": 3 * fact.plen}]      # 3 non-null columns
    _assert_tables_equal(deferred, eager)


# ---------------------------------------------------------------------------
# (a, b, c) statement level: deferred against eager against under-threshold
# ---------------------------------------------------------------------------

_STAR = """
    select d_year, i_brand_id, i_category, count(*) c,
           sum(ss_ext_sales_price) s
    from store_sales, date_dim, item
    where ss_sold_date_sk = d_date_sk and ss_item_sk = i_item_sk {where}
    group by d_year, i_brand_id, i_category
    order by d_year, i_brand_id, i_category
"""

_STATEMENTS = {
    # survivor shares: none, one row, a twelfth, every row that matches
    "star_none": _STAR.format(where="and d_moy = 13"),
    "star_one_row": _STAR.format(where="and ss_ticket_number = 17"),
    "star_month": _STAR.format(where="and d_moy = 11"),
    "star_unfiltered": _STAR.format(where=""),
    # the LEFT-on-PK arm (misses null-extended), then a snowflake edge that
    # keys on a deferred column of it: query93's shape
    "left_snowflake": """
        select ss_item_sk, r_reason_desc, count(*) c,
               sum(case when sr_return_quantity is not null
                        then ss_quantity - sr_return_quantity
                        else ss_quantity end) q
        from store_sales left outer join store_returns
             on (sr_item_sk = ss_item_sk
                 and sr_ticket_number = ss_ticket_number), reason
        where sr_reason_sk = r_reason_sk and r_reason_desc = 'reason 4'
        group by ss_item_sk, r_reason_desc order by ss_item_sk""",
    # the LEFT arm alone: every fact row survives, returns columns NULL
    # on the misses
    "left_all_rows": """
        select count(*) c, count(sr_return_quantity) r,
               sum(ss_quantity) q, sum(sr_return_quantity) rq
        from store_sales left outer join store_returns
             on (sr_item_sk = ss_item_sk
                 and sr_ticket_number = ss_ticket_number)""",
    # a hash join after the pk chain (its pair gathers compose), with an
    # in-join residual that reads a deferred dimension column
    "residual_in_join": """
        select a.ss_item_sk, count(*) c, sum(b.ss_quantity) q
        from store_sales a, store_sales b, date_dim, item
        where a.ss_sold_date_sk = d_date_sk and a.ss_item_sk = i_item_sk
          and a.ss_item_sk = b.ss_item_sk and d_moy = 3
          and a.ss_quantity < 20
          and b.ss_quantity < i_brand_id - 950 - d_moy
        group by a.ss_item_sk order by a.ss_item_sk""",
    # a residual over two dimensions' deferred columns and the fact's
    "residual_post_join": _STAR.format(
        where="and d_moy + i_brand_id > 1010 + ss_quantity / 10"),
}


@pytest.mark.parametrize("name", list(_STATEMENTS))
def test_statement_rows_reads_and_cells(monkeypatch, name):
    """Every statement shape gives the same rows deferred, eager (the
    parent's operations, same threshold) and under the threshold; deferred
    and eager make the SAME counted host reads (the deferral adds none);
    deferred gathers no array at the fact's bucket but the columns read on
    demand (a snowflake key, a residual's operands), and ``deferredArrays``
    counts the rest; its cells never pass the eager form's."""
    s = _session()
    q = _STATEMENTS[name]
    fact = s.catalog["store_sales"]
    with _arm(monkeypatch, "under"):
        want, _, _ = _run(s, q)
    with _arm(monkeypatch, "eager"):
        rows_e, reads_e, gathers_e = _run(s, q)
    with _arm(monkeypatch, "deferred"), monkeypatch.context() as m:
        widths = _spy_gathers(m)
        rows_d, reads_d, gathers_d = _run(s, q)
    assert want == rows_e == rows_d
    if name not in ("star_none",):
        assert want, "statement unexpectedly empty"
    assert reads_d == reads_e, (reads_d, reads_e)
    cells = {a: sum(r.attrs["cells"] for r in g)
             for a, g in (("eager", gathers_e), ("deferred", gathers_d))}
    deferred_arrays = sum(r.attrs.get("deferredArrays", 0)
                          for r in gathers_d)
    assert not any("deferredArrays" in r.attrs for r in gathers_e)
    # (the LEFT arm alone keeps every fact row and its aggregate reads one
    # column of the group: nothing is left to compose)
    assert (deferred_arrays > 0) == (name != "left_all_rows")
    assert cells["deferred"] <= cells["eager"]
    roll = obs_export.rollup(gathers_d)["phases"]["op.gather"]
    assert roll["cells"] == cells["deferred"]
    assert roll.get("deferredArrays", 0) == deferred_arrays
    # arrays gathered at the fact's bucket: only what an operator read
    # before the compaction (the eager form gathers every dimension column
    # there, then every column again at the survivors' bucket)
    on_demand = {"left_snowflake": 2,       # sr_reason_sk + validity: a key
                 "left_all_rows": 1,        # the aggregates' one operand
                 "star_unfiltered": None}   # survivors fill the fact's bucket
    if on_demand.get(name, 0) is not None:
        assert sum(a for w, a in widths if w == fact.plen) \
            == on_demand.get(name, 0), widths
        assert cells["deferred"] < cells["eager"]


def test_unfiltered_star_join_costs_one_index_a_dimension(monkeypatch):
    """The worst case: no filter and every key matches, so the compaction
    keeps every row and the dimensions' columns are gathered at the fact's
    full bucket anyway, through a composed index: equal rows, and at most
    one array a dimension beyond the cells the eager form gathers."""
    s = _session(all_match=True)
    fact = s.catalog["store_sales"]
    q = _STATEMENTS["star_unfiltered"]
    with _arm(monkeypatch, "eager"):
        rows_e, reads_e, gathers_e = _run(s, q)
    with _arm(monkeypatch, "deferred"):
        rows_d, reads_d, gathers_d = _run(s, q)
    assert rows_e and rows_e == rows_d and reads_e == reads_d
    assert sum(int(r[3]) for r in rows_d) == N_FACT
    ce = sum(r.attrs["cells"] for r in gathers_e)
    cd = sum(r.attrs["cells"] for r in gathers_d)
    assert cd <= ce + 2 * fact.plen
    # the survivors' gather ran at the fact's bucket: 2 composed indices
    wide = [r for r in gathers_d if "deferredArrays" in r.attrs]
    assert [r.attrs["deferredArrays"] for r in wide] == [2 + 4]
    assert wide[0].attrs["cells"] % fact.plen == 0


def test_deferred_statement_replays_with_sync_parity(monkeypatch):
    """Eager execution, recording and replay of a statement whose PK-gather
    joins defer: equal rows, the recording makes the reads the eager run
    made (outside the recorder's own), it compiles (the composed gathers
    trace like any other), and the replayed executions make the one result
    read."""
    monkeypatch.setenv("NDS_TPU_REPLAY", "force")
    monkeypatch.setenv("NDS_TPU_LAZY_SHRINK_ROWS", ENGAGED)
    s = _session()
    q = _STATEMENTS["left_snowflake"]
    E.resolve_counts()
    obs_trace.drain_spans()
    runs = []
    for _ in range(4):                    # eager, record + compile, replay x2
        before = E.sync_count()
        rows = s.sql(q).collect()
        roll = obs_export.rollup(obs_trace.drain_spans(), top_sites=20)
        own = {(x["site"], x["tag"]): x["syncs"] for x in roll["syncSites"]
               if x["tag"] != "dense_dim"}
        runs.append((rows, E.sync_count() - before, own, roll["phases"]))
    (r0, n0, own0, ph0), (r1, _n1, own1, ph1), (r2, n2, _, ph2), \
        (r3, n3, _, _) = runs
    assert r0 and r0 == r1 == r2 == r3
    assert own0 == own1 and sum(own0.values()) == n0
    assert "replay.compile" in ph1 and s._replay_cache
    assert "replay.drive" in ph2 and n2 == n3 <= 1
    assert ph0["op.gather"]["deferredArrays"] \
        == ph1["op.gather"]["deferredArrays"] > 0
    assert ph0["op.gather"]["cells"] == ph1["op.gather"]["cells"]


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs the virtual multi-device mesh")
def test_deferred_gather_under_a_mesh_session(monkeypatch):
    """The same ``jnp.take`` on row-sharded arrays: a mesh session whose
    fact is sharded over 8 devices gives the single-device rows with the
    deferral engaged, and engages it."""
    monkeypatch.setenv("NDS_TPU_BROADCAST_BYTES", "4096")   # shard the fact
    single = _session()
    meshed = _session(conf={"mesh_shape": 8})
    assert meshed.mesh is not None and meshed.mesh.devices.size == 8
    for name in ("star_month", "left_snowflake"):
        q = _STATEMENTS[name]
        want = single.sql(q).collect()
        with _arm(monkeypatch, "deferred"):
            rows, _, gathers = _run(meshed, q)
        assert want and rows == want
        assert any("deferredArrays" in r.attrs for r in gathers)


# ---------------------------------------------------------------------------
# (d) where it does NOT engage: every chunk program, and under the threshold
# ---------------------------------------------------------------------------


def _chunk_program_text(monkeypatch, threshold):
    """The jaxpr text of the compiled chunk program of one streamed star
    statement (store_sales bound in 2,048-row chunks), its rows, and the
    ``op.gather`` spans of the execution."""
    from nds_tpu.engine import stream
    from nds_tpu.engine.table import ChunkedTable
    from nds_tpu.listener import drain_stream_events
    s = Session()
    for name, t in _tables().items():
        if name == "store_sales":
            t = ChunkedTable(t, chunk_rows=2048)
        s.create_temp_view(name, t, base=True)
    if threshold is not None:
        monkeypatch.setenv("NDS_TPU_LAZY_SHRINK_ROWS", threshold)
    stream.reset_pipeline_cache()
    drain_stream_events()
    rows, _, gathers = _run(s, _STATEMENTS["star_month"])
    assert [e.path for e in drain_stream_events()] == ["compiled"]
    (pipe,) = stream._PIPELINE_CACHE.values()
    pipe = pipe if isinstance(pipe, stream.StreamPipeline) else pipe[-1]
    seen = []
    inner = pipe.jitted

    def spy(*args, **kw):
        if not seen:
            seen.append(jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                (args, kw)))
        return inner(*args, **kw)

    pipe.jitted = spy
    try:
        assert s.sql(_STATEMENTS["star_month"]).collect() == rows
    finally:
        pipe.jitted = inner
    args, kw = seen[0]
    return str(jax.make_jaxpr(inner.__wrapped__)(*args, **kw)), rows, gathers


def test_chunk_program_is_the_same_whatever_the_threshold(monkeypatch):
    """Inside ``E.stream_bounds()`` the planner gathers a PK-gather join's
    columns at once, in the eager order, whatever the threshold: the traced
    chunk program's jaxpr text is the same with the threshold under the
    chunk's bucket as with the default, it holds no composed index, and no
    gather of the statement states ``deferredArrays``."""
    with monkeypatch.context() as m:
        default, rows0, g0 = _chunk_program_text(m, None)
    with monkeypatch.context() as m:
        lowered, rows1, g1 = _chunk_program_text(m, "1024")
    assert rows0 and rows0 == rows1
    assert default == lowered
    assert "_gather_cols_impl" in default
    assert "_compose_impl" not in default
    assert not any("deferredArrays" in r.attrs for r in g0 + g1)


@pytest.mark.parametrize("region", ["under_threshold", "stream_bounds"])
def test_pk_joined_gathers_at_once_where_no_count_is_read(monkeypatch,
                                                          region):
    """``Planner._pk_joined`` under the threshold, and inside a stream-
    bounds region whatever the threshold, returns the materialised table
    through ONE ``op.gather`` of the dimension at the fact's width (the
    parent's operation); past the threshold outside it, nothing is
    gathered."""
    s = _session()
    fact, dim = s.catalog["store_sales"], s.catalog["date_dim"]
    r_idx = jnp.asarray(np.random.default_rng(1).integers(0, dim.plen,
                                                          fact.plen))
    if region == "stream_bounds":
        monkeypatch.setenv("NDS_TPU_LAZY_SHRINK_ROWS", ENGAGED)
    obs_trace.drain_spans()
    with (E.stream_bounds() if region == "stream_bounds"
          else contextlib.nullcontext()):
        out = Planner._pk_joined(fact, dim, r_idx)
    spans = [r.attrs for r in obs_trace.drain_spans()
             if isinstance(r, obs_trace.SpanRecord)]
    assert not out.split()[1]
    assert spans == [{"cells": fact.plen * len(dim.column_names)}]
    monkeypatch.setenv("NDS_TPU_LAZY_SHRINK_ROWS", ENGAGED)
    out = Planner._pk_joined(fact, dim, r_idx)
    assert len(out.split()[1]) == 1 and not obs_trace.drain_spans()
    assert E.count_first(fact.plen) and not E.count_first(FACT_BUCKET // 2)
    with E.stream_bounds():
        assert not E.count_first(fact.plen)
