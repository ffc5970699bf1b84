# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
""">HBM streaming scans (ChunkedTable): queries over a host-resident,
chunk-bound fact table must match the fully device-resident results —
SURVEY.md §5.7's structural requirement (tables larger than HBM stream
through the operators)."""

import functools

import numpy as np
import pyarrow as pa
import pytest

from nds_tpu.engine.session import Session
from nds_tpu.engine.table import ChunkedTable


def _tables(n=5000):
    rng = np.random.default_rng(21)
    sales = pa.table({
        "s_item": pa.array(rng.integers(1, 80, n), pa.int64()),
        "s_date": pa.array(rng.integers(1, 300, n), pa.int64()),
        "s_qty": pa.array(rng.integers(1, 50, n), pa.int64()),
        "s_price": pa.array([None if x % 13 == 0 else int(x)
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
    return sales, items, dates


CASES = [
    # star join + group + order (the flagship shape)
    """select d_year, i_cat, sum(s_qty) q, count(*) c, avg(s_price)
       from sales, items, dates
       where s_item = i_item and s_date = d_date and s_qty > 5
       group by d_year, i_cat order by d_year, i_cat""",
    # direct filter + projection on the streamed table only
    """select s_item, s_qty from sales where s_qty > 47 and s_tag = 'b'
       order by s_item, s_qty""",
    # distinct + semi-join against the streamed fact
    """select distinct s_tag from sales
       where s_item in (select i_item from items where i_cat = 'cat2')
       order by s_tag""",
    # window over the streamed join output
    """select i_cat, s_qty, rank() over (partition by i_cat
       order by s_qty desc, s_item) r
       from sales, items where s_item = i_item and s_qty > 45
       order by i_cat, r limit 40""",
]


@pytest.mark.parametrize("case", range(len(CASES)))
def test_streamed_scan_matches_resident(case):
    sales, items, dates = _tables()
    resident = Session()
    streamed = Session()
    for s, kind in ((resident, "resident"), (streamed, "streamed")):
        s.create_temp_view("items", items, base=True)
        s.create_temp_view("dates", dates, base=True)
    resident.create_temp_view("sales", sales, base=True)
    # 7 chunks of 800 rows exercise partial-trailing-chunk bucketing too
    streamed.create_temp_view("sales", ChunkedTable(sales, chunk_rows=800),
                              base=True)
    a = resident.sql(CASES[case]).collect()
    b = streamed.sql(CASES[case]).collect()
    assert a == b


def test_two_streamed_tables_one_axis():
    """With two streamed parts, one streams and the other materializes —
    results still exact."""
    sales, items, dates = _tables(2000)
    resident = Session()
    streamed = Session()
    resident.create_temp_view("sales", sales, base=True)
    resident.create_temp_view("items", items, base=True)
    streamed.create_temp_view("sales", ChunkedTable(sales, chunk_rows=512),
                              base=True)
    streamed.create_temp_view("items", ChunkedTable(items, chunk_rows=32),
                              base=True)
    sql = ("select i_cat, sum(s_qty) q from sales, items "
           "where s_item = i_item group by i_cat order by i_cat")
    assert resident.sql(sql).collect() == streamed.sql(sql).collect()


def test_padded_chunks_capacity_edges(monkeypatch):
    """ChunkedTable.padded_chunks at the capacity boundaries the compiled
    pipeline (and mem_audit's width model) depends on: exact power-of-two
    fits, one-past-the-boundary short chunks, non-power-of-two chunk_rows
    rounding, single-row and empty tables — every chunk at ONE uniform
    capacity with explicit validity and a single shared string
    dictionary."""
    from nds_tpu.analysis.mem_audit import type_width
    from nds_tpu.engine.ops import bucket_len

    def tbl(n):
        return pa.table({
            "v": pa.array(np.arange(n), pa.int64()),
            "s": pa.array([f"x{i % 3}" for i in range(n)], pa.string())})

    # exact power-of-two boundary: one full chunk, no pad rows
    ct = ChunkedTable(tbl(1024), chunk_rows=1024)
    chunks = list(ct.padded_chunks())
    assert len(chunks) == 1 and ct.num_chunks() == 1
    c = chunks[0]
    assert c.plen == ct.chunk_cap == bucket_len(1024) == 1024
    assert int(c.nrows) == 1024
    assert bool(np.asarray(c["v"].valid).all())
    # one row past the boundary: a second chunk with a single live row,
    # zero-padded to the SAME capacity (validity False past the prefix)
    ct = ChunkedTable(tbl(1025), chunk_rows=1024)
    chunks = list(ct.padded_chunks())
    assert [int(c.nrows) for c in chunks] == [1024, 1]
    assert chunks[-1].plen == 1024
    assert int(np.asarray(chunks[-1]["v"].valid).sum()) == 1
    # non-power-of-two chunk_rows round up to one shared capacity while
    # slicing exactly chunk_rows live rows per chunk (final chunk short)
    ct = ChunkedTable(tbl(2500), chunk_rows=800)
    chunks = list(ct.padded_chunks())
    assert [c.plen for c in chunks] == [1024] * 4
    assert [int(c.nrows) for c in chunks] == [800, 800, 800, 100]
    # every chunk shares ONE string dictionary object (identity: the
    # whole-table encoding — per-chunk dictionaries would make the same
    # code mean different strings chunk to chunk)
    assert len({id(c["s"].dict_values) for c in chunks}) == 1
    # pytree uniformity: same kinds, validity present on every column
    assert len({tuple((n, c[n].kind, c[n].valid is not None)
                      for n in c.column_names) for c in chunks}) == 1
    # width-model mirror, encoded execution ON (the default): the narrow
    # int64 column uploads as an int16 FOR code that round-trips exactly,
    # and string dictionary codes are unchanged
    enc_col = chunks[0]["v"]
    assert enc_col.enc is not None and enc_col.enc.mode == "for"
    assert enc_col.data.dtype == np.int16
    assert chunks[0]["s"].data.dtype.itemsize + 1 == type_width("string")
    np.testing.assert_array_equal(np.asarray(enc_col.plain().data)[:800],
                                  np.arange(800))
    # the NDS_TPU_ENCODED=0 escape hatch preserves today's path: plain
    # widths are exactly what mem_audit's base model prices
    monkeypatch.setenv("NDS_TPU_ENCODED", "0")
    plain = list(ChunkedTable(tbl(100), chunk_rows=1024).padded_chunks())
    assert plain[0]["v"].enc is None
    assert plain[0]["v"].data.dtype.itemsize + 1 == type_width("int64")
    monkeypatch.delenv("NDS_TPU_ENCODED")
    # single-row and empty tables still yield one full-capacity chunk
    for n in (1, 0):
        ct = ChunkedTable(tbl(n), chunk_rows=1024)
        chunks = list(ct.padded_chunks())
        assert len(chunks) == 1 and chunks[0].plen == 1024
        assert int(chunks[0].nrows) == n
        assert int(np.asarray(chunks[0]["v"].valid).sum()) == n


def test_encoded_chunk_codecs():
    """The encoded upload path (io/columnar.plan_column_codec through
    padded_chunks): FOR base round-trip for offset int64/date domains,
    the narrow-width overflow guard falling back to unencoded, sorted-
    dict encoding for wide-span low-cardinality ints, shared-encoding
    identity across chunks, and empty/single-row tables."""
    from nds_tpu.io.columnar import plan_column_codec

    n = 5000
    rng = np.random.default_rng(7)
    # span past int32 AND more distinct values than the dict codec
    # admits (DICT_MAX_VALUES): no narrow width fits — the guard case
    wide = np.arange(n) * (1 << 40) + rng.integers(0, 1 << 30, n)
    lowcard = rng.choice([5, 10 ** 12, -3, 99], n)   # wide span, 4 values
    offs = 5_000_000 + rng.integers(0, 900, n)   # FOR int16 after rebase
    t = pa.table({
        "offs": pa.array(offs, pa.int64()),
        "wide": pa.array(wide, pa.int64()),
        "lowcard": pa.array(lowcard, pa.int64()),
        "d": pa.array((np.arange(n) % 400 + 10000).astype("int32"),
                      pa.date32()),
        "dec": pa.array([None] * n, pa.int64()),
    })
    ct = ChunkedTable(t, chunk_rows=1024)
    chunks = list(ct.padded_chunks())
    c0 = chunks[0]
    # FOR round-trip: int16 offsets from the whole-table min
    assert c0["offs"].enc is not None and c0["offs"].enc.mode == "for"
    assert c0["offs"].data.dtype == np.int16
    np.testing.assert_array_equal(
        np.asarray(c0["offs"].plain().data)[:1024], offs[:1024])
    # narrow-width overflow guard: the wide-span column stays unencoded
    assert c0["wide"].enc is None
    assert c0["wide"].data.dtype == np.int64
    # sorted-dict codes for the wide-span low-cardinality column
    assert c0["lowcard"].enc is not None and c0["lowcard"].enc.mode == "dict"
    assert list(c0["lowcard"].enc.values) == [-3, 5, 99, 10 ** 12]
    np.testing.assert_array_equal(
        np.asarray(c0["lowcard"].plain().data)[:1024], lowcard[:1024])
    # dates narrow too (the span is the sales window, not the calendar)
    assert c0["d"].enc is not None and c0["d"].data.dtype == np.int16
    np.testing.assert_array_equal(
        np.asarray(c0["d"].plain().data)[:1024],
        (np.arange(1024) % 400 + 10000))
    # an all-null column encodes as trivial FOR (the static width model
    # prices it narrow, so the runtime must never upload it wide)
    assert c0["dec"].enc is not None and c0["dec"].data.dtype == np.int16
    assert not np.asarray(c0["dec"].valid).any()
    # shared-encoding identity across chunks: one Encoding object (a
    # cache-key member, like the string dictionaries)
    assert len({id(c["offs"].enc) for c in chunks}) == 1
    assert len({id(c["lowcard"].enc.values) for c in chunks}) == 1
    # empty and single-row tables still chunk cleanly
    for m in (1, 0):
        small = ChunkedTable(t.slice(0, m), chunk_rows=1024)
        (chunk,) = list(small.padded_chunks())
        assert int(chunk.nrows) == m and chunk.plen == 1024
    # plan_column_codec rejects non-int kinds outright
    assert plan_column_codec(pa.array(["x", "y"]), "string") is None


def test_encoded_codec_boundaries():
    """Satellite of analysis/num_audit: the EXACT codec edges the static
    width rules promise, end-to-end through padded_chunks — span
    2^15 - 1 fits int16 / span 2^15 widens to int32, exactly 4096
    distinct values dict-encode with code 4095 live / 4097 refuse,
    an all-negative span rebases bit-exactly, and a full-range
    decimal(7,2) survives the scaled FOR round-trip to the cent."""
    from decimal import Decimal

    from nds_tpu.io.columnar import DICT_MAX_VALUES, plan_column_codec

    span16 = (1 << 15) - 1
    n = 3000
    base = 1_000_000_000
    edge16 = base + (np.arange(n) * 131) % (span16 + 1)
    edge16[0], edge16[1] = base, base + span16       # both endpoints live
    over16 = edge16.copy()
    over16[2] = base + span16 + 1                    # span 2^15: one too far
    neg = -(40_000) + (np.arange(n)[::-1] * 37) % (span16 + 1)
    cents = (np.arange(n) * 6673) % (2 * 10 ** 7) - (10 ** 7 - 1)
    cents[0], cents[1] = 10 ** 7 - 1, -(10 ** 7 - 1)
    t = pa.table({
        "edge16": pa.array(edge16, pa.int64()),
        "over16": pa.array(over16, pa.int64()),
        "neg": pa.array(neg, pa.int64()),
        "dec": pa.array([Decimal(int(c)) / 100 for c in cents],
                        pa.decimal128(7, 2)),
    })
    ct = ChunkedTable(t, chunk_rows=1024, canonical_types={
        "edge16": "int64", "over16": "int64", "neg": "int64",
        "dec": "decimal(7,2)"})
    c0 = list(ct.padded_chunks())[0]
    # span exactly 2^15 - 1: int16 FOR, both endpoints round-trip
    assert c0["edge16"].enc.mode == "for"
    assert c0["edge16"].data.dtype == np.int16
    np.testing.assert_array_equal(
        np.asarray(c0["edge16"].plain().data)[:1024], edge16[:1024])
    # span exactly 2^15: int16 refused, int32 takes it bit-exactly
    assert c0["over16"].data.dtype == np.int32
    np.testing.assert_array_equal(
        np.asarray(c0["over16"].plain().data)[:1024], over16[:1024])
    # all-negative span rebases against a negative base exactly
    assert c0["neg"].enc is not None
    np.testing.assert_array_equal(
        np.asarray(c0["neg"].plain().data)[:1024], neg[:1024])
    # full-range decimal(7,2): int32 FOR over the scaled ints, exact to
    # the cent at both extremes
    assert c0["dec"].enc.mode == "for"
    assert c0["dec"].data.dtype == np.int32
    np.testing.assert_array_equal(
        np.asarray(c0["dec"].plain().data)[:1024], cents[:1024])
    # dict code space: exactly DICT_MAX_VALUES distinct values encode
    # (top code 4095 is a live value-table index); one more refuses
    vals = np.arange(DICT_MAX_VALUES) * (1 << 40)
    got = plan_column_codec(pa.array(vals, pa.int64()), "int64")
    assert got is not None and got[2].mode == "dict"
    assert got[0].dtype == np.int16
    assert int(got[0].max()) == DICT_MAX_VALUES - 1
    np.testing.assert_array_equal(
        np.asarray(got[2].values)[np.asarray(got[0])], vals)
    more = np.append(vals, (DICT_MAX_VALUES + 9) * (1 << 40))
    assert plan_column_codec(pa.array(more, pa.int64()), "int64") is None


def test_encoded_compiled_matches_unencoded_and_shrinks_h2d():
    """Acceptance: A/B templates run the ENCODED compiled path bit-for-
    bit equal to the decoded run under NDS_TPU_STREAM_STRICT=1, and
    streamedScans reports bytes_h2d strictly below the unencoded upload
    bytes on every encoded scan — the compression win is measured, not
    asserted."""
    import os

    from nds_tpu.listener import drain_stream_events
    from tests.test_synccount import (_STREAM_AB_QUERIES,
                                      _chunked_star_session,
                                      _forced_stream_partitions)

    ab = [_STREAM_AB_QUERIES[0][0], _STREAM_AB_QUERIES[7][0]]
    runs = {}
    for flag in ("1", "0"):
        old = os.environ.get("NDS_TPU_ENCODED")
        os.environ["NDS_TPU_ENCODED"] = flag
        try:
            with _forced_stream_partitions():
                s = _chunked_star_session(np.random.default_rng(42))
                drain_stream_events()
                rows, bytes_h2d = [], []
                for q in ab:
                    rows.append(s.sql(q).collect())
                    events = drain_stream_events()
                    assert [e.path for e in events] == ["compiled"], \
                        (flag, q, events)
                    bytes_h2d.append(events[0].bytes_h2d)
                runs[flag] = (rows, bytes_h2d)
        finally:
            if old is None:
                os.environ.pop("NDS_TPU_ENCODED", None)
            else:
                os.environ["NDS_TPU_ENCODED"] = old
    assert runs["1"][0] == runs["0"][0], "encoded/decoded divergence"
    for enc_b, plain_b in zip(runs["1"][1], runs["0"][1]):
        assert 0 < enc_b < plain_b, \
            f"encoded upload {enc_b} not below unencoded {plain_b}"


def test_acc_ceiling_env_read_at_build_time(monkeypatch, tmp_path):
    """Regression for the import-time env freeze: NDS_TPU_STREAM_ACC_ROWS
    set AFTER module import must clamp the accumulator at pipeline build
    (forcing the overflow rerun), the rerun must emit the
    stream.overflow-rerun span (priced by tools/trace_report.py), and
    removing the ceiling must restore the proof-sized compiled path."""
    import importlib.util
    import os as _os
    import sys as _sys

    from nds_tpu.engine import ops as E
    from nds_tpu.listener import drain_stream_events
    from nds_tpu.obs import export as obs_export
    from nds_tpu.obs import trace as obs_trace

    monkeypatch.setenv("NDS_TPU_STREAM_FANOUT", "16")
    assert E.stream_fanout() == 16       # read at use time, not import
    monkeypatch.delenv("NDS_TPU_STREAM_FANOUT")

    sales, _items, _dates = _tables()    # 5000 rows
    sql = "select s_item, s_qty from sales order by s_item, s_qty"
    resident = Session()
    resident.create_temp_view("sales", sales, base=True)
    expect = resident.sql(sql).collect()

    # ceiling far below the 5000 survivors: the proof is overridden by
    # the explicit hard ceiling, the accumulator overflows, and the
    # query reruns eagerly — bit-identical results either way
    monkeypatch.setenv("NDS_TPU_STREAM_ACC_ROWS", "1024")
    s = Session()
    s.create_temp_view("sales", ChunkedTable(sales, chunk_rows=800),
                       base=True)
    drain_stream_events()
    obs_trace.drain_spans()
    assert s.sql(sql).collect() == expect
    events = drain_stream_events()
    assert [e.path for e in events] == ["eager"]
    assert events[0].reason == "bound-bucket overflow"
    records = obs_trace.drain_spans()
    names = [r.name for r in records
             if isinstance(r, obs_trace.SpanRecord)]
    assert "stream.overflow-rerun" in names
    assert "stream.eager" not in names
    # trace_report prices the rerun separately from ordinary fallbacks
    tdir = tmp_path / "traces"
    tdir.mkdir()
    obs_export.write_chrome_trace(str(tdir / "q.trace.json"), records,
                                  query="q")
    repo = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "trace_report", _os.path.join(repo, "tools", "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = "\n".join(mod.report(str(tdir)))
    assert "bound-bucket overflow" in out and "overflow rerun:" in out

    # ceiling removed: the proof sizes the accumulator and the SAME
    # statement streams compiled, keeping every survivor
    monkeypatch.delenv("NDS_TPU_STREAM_ACC_ROWS")
    s2 = Session()
    s2.create_temp_view("sales", ChunkedTable(sales, chunk_rows=800),
                        base=True)
    assert s2.sql(sql).collect() == expect
    events = drain_stream_events()
    assert [e.path for e in events] == ["compiled"]
    assert events[0].rows == 5000        # survivor count on the event


def _return_tables(n=5000, n_keys=79):
    """sales (streamed) + a returns side whose join key covers no PK —
    the fan-out (k=1) shape partitioned accumulation exists for.
    ``n_keys`` caps the sales key cardinality: 1 = every row carries one
    key (the whole table hashes to ONE partition: the skew case); a few
    keys under a large partition count guarantees EMPTY partitions."""
    rng = np.random.default_rng(7)
    keys = rng.integers(1, n_keys + 1, n)
    sales = pa.table({
        "s_item": pa.array(keys, pa.int64()),
        "s_qty": pa.array(rng.integers(1, 50, n), pa.int64()),
    })
    returns = pa.table({
        "r_item": pa.array(np.repeat(np.arange(1, 81), 2), pa.int64()),
        "r_amt": pa.array(rng.integers(1, 100, 160), pa.int64()),
    })
    return sales, returns


_PART_SQL = ("select s_item, count(*) c, sum(r_amt) a from sales, returns "
             "where s_item = r_item group by s_item order by s_item")


def _run_partition_case(monkeypatch, sales, returns, partitions,
                        chunk_rows=800, acc_rows=None):
    from nds_tpu.listener import drain_stream_events
    resident = Session()
    resident.create_temp_view("sales", sales, base=True)
    resident.create_temp_view("returns", returns, base=True)
    expect = resident.sql(_PART_SQL).collect()
    if partitions is not None:
        monkeypatch.setenv("NDS_TPU_STREAM_PARTITIONS", str(partitions))
    if acc_rows is not None:
        monkeypatch.setenv("NDS_TPU_STREAM_ACC_ROWS", str(acc_rows))
    s = Session()
    s.create_temp_view("sales", ChunkedTable(sales, chunk_rows=chunk_rows),
                       base=True)
    s.create_temp_view("returns", returns, base=True)
    drain_stream_events()
    got = s.sql(_PART_SQL).collect()
    events = drain_stream_events()
    assert got == expect, "partitioned result diverged from resident"
    return events


def test_partitioned_pipeline_empty_partitions(monkeypatch, tmp_path):
    """Partition count far above the key cardinality (4 keys over 32
    partitions) GUARANTEES empty partitions: the pipeline must stay
    compiled, report a zero survivor count for each empty partition, and
    the per-partition survivors must sum to the scan total — results
    exact either way. The partition passes must emit zero-sync
    stream.partition spans that tools/trace_report.py prices as their
    own phase column."""
    import importlib.util
    import os as _os

    from nds_tpu.obs import export as obs_export
    from nds_tpu.obs import trace as obs_trace

    obs_trace.drain_spans()
    sales, returns = _return_tables(n=2000, n_keys=4)
    events = _run_partition_case(monkeypatch, sales, returns, 32)
    assert [e.path for e in events] == ["compiled"]
    (e,) = events
    assert e.partitions == 32 and len(e.part_rows) == 32
    assert sum(e.part_rows) == e.rows
    assert 0 in e.part_rows, "4 keys over 32 partitions must leave gaps"
    records = obs_trace.drain_spans()
    part_spans = [r for r in records
                  if isinstance(r, obs_trace.SpanRecord)
                  and r.name == "stream.partition"]
    assert len(part_spans) == 3          # one partition pass per chunk
    assert all(s.syncs == 0 for s in part_spans), \
        "the radix partition pass must never charge a host sync"
    tdir = tmp_path / "traces"
    tdir.mkdir()
    obs_export.write_chrome_trace(str(tdir / "q.trace.json"), records,
                                  query="q")
    repo = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "trace_report", _os.path.join(repo, "tools", "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = "\n".join(mod.report(str(tdir)))
    assert "stream.partition" in out, \
        "trace_report must price partition passes as their own column"


def test_partitioned_pipeline_hot_partition_overflow_rerun(monkeypatch):
    """Skewed keys: every row carries ONE join key, so the hash routes
    the whole table into a single partition. With a per-partition
    accumulator ceiling below that partition's survivors, the enforced
    per-partition overflow flag must fire and the query must rerun
    eagerly — bit-identical results, path='eager', the overflow reason
    on the event (the skew-conditional proof is a perf property, never
    a correctness one)."""
    sales, returns = _return_tables(n_keys=1)
    events = _run_partition_case(monkeypatch, sales, returns, 4,
                                 acc_rows=2048)
    assert [e.path for e in events] == ["eager"]
    assert events[0].reason == "bound-bucket overflow"


def test_partitioned_pipeline_survives_adaptive_resolve(monkeypatch):
    """Regression: at production chunk sizes (chunk_cap past the
    NDS_TPU_LAZY_SHRINK_ROWS threshold) the partition mask's lazy
    compact must NOT take compact_table's adaptive host resolve inside
    the traced program — that would raise on the tracer and silently
    divert every partitioned pipeline to the eager loop. Simulated by
    lowering the threshold below the toy chunk capacity."""
    from nds_tpu.engine import ops as E
    monkeypatch.setenv("NDS_TPU_LAZY_SHRINK_ROWS", "256")
    sales, returns = _return_tables()
    events = _run_partition_case(monkeypatch, sales, returns, 4,
                                 chunk_rows=800)    # chunk_cap 1024 > 256
    assert [e.path for e in events] == ["compiled"], \
        "partition compact took the adaptive resolve inside the trace"
    assert events[0].partitions == 4


def test_partition_count_one_is_unpartitioned(monkeypatch):
    """NDS_TPU_STREAM_PARTITIONS=1 must run bit-for-bit identical to
    today's unpartitioned pipeline: same compiled path, partition count
    1 on the event, no per-partition evidence, same rows."""
    sales, returns = _return_tables()
    base = _run_partition_case(monkeypatch, sales, returns, None)
    monkeypatch.delenv("NDS_TPU_STREAM_PARTITIONS", raising=False)
    forced1 = _run_partition_case(monkeypatch, sales, returns, 1)
    for events in (base, forced1):
        assert [e.path for e in events] == ["compiled"]
        (e,) = events
        assert e.partitions == 1 and e.part_rows == ()
    assert base[0].rows == forced1[0].rows


def test_session_stream_threshold(monkeypatch, tmp_path):
    """read_columnar_view streams tables past the byte threshold."""
    import pyarrow.parquet as pq
    sales, _, _ = _tables(3000)
    p = tmp_path / "sales.parquet"
    pq.write_table(sales, p)
    monkeypatch.setenv("NDS_TPU_STREAM_BYTES", "1024")
    s = Session()
    s.read_columnar_view("sales", str(p))
    assert isinstance(s.catalog["sales"], ChunkedTable)
    r = s.sql("select count(*), sum(s_qty) from sales").collect()
    assert r[0][0] == 3000


# ---------------------------------------------------------------------------
# multi-pass streaming (subquery residuals, deferred outer joins, strict
# failure mode)
# ---------------------------------------------------------------------------


def test_stream_strict_reraises_engine_bugs(monkeypatch):
    """NDS_TPU_STREAM_STRICT=1: a record/trace failure that is NOT one of
    the two legitimate routing exceptions (StreamSyncError /
    ReplayMismatch) must RE-RAISE instead of hiding inside an eager
    fallback; without strict mode the fallback reason must carry the
    exception class so the event is auditable."""
    from nds_tpu.engine import stream as S
    from nds_tpu.listener import drain_stream_events

    sales, items, dates = _tables(1500)
    sql = ("select s_item, sum(s_qty) q from sales, items "
           "where s_item = i_item group by s_item order by s_item")

    def boom(*a, **k):
        raise ValueError("injected engine bug")

    def run():
        s = Session()
        s.create_temp_view("items", items, base=True)
        s.create_temp_view("sales", ChunkedTable(sales, chunk_rows=512),
                           base=True)
        drain_stream_events()
        return s, s.sql(sql)

    # the pipeline's run phase trips the injected bug (record succeeds;
    # the StreamPipeline.run entry raises like a trace-time ValueError)
    monkeypatch.setattr(S.StreamPipeline, "run", boom)
    monkeypatch.delenv("NDS_TPU_STREAM_STRICT", raising=False)
    s, res = run()
    rows = res.collect()
    events = drain_stream_events()
    assert rows, "fallback must still produce the result"
    assert [e.path for e in events] == ["eager"]
    assert "ValueError" in events[0].reason, events[0].reason
    monkeypatch.setenv("NDS_TPU_STREAM_STRICT", "1")
    with pytest.raises(ValueError, match="injected engine bug"):
        run()[1].collect()


def test_outer_build_extras_all_unmatched(monkeypatch):
    """Outer-build edge: NO build row matches any chunk — the entire
    output is extras, emitted at materialize time from the unmatched-key
    accumulator, null-extended on the chunk side."""
    rng = np.random.default_rng(5)
    n = 3000
    sales = pa.table({
        "s_item": pa.array(rng.integers(1, 80, n), pa.int64()),
        "s_tick": pa.array(np.arange(n), pa.int64()),
        "s_qty": pa.array(rng.integers(1, 50, n), pa.int64()),
    })
    # returns keys entirely OUTSIDE the sales key range: zero matches
    returns = pa.table({
        "r_item": pa.array(np.arange(900, 950), pa.int64()),
        "r_tick": pa.array(np.arange(50), pa.int64()),
        "r_amt": pa.array(rng.integers(1, 9, 50), pa.int64()),
    })
    from nds_tpu.listener import drain_stream_events
    s = Session()
    s.create_temp_view("returns", returns, base=True)
    s.create_temp_view("sales", ChunkedTable(sales, chunk_rows=512),
                       base=True)
    drain_stream_events()
    sql = ("select r_item, r_amt, s_qty from returns left join sales "
           "on r_item = s_item and r_tick = s_tick "
           "order by r_item")
    rows = s.sql(sql).collect()
    events = drain_stream_events()
    assert [e.path for e in events] == ["compiled"]
    assert events[0].rows == 0           # the accumulator kept no pairs
    assert len(rows) == 50               # ...but every build row came out
    assert all(r[2] is None for r in rows), "extras must null-extend"


def test_subquery_residual_reused_across_eager_chunks():
    """The residual registry also serves the EAGER loop: an escape-hatch
    run must plan each distinct subquery once per statement, not once per
    chunk (results identical either way)."""
    import os

    sales, items, dates = _tables(2000)
    sql = ("select count(*) c from sales where s_item in "
           "(select i_item from items where i_cat = 'cat2')")

    def run():
        s = Session()
        s.create_temp_view("items", items, base=True)
        s.create_temp_view("sales", ChunkedTable(sales, chunk_rows=256),
                           base=True)
        return s.sql(sql).collect()

    compiled = run()
    old = os.environ.get("NDS_TPU_STREAM_EXEC")
    os.environ["NDS_TPU_STREAM_EXEC"] = "eager"
    try:
        eager = run()
    finally:
        if old is None:
            del os.environ["NDS_TPU_STREAM_EXEC"]
        else:
            os.environ["NDS_TPU_STREAM_EXEC"] = old
    assert compiled == eager and compiled[0][0] > 0


def test_outer_build_not_deferred_under_parent_join():
    """Review regression: SQL left-assoc makes ``returns ⟕ sales JOIN
    dates`` drop every unmatched returns row (its sales-side date is
    NULL, so the parent inner join filters it). The outer-build deferral
    must NOT fire under a parent join — materialize-time extras cannot
    flow through post-join structure — and the chunked plan must match
    the resident one bit for bit."""
    rng = np.random.default_rng(9)
    n = 2000
    sales = pa.table({
        "s_item": pa.array(rng.integers(1, 60, n), pa.int64()),
        "s_tick": pa.array(np.arange(n), pa.int64()),
        "s_date": pa.array(rng.integers(1, 300, n), pa.int64()),
        "s_qty": pa.array(rng.integers(1, 50, n), pa.int64()),
    })
    returns = pa.table({
        # half the keys land outside the sales tick range: unmatched
        "r_item": pa.array(rng.integers(1, 60, 80), pa.int64()),
        "r_tick": pa.array(np.arange(0, 8000, 100), pa.int64()),
        "r_amt": pa.array(rng.integers(1, 9, 80), pa.int64()),
    })
    dates = pa.table({
        "d_date": pa.array(np.arange(1, 301), pa.int64()),
        "d_year": pa.array(1998 + np.arange(300) // 100, pa.int64()),
    })
    sql = ("select r_item, r_amt, s_qty, d_year from returns "
           "left join sales on r_item = s_item and r_tick = s_tick "
           "join dates on s_date = d_date "
           "order by r_item, r_amt, s_qty")
    resident = Session()
    streamed = Session()
    for s in (resident, streamed):
        s.create_temp_view("returns", returns, base=True)
        s.create_temp_view("dates", dates, base=True)
    resident.create_temp_view("sales", sales, base=True)
    streamed.create_temp_view("sales", ChunkedTable(sales, chunk_rows=512),
                              base=True)
    a = resident.sql(sql).collect()
    b = streamed.sql(sql).collect()
    assert a == b
    # the parent inner join drops unmatched returns rows: no row may
    # carry a NULL sales side (extras leaking through would)
    assert all(r[2] is not None for r in b)


# ---------------------------------------------------------------------------
# chunk filtering and partition routing held to numpy: the predicate
# shapes, chunk edges and routing hash no engine arm is the reference for
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _synccount():
    """tests/test_synccount.py, loaded once by path as the diff tools
    do: the home of the shared forced-partition context."""
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "test_synccount.py")
    spec = importlib.util.spec_from_file_location("sc_fixtures", path)
    sc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sc)
    return sc


def _lineitem():
    """8,000 rows in 1,024-row chunks: a FOR-coded int key, a string
    dictionary, a float column with real NULLs, a FOR-coded price; and a
    fan-out side (two rows a key, no PK) that makes a join partition."""
    rng = np.random.default_rng(11)
    n = 8_000
    cats = np.asarray(["alpha", "beta", "gamma", "delta"], dtype=object)
    qty = rng.integers(0, 50, n).astype(float)
    null = rng.random(n) < 0.15
    cols = {
        "l_key": rng.integers(1, 500, n),
        "l_cat": cats[rng.integers(0, 4, n)],
        "l_qty": np.where(null, np.nan, qty),
        "l_price": rng.integers(1, 10_000, n),
    }
    table = pa.table({
        "l_key": pa.array(cols["l_key"], pa.int64()),
        "l_cat": pa.array(cols["l_cat"]),
        "l_qty": pa.array(qty, mask=null),
        "l_price": pa.array(cols["l_price"], pa.int64()),
    })
    fan = pa.table({
        "r_key": pa.array(np.repeat(np.arange(1, 500), 2), pa.int64()),
        "r_amt": pa.array(rng.integers(1, 100, 998), pa.int64()),
    })
    return cols, table, fan


# (WHERE text, numpy mask over the columns, whether sum(l_price) is asked)
_PREDICATE_SHAPES = {
    "str-eq": ("l_cat = 'beta'",
               lambda c: c["l_cat"] == "beta", True),
    "str-ne-absent": ("l_cat <> 'omega'",
                      lambda c: c["l_cat"] != "omega", False),
    "between": ("l_price between 100 and 5000",
                lambda c: (c["l_price"] >= 100) & (c["l_price"] <= 5000),
                True),
    "in": ("l_key in (1, 2, 3, 499)",
           lambda c: np.isin(c["l_key"], [1, 2, 3, 499]), False),
    "not-in-and": ("l_key not in (7, 9) and l_price > 50",
                   lambda c: ~np.isin(c["l_key"], [7, 9])
                   & (c["l_price"] > 50), False),
    "is-null": ("l_qty is null", lambda c: np.isnan(c["l_qty"]), False),
    "not-null-float-lit": ("l_qty is not null and l_price > 2500.5",
                           lambda c: ~np.isnan(c["l_qty"])
                           & (c["l_price"] > 2500.5), True),
    # NOT IN whose literals are all ABSENT (string dictionary /
    # fractional at the column's scale): membership is all-false, so
    # the negation must keep every non-null row
    "not-in-absent-str": ("l_cat not in ('omega', 'zeta')",
                          lambda c: ~np.isin(c["l_cat"],
                                             ["omega", "zeta"]), False),
    "not-in-fractional": ("l_key not in (2.5, 3.5)",
                          lambda c: ~np.isin(c["l_key"], [2.5, 3.5]),
                          False),
    # mixed-lane BETWEEN (float low bound, int high bound) and the
    # negated int-lane range
    "between-mixed": ("l_price between 100.5 and 5000",
                      lambda c: (c["l_price"] >= 100.5)
                      & (c["l_price"] <= 5000), True),
    "not-between": ("l_price not between 100 and 5000",
                    lambda c: ~((c["l_price"] >= 100)
                                & (c["l_price"] <= 5000)), False),
}


@pytest.mark.parametrize("drive", ["plain", "partitioned"])
@pytest.mark.parametrize("shape", list(_PREDICATE_SHAPES))
def test_streamed_predicate_shapes_match_numpy(shape, drive):
    """Each predicate shape through the COMPILED chunk pipeline, counted
    and summed against numpy over the same arrays: under the plain drive
    loop as a bare scan, and under the forced partition count joined to
    a fan-out side (two rows a key), where the chunk filter composes
    with the partition pass's masks over two dispatches a chunk."""
    import contextlib

    from nds_tpu.listener import drain_stream_events
    where, mask_of, with_sum = _PREDICATE_SHAPES[shape]
    cols, table, fan = _lineitem()
    live = mask_of(cols)
    assert 0 < live.sum(), shape
    aggs = "count(*) c" + (", sum(l_price) s" if with_sum else "")
    if drive == "plain":
        sql = f"select {aggs} from lineitem where {where}"
        ctx, mult, parts = contextlib.nullcontext(), 1, 1
    else:
        sql = (f"select {aggs} from lineitem, fan "
               f"where l_key = r_key and {where}")
        ctx, mult, parts = _synccount()._forced_stream_partitions(), 2, 2
    want = (int(live.sum()) * mult,)
    if with_sum:
        want += (int(cols["l_price"][live].sum()) * mult,)
    with ctx:
        s = Session()
        s.create_temp_view("lineitem", ChunkedTable(table, chunk_rows=1024),
                           base=True)
        s.create_temp_view("fan", fan, base=True)
        drain_stream_events()
        got = s.sql(sql).collect()
        events = drain_stream_events()
    assert [e.path for e in events] == ["compiled"], events
    assert events[0].chunks == 8 and events[0].partitions == parts
    assert got == [want], (sql, got, want)


def _edge_table(n, chunk_rows, null_tail=False):
    rng = np.random.default_rng(n)
    key = rng.integers(1, 100, n)
    val = rng.integers(1, 1000, n)
    null = np.zeros(n, dtype=bool)
    if null_tail:
        null[-3:] = True                 # the last live rows are NULL keys
    t = pa.table({"e_key": pa.array(key, pa.int64(), mask=null),
                  "e_val": pa.array(val, pa.int64())})
    return key, val, null, ChunkedTable(t, chunk_rows=chunk_rows)


# case -> (rows, WHERE text, numpy mask over (key, null), NULL keys in
# the last live rows)
_CHUNK_EDGES = {
    "all-survive": (3000, "e_key >= 1", lambda k, nl: k >= 1, False),
    "none-survive": (3000, "e_key > 100", lambda k, nl: k > 100, False),
    "exact-multiple": (3072, "e_key < 50", lambda k, nl: k < 50, False),
    "one-fewer": (3071, "e_key < 50", lambda k, nl: k < 50, False),
    "one-more": (3073, "e_key < 50", lambda k, nl: k < 50, False),
    "null-tail": (3000, "e_key >= 1", lambda k, nl: (k >= 1) & ~nl, True),
    "null-tail-is-null": (3000, "e_key is null", lambda k, nl: nl, True),
}


@pytest.mark.parametrize("case", list(_CHUNK_EDGES))
def test_streamed_filter_chunk_edges(case):
    """The compiled chunk filter at its edges: every row survives; none
    does; a table of exactly k chunks, one row fewer and one more (the
    padded last chunk's pad rows never count); NULL keys in the last
    live rows of the padded last chunk. Count and sum against numpy."""
    from nds_tpu.listener import drain_stream_events
    n, where, mask_of, null_tail = _CHUNK_EDGES[case]
    key, val, null, chunked = _edge_table(n, 1024, null_tail)
    live = mask_of(key, null)
    s = Session()
    s.create_temp_view("edges", chunked, base=True)
    drain_stream_events()
    got = s.sql(f"select count(*) c, sum(e_val) s from edges "
                f"where {where}").collect()
    events = drain_stream_events()
    assert [e.path for e in events] == ["compiled"], events
    assert events[0].chunks == -(-n // 1024)
    want_sum = int(val[live].sum()) if live.any() else None
    assert got == [(int(live.sum()), want_sum)], (case, got)


def _np_hash_mix(h, data):
    """numpy re-computation of the routing hash's fold (FNV-style
    multiplicative mix over the low and high 32 bits of each key)."""
    data = np.asarray(data)
    if np.issubdtype(data.dtype, np.floating):
        data = data.view(np.int64 if data.dtype.itemsize == 8 else np.int32)
    x = data.astype(np.int64)
    lo = (x & 0xffffffff).astype(np.uint32)
    hi = ((x >> 32) & 0xffffffff).astype(np.uint32)
    h = (h ^ lo) * np.uint32(2654435761)
    h = h ^ (h >> np.uint32(16))
    h = (h ^ hi) * np.uint32(2246822519)
    return h ^ (h >> np.uint32(13))


# fold of int64 [0, 1, -1] from the FNV offset basis, as the tree routed
# rows before the hash had a test of its own
_GOLDEN_HASH_MIX = [342040849, 3227572040, 2575806610]


def test_hash_mix_routing_is_pinned(monkeypatch):
    """The streamed partition/shard routing hash, pinned by itself: the
    fold over fixed int32 / int64 / uint32 / float vectors equals a
    numpy re-computation (and three golden values), and the partition
    pass of a P = 4 pipeline hands out exactly ``hash & 3`` per row with
    a histogram equal to ``np.bincount`` of the live rows' ids."""
    import jax.numpy as jnp

    from nds_tpu.engine import stream as S
    seed = np.full(6, 2166136261, dtype=np.uint32)
    vectors = [
        np.array([0, 1, -1, 7, -2 ** 31, 2 ** 31 - 1], dtype=np.int32),
        np.array([0, 1, -1, 2 ** 40 + 3, -2 ** 63, 2 ** 63 - 1],
                 dtype=np.int64),
        np.array([0, 1, 2 ** 31, 2 ** 32 - 1, 12345, 99], dtype=np.uint32),
        np.array([0.0, -0.0, 1.5, -2.25, 1e300, np.inf], dtype=np.float64),
        np.array([0.0, -0.0, 1.5, -2.25, 3e38, np.inf], dtype=np.float32),
    ]
    h_dev, h_np = jnp.asarray(seed), seed
    for v in vectors:
        h_dev = S._hash_mix(h_dev, jnp.asarray(v))
        h_np = _np_hash_mix(h_np, v)
        assert h_dev.dtype == jnp.uint32
        np.testing.assert_array_equal(np.asarray(h_dev), h_np)
    one = S._hash_mix(jnp.asarray(seed[:3]),
                      jnp.asarray(np.array([0, 1, -1], dtype=np.int64)))
    assert np.asarray(one).tolist() == _GOLDEN_HASH_MIX

    # the partition pass of a real P = 4 pipeline
    from nds_tpu.listener import drain_stream_events
    sales, returns = _return_tables(n=2000)
    monkeypatch.setenv("NDS_TPU_STREAM_PARTITIONS", "4")
    S.reset_pipeline_cache()
    s = Session()
    s.create_temp_view("sales", ChunkedTable(sales, chunk_rows=800),
                       base=True)
    s.create_temp_view("returns", returns, base=True)
    drain_stream_events()
    first = s.sql(_PART_SQL).collect()
    assert [e.partitions for e in drain_stream_events()] == [4]
    (pipe,) = [p for p in S._PIPELINE_CACHE.values()
               if p.n_partitions == 4]
    (slot,) = pipe.key_slots
    seen = []
    real = pipe._pid_jit

    def spy(flat, n_dev, hist):
        pids, hist = real(flat, n_dev, hist)
        seen.append((np.asarray(flat[slot]), int(n_dev), np.asarray(pids),
                     np.asarray(hist)))
        return pids, hist
    monkeypatch.setattr(pipe, "_pid_jit", spy)
    assert s.sql(_PART_SQL).collect() == first          # the cached pipeline
    assert len(seen) == 3                # one partition pass per chunk
    total = np.zeros(4, dtype=np.int64)
    for keys, n_live, pids, hist in seen:
        want = (_np_hash_mix(np.full(len(keys), 2166136261,
                                     dtype=np.uint32), keys)
                & np.uint32(3)).astype(np.int32)
        np.testing.assert_array_equal(pids, want)
        total += np.bincount(want[:n_live], minlength=4)
        np.testing.assert_array_equal(hist, total)
    assert total.sum() == 2000 and (total > 0).all()
