# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""The scan-bytes function on a hand-made schema, and the table of peaks."""

import decimal
import os

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from benchmark import peaks, scanbytes


@pytest.fixture()
def parquet_dir(tmp_path):
    """fact: 1000 rows in two date partitions (int32 key, int64 count,
    decimal(7,2) price, a string); dim: 10 rows."""
    for part, n in (("f_date_sk=1", 600), ("f_date_sk=2", 400)):
        d = tmp_path / "fact" / part
        d.mkdir(parents=True)
        pq.write_table(pa.table({
            "f_item_sk": pa.array(range(n), pa.int32()),
            "f_quantity": pa.array(range(n), pa.int64()),
            "f_price": pa.array([decimal.Decimal("1.25")] * n,
                                pa.decimal128(7, 2)),
            "f_note": pa.array(["ab"] * n, pa.string())}),
            d / "part-0.parquet", use_dictionary=False)
    (tmp_path / "dim").mkdir()
    pq.write_table(pa.table({"d_item_sk": pa.array(range(10), pa.int32()),
                             "d_flag": pa.array([True] * 10)}),
                   tmp_path / "dim" / "part-0.parquet")
    return str(tmp_path)


def test_physical_widths(parquet_dir):
    stats = scanbytes.table_stats(parquet_dir, "fact")
    assert stats["rows"] == 1000
    assert stats["bytes"]["f_item_sk"] == 4000          # INT32
    assert stats["bytes"]["f_quantity"] == 8000         # INT64
    assert stats["bytes"]["f_price"] == 4000            # FLBA(4): decimal(7,2)
    assert 2000 <= stats["bytes"]["f_note"] <= 8000     # bytes + lengths
    assert "f_date_sk" not in stats["bytes"]            # the partition key


def test_statement_counts_listed_columns_named_in_its_text(parquet_dir):
    scans = {"fact": ["f_date_sk", "f_item_sk", "f_price", "f_quantity"],
             "dim": ["d_item_sk", "d_flag"]}
    sql = ("select sum(f_price) from fact, dim where f_item_sk = d_item_sk "
           "and f_date_sk = 2")

    def stats_of(table):
        return scanbytes.table_stats(parquet_dir, table)
    got = scanbytes.statement_scan_bytes(scans, sql, stats_of)
    # partition key as INT32 + item key + price, and the dim's key;
    # f_quantity and d_flag are listed but not in the text
    assert got == 1000 * 4 + 4000 + 4000 + 10 * 4
    assert scanbytes.columns_in_text(["f_price", "f_pric"], sql) == ["f_price"]


def test_missing_table_is_an_error(parquet_dir):
    with pytest.raises(FileNotFoundError):
        scanbytes.table_stats(parquet_dir, "nope")


def test_peaks_known_kind_and_unknown_kind_refused():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["bf16_flops_per_s"] == 197e12
    assert "Google Cloud" in v5e["source"]
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("TPU v9 imaginary")
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("cpu")
