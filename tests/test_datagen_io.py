# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Native generator + CSV ingest + columnar IO tests."""

import filecmp
import os
import subprocess

import pyarrow as pa
import pytest

from nds_tpu.io import read_raw_table, read_table, write_table
from nds_tpu.schema import get_maintenance_schemas, get_schemas

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NDSGEN = os.path.join(REPO, "native", "ndsgen", "ndsgen")


@pytest.fixture(scope="module", autouse=True)
def built_generator():
    """The generator these tests run, built as ``benchmark/datagen.py``
    builds it (the Makefile links to a name of its own and renames, so two
    makes at once are safe). A checkout that cannot build it fails these
    tests: skipped, they would count in a used checkout and not in a clean
    one."""
    made = subprocess.run(["make", "-C", os.path.dirname(NDSGEN)],
                          capture_output=True, text=True)
    assert made.returncode == 0 and os.path.exists(NDSGEN), (
        f"make -C native/ndsgen: exit {made.returncode}\n"
        f"{made.stdout[-2000:]}{made.stderr[-2000:]}")


def gen(tmp, *extra):
    subprocess.run([NDSGEN, "-scale", "0.001", "-dir", str(tmp), *extra], check=True)


def test_generator_emits_all_source_tables(tmp_path):
    gen(tmp_path)
    schemas = get_schemas(use_decimal=True)
    for table, fields in schemas.items():
        f = tmp_path / f"{table}.dat"
        assert f.exists(), table
        with open(f, encoding="iso8859-1") as fh:
            line = fh.readline()
        # trailing delimiter => n_fields + 1 splits
        assert line.count("|") == len(fields), table


def test_generator_deterministic(tmp_path):
    a, b = tmp_path / "a", tmp_path / "b"
    a.mkdir(); b.mkdir()
    gen(a, "-table", "customer")
    gen(b, "-table", "customer")
    assert filecmp.cmp(a / "customer.dat", b / "customer.dat", shallow=False)
    c = tmp_path / "c"
    c.mkdir()
    gen(c, "-table", "customer", "-rngseed", "7")
    assert not filecmp.cmp(a / "customer.dat", c / "customer.dat", shallow=False)


def test_chunks_union_equals_whole(tmp_path):
    """Parallel chunk files concatenate to the single-chunk output, so
    distributed generation is exact (ref: chunk semantics of
    nds/nds_gen_data.py:183-244)."""
    whole, parts = tmp_path / "whole", tmp_path / "parts"
    whole.mkdir(); parts.mkdir()
    gen(whole, "-table", "time_dim")
    for child in (1, 2, 3):
        subprocess.run([NDSGEN, "-scale", "0.001", "-dir", str(parts),
                        "-table", "time_dim", "-parallel", "3",
                        "-child", str(child)], check=True)
    merged = b"".join(
        (parts / f"time_dim_{c}_3.dat").read_bytes() for c in (1, 2, 3))
    assert merged == (whole / "time_dim.dat").read_bytes()


def test_update_mode_emits_refresh_tables(tmp_path):
    gen(tmp_path, "-update", "1")
    schemas = get_maintenance_schemas(use_decimal=True)
    for table, fields in schemas.items():
        fname = f"{table}_1.dat" if table in ("delete", "inventory_delete") \
            else f"{table}.dat"
        f = tmp_path / fname
        assert f.exists(), table
        with open(f) as fh:
            line = fh.readline()
        assert line.count("|") == len(fields), table


def test_csv_ingest_types_and_nulls(tmp_path):
    gen(tmp_path)
    schemas = get_schemas(use_decimal=True)
    t = read_raw_table(str(tmp_path / "store_sales.dat"), schemas["store_sales"])
    assert t.num_columns == 23
    assert t.schema.field("ss_list_price").type == pa.decimal128(7, 2)
    assert t.schema.field("ss_sold_date_sk").type == pa.int32()
    assert t.num_rows > 1000
    # nullable FK columns should actually contain nulls (~4%)
    assert t["ss_customer_sk"].null_count > 0
    # item_sk is non-nullable in the generator output
    assert t["ss_item_sk"].null_count == 0
    d = read_raw_table(str(tmp_path / "date_dim.dat"), schemas["date_dim"])
    assert d.schema.field("d_date").type == pa.date32()
    years = pa.compute.unique(d["d_year"]).to_pylist()
    assert 1900 in years and 2000 in years


def test_csv_ingest_directory_of_chunks(tmp_path):
    d = tmp_path / "time_dim"
    d.mkdir()
    for child in (1, 2):
        subprocess.run([NDSGEN, "-scale", "0.001", "-dir", str(d),
                        "-table", "time_dim", "-parallel", "2",
                        "-child", str(child)], check=True)
    t = read_raw_table(str(d), get_schemas(True)["time_dim"])
    assert t.num_rows == 86400


def test_columnar_roundtrip_partitioned(tmp_path):
    gen(tmp_path)
    schemas = get_schemas(use_decimal=True)
    t = read_raw_table(str(tmp_path / "store_sales.dat"), schemas["store_sales"])
    out = tmp_path / "pq"
    write_table(t, str(out), "parquet", partition_col="ss_sold_date_sk")
    back = read_table(str(out), "parquet")
    assert back.num_rows == t.num_rows
    assert set(back.column_names) == set(t.column_names)
    # partition dirs exist
    assert any(p.name.startswith("ss_sold_date_sk=") for p in out.iterdir())


def test_avro_roundtrip_values_and_partitioning(tmp_path):
    """Avro Load Test target (ref: nds/nds_transcode.py:61,85,257): the
    pure-python container codec must round-trip values exactly — decimals,
    dates, nulls — both flat and hive-partitioned."""
    gen(tmp_path)
    schemas = get_schemas(use_decimal=True)
    t = read_raw_table(str(tmp_path / "store_sales.dat"),
                       schemas["store_sales"])
    flat = tmp_path / "avro_flat"
    write_table(t, str(flat), "avro")
    back = read_table(str(flat), "avro")
    assert back.num_rows == t.num_rows
    assert set(back.column_names) == set(t.column_names)
    for name in ("ss_sold_date_sk", "ss_ticket_number", "ss_sales_price",
                 "ss_ext_list_price"):
        assert back.column(name).to_pylist() == t.column(name).to_pylist(), \
            name
    assert back.schema.field("ss_sales_price").type == \
        t.schema.field("ss_sales_price").type
    # hive-partitioned layout + deflate codec
    part = tmp_path / "avro_part"
    write_table(t, str(part), "avro", partition_col="ss_sold_date_sk",
                compression="deflate")
    assert any(p.name.startswith("ss_sold_date_sk=")
               for p in part.iterdir())
    back = read_table(str(part), "avro")
    assert back.num_rows == t.num_rows
    assert set(back.column_names) == set(t.column_names)
    assert sorted(back.column("ss_sold_date_sk").to_pylist(),
                  key=lambda v: (v is None, v)) == \
        sorted(t.column("ss_sold_date_sk").to_pylist(),
               key=lambda v: (v is None, v))


def test_referential_integrity_returns_match_sales(tmp_path):
    """Returns rows must hit real sale rows: same ticket+item exists in
    store_sales (generator derives returns from their originating sale)."""
    gen(tmp_path)
    schemas = get_schemas(use_decimal=True)
    ss = read_raw_table(str(tmp_path / "store_sales.dat"), schemas["store_sales"])
    sr = read_raw_table(str(tmp_path / "store_returns.dat"), schemas["store_returns"])
    sales_keys = set(zip(ss["ss_ticket_number"].to_pylist(),
                         ss["ss_item_sk"].to_pylist()))
    ret_keys = list(zip(sr["sr_ticket_number"].to_pylist(),
                        sr["sr_item_sk"].to_pylist()))
    hit = sum(1 for k in ret_keys if k in sales_keys)
    assert hit == len(ret_keys)


def test_state_vocabulary_banded_by_scale(tmp_path):
    """Generator state vocabulary and query-sampler band must agree (the
    scale-banded fips-distribution idea): at sub-SF1 both sides use the
    first 8 states, so state predicates stay non-degenerate."""
    import subprocess
    from nds_tpu.queries import POOLS, active_states, instantiate_template
    subprocess.run([NDSGEN, "-scale", "0.01", "-dir", str(tmp_path),
                    "-table", "customer_address"], check=True)
    allowed = set(POOLS["state"][:active_states(0.01)])
    assert len(allowed) == 8
    allowed_city = set(POOLS["city"][:8])
    allowed_county = set(POOLS["county"][:8])
    states, cities, counties = set(), set(), set()
    for ln in open(tmp_path / "customer_address.dat", encoding="iso-8859-1"):
        parts = ln.split("|")
        if parts[8]:
            states.add(parts[8])
        if parts[6]:
            cities.add(parts[6])
        if parts[7]:
            counties.add(parts[7])
    assert states and states <= allowed
    assert cities and cities <= allowed_city
    assert counties and counties <= allowed_county

    import numpy as np
    rng = np.random.default_rng(0)
    for _ in range(20):
        sql = instantiate_template("--@ ST = pool(state)\nselect '[ST]'",
                                   rng, scale=0.01)
        got = sql.split("'")[1]
        assert got in allowed
