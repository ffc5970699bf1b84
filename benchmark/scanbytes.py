# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""The bytes a statement's scans must read, whatever implements them.

Fed by data only: the traffic entry lists, per statement, the tables and
columns it scans; a column counts when its name stands in the statement's
text (a template may pick one of several measures), at rows x the physical
width of the column in the cell's Parquet files. Never a count the program
makes.

Physical width: INT32 4, INT64 8, FLOAT 4, DOUBLE 8, BOOLEAN 1,
FIXED_LEN_BYTE_ARRAY its length (a decimal(7,2) is 4 bytes), BYTE_ARRAY the
uncompressed size of its column chunks. A column that is in no footer is the
directory's partition key (``store_sales/ss_sold_date_sk=.../``) and counts
as an INT32.
"""

from __future__ import annotations

import glob
import os
import re

PHYSICAL_WIDTH = {"INT32": 4, "INT64": 8, "INT96": 12, "FLOAT": 4,
                  "DOUBLE": 8, "BOOLEAN": 1}
PARTITION_KEY_WIDTH = 4


def table_stats(parquet_dir: str, table: str) -> dict:
    """{"rows": n, "bytes": {column: n}} from the table's Parquet footers;
    no data page is read."""
    import pyarrow.parquet as pq
    files = sorted(glob.glob(os.path.join(parquet_dir, table, "**",
                                          "*.parquet"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no Parquet files for table {table!r} under "
                                f"{parquet_dir}")
    rows = 0
    col_bytes: dict = {}
    for path in files:
        meta = pq.ParquetFile(path).metadata
        rows += meta.num_rows
        for rg in range(meta.num_row_groups):
            group = meta.row_group(rg)
            for ci in range(group.num_columns):
                col = group.column(ci)
                physical = col.physical_type
                if physical == "BYTE_ARRAY":
                    n = col.total_uncompressed_size
                elif physical == "FIXED_LEN_BYTE_ARRAY":
                    n = group.num_rows * meta.schema.column(ci).length
                else:
                    n = group.num_rows * PHYSICAL_WIDTH[physical]
                col_bytes[col.path_in_schema] = (
                    col_bytes.get(col.path_in_schema, 0) + n)
    return {"rows": rows, "bytes": col_bytes}


def columns_in_text(columns, sql: str) -> list:
    return [c for c in columns
            if re.search(r"\b" + re.escape(c) + r"\b", sql)]


def statement_scan_bytes(scans: dict, sql: str, stats_of) -> int:
    """``scans`` = {table: [columns]}; ``stats_of(table)`` -> table_stats."""
    total = 0
    for table, cols in scans.items():
        stats = stats_of(table)
        for c in columns_in_text(cols, sql):
            total += stats["bytes"].get(
                c, stats["rows"] * PARTITION_KEY_WIDTH)
    return total
