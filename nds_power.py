#!/usr/bin/env python3
# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Power Run driver.

TPU-build equivalent of the reference Power Run CLI (ref: nds/nds_power.py:
332-410): runs a generated query stream against the columnar device engine,
recording per-query times to a CSV log and JSON summaries, with the same
argument surface plus a ``--device`` switch (the north star's
``power_run_tpu.template`` contract: same driver, TPU execution).
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from nds_tpu.check import check_version, select_device  # noqa: E402

check_version()


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("input_prefix",
                        help="text to prepend to every input file path; the "
                        "warehouse root for iceberg/delta input formats.")
    parser.add_argument("query_stream_file",
                        help="query stream file that contains NDS queries in "
                        "specific order.")
    parser.add_argument("time_log",
                        nargs="?",
                        help="path to execution time log.",
                        default="")
    parser.add_argument("--input_format",
                        choices=["parquet", "orc", "avro", "csv", "json",
                                 "iceberg", "delta"],
                        default="parquet",
                        help="type for input data source "
                        "(ref: nds/nds_power.py:357-364).")
    parser.add_argument("--output_prefix",
                        help="text to prepend to every output file.")
    parser.add_argument("--output_format",
                        default="parquet",
                        help="type of query output.")
    parser.add_argument("--property_file",
                        help="property file for engine configuration.")
    parser.add_argument("--floats",
                        action="store_true",
                        help="use double instead of decimal for monetary "
                        "columns when loading text data.")
    parser.add_argument("--json_summary_folder",
                        help="empty folder/path to save JSON summary files.")
    parser.add_argument("--extra_time_log",
                        help="extra path to save time log (cloud copy).")
    parser.add_argument("--sub_queries",
                        type=lambda s: [x.strip() for x in s.split(",")],
                        help="comma separated list of queries to run, e.g. "
                        "'query1,query2'. Use _part1/_part2 suffixes for "
                        "query14/23/24/39.")
    parser.add_argument("--allow_failure",
                        action="store_true",
                        help="do not exit non-zero when a query fails.")
    parser.add_argument("--device",
                        choices=["tpu", "cpu"],
                        default="tpu",
                        help="execution device; 'cpu' pins the engine to the "
                        "host platform (useful for baseline/validation "
                        "runs). 'tpu' with JAX_PLATFORMS unset pins the "
                        "TPU: a missing chip is an error, never a CPU run.")
    parser.add_argument("--profile",
                        help="folder for per-query device profiler traces "
                        "(XProf/TensorBoard dumps).")
    parser.add_argument("--trace-dir",
                        help="folder for per-query Chrome trace_event JSON "
                        "files from the engine's span tracer (load in "
                        "chrome://tracing or Perfetto; aggregate with "
                        "tools/trace_report.py). Zero added host syncs.")
    parser.add_argument("--ledger",
                        help="campaign evidence ledger file (append-only "
                        "JSONL, nds_tpu/obs/ledger.py): one validated "
                        "record per query, flushed as it lands, plus a "
                        "terminal end record — the input to "
                        "tools/bench_compare.py. Also via NDS_TPU_LEDGER.")
    parser.add_argument("--warm",
                        action="store_true",
                        help="precompile pass: execute the stream once to "
                        "populate the persistent XLA compile cache (the "
                        "warmed-JVM analog); the time log is written with "
                        "Warm markers so it can never be mistaken for an "
                        "official Power Run.")
    args = parser.parse_args()

    select_device(args.device)

    from nds_tpu.power import gen_sql_from_stream, run_query_stream  # noqa: E402

    query_dict = gen_sql_from_stream(args.query_stream_file)
    run_query_stream(args.input_prefix,
                     args.property_file,
                     query_dict,
                     args.time_log,
                     args.extra_time_log,
                     args.sub_queries,
                     args.input_format,
                     not args.floats,
                     args.output_prefix,
                     args.output_format,
                     args.json_summary_folder,
                     args.allow_failure,
                     profile_folder=args.profile,
                     warm=args.warm,
                     trace_dir=args.trace_dir,
                     ledger_path=args.ledger)
