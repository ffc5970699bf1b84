#!/usr/bin/env python3
# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Data Maintenance driver.

TPU-build equivalent of the reference maintenance CLI (ref:
nds/nds_maintenance.py:40-319): registers the refresh (``s_*``) CSVs as temp
views, loads the LF_*/DF_* refresh functions, substitutes the DATE1/DATE2
placeholders from the generated ``delete``/``inventory_delete`` tables, runs
each function against the snapshot warehouse under a BenchReport, and writes
the CSV time log (seconds) + per-query JSON summaries.
"""

import argparse
import csv
import os
import sys
from datetime import datetime

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from nds_tpu.check import check_version, check_json_summary_folder, \
    get_abs_path, select_device  # noqa: E402

check_version()

INSERT_FUNCS = [
    'LF_CR',
    'LF_CS',
    'LF_I',
    'LF_SR',
    'LF_SS',
    'LF_WR',
    'LF_WS']
DELETE_FUNCS = [
    'DF_CS',
    'DF_SS',
    'DF_WS']
INVENTORY_DELETE_FUNC = ['DF_I']
DM_FUNCS = INSERT_FUNCS + DELETE_FUNCS + INVENTORY_DELETE_FUNC


def get_delete_date(session):
    """Delete-date tuples for the DELETE functions, from the generated
    ``delete``/``inventory_delete`` tables (ref: nds/nds_maintenance.py:60-73)."""
    date_dict = {}
    for key, table in (("delete", "delete"),
                       ("inventory_delete", "inventory_delete")):
        rows = session.sql(f"select * from `{table}`").collect()
        date_dict[key] = [(str(r[0]), str(r[1])) for r in rows]
    return date_dict


def replace_date(query_list, date_tuple_list):
    """Apply each (date1, date2) tuple to the DELETE statements, earlier date
    first (ref: nds/nds_maintenance.py:75-96)."""
    q_updated = []
    for date_tuple in date_tuple_list:
        earlier, later = sorted(date_tuple)
        for q in query_list:
            q_updated.append(q.replace("DATE1", earlier).replace("DATE2", later))
    return q_updated


def get_valid_query_names(spec_queries):
    if spec_queries:
        for q in spec_queries:
            if q not in DM_FUNCS:
                raise Exception(f"invalid Data Maintenance query: {q}. "
                                f"Valid are: {DM_FUNCS}")
        return spec_queries
    return DM_FUNCS


def split_statements(text: str):
    """Split a refresh-function file into executable statements, dropping
    comment lines and empty fragments."""
    lines = [ln for ln in text.splitlines() if not ln.lstrip().startswith("--")]
    statements = []
    for frag in "\n".join(lines).split(";"):
        frag = frag.strip()
        if frag:
            statements.append(frag + ";")
    return statements


def get_maintenance_queries(session, folder, valid_queries):
    """Load refresh-function statement lists, with DATE substitution for the
    delete functions (ref: nds/nds_maintenance.py:121-147)."""
    delete_date_dict = get_delete_date(session)
    folder_abs_path = get_abs_path(folder)
    q_dict = {}
    for q in valid_queries:
        with open(os.path.join(folder_abs_path, q + '.sql')) as f:
            q_content = split_statements(f.read())
        if q in DELETE_FUNCS:
            # 3 date tuples per DELETE function (TPC-DS spec 5.3.11)
            q_content = replace_date(q_content, delete_date_dict['delete'])
        if q in INVENTORY_DELETE_FUNC:
            q_content = replace_date(q_content,
                                     delete_date_dict['inventory_delete'])
        q_dict[q] = q_content
    return q_dict


def run_dm_query(session, query_list, query_name):
    for q in query_list:
        session.sql(q)


def run_query(session, query_dict, time_log_output_path, json_summary_folder,
              property_file):
    """Run every maintenance function under a BenchReport and write the time
    log in seconds (ref: nds/nds_maintenance.py:207-268)."""
    from nds_tpu.report import BenchReport

    execution_time_list = []
    check_json_summary_folder(json_summary_folder)
    total_time_start = datetime.now()
    app_id = session.app_id
    DM_start = datetime.now()
    for query_name, q_content in query_dict.items():
        print(f"====== Run {query_name} ======")
        q_report = BenchReport(session)
        elapsed_ms = q_report.report_on(run_dm_query, session, q_content,
                                        query_name)
        print(f"Time taken: {elapsed_ms} millis for {query_name}")
        execution_time_list.append((app_id, query_name, elapsed_ms / 1000.0))
        if json_summary_folder:
            if property_file:
                summary_prefix = os.path.join(
                    json_summary_folder,
                    os.path.basename(property_file).split('.')[0])
            else:
                summary_prefix = os.path.join(json_summary_folder, '')
            q_report.write_summary(query_name, prefix=summary_prefix)
    DM_end = datetime.now()
    DM_elapse = (DM_end - DM_start).total_seconds()
    total_elapse = (DM_end - total_time_start).total_seconds()
    print(f"====== Data Maintenance Start Time: {DM_start}")
    print(f"====== Data Maintenance Time: {DM_elapse} s ======")
    print(f"====== Total Time: {total_elapse} s ======")
    execution_time_list.append((app_id, "Data Maintenance Start Time", DM_start))
    execution_time_list.append((app_id, "Data Maintenance End Time", DM_end))
    execution_time_list.append((app_id, "Data Maintenance Time", DM_elapse))
    execution_time_list.append((app_id, "Total Time", total_elapse))

    header = ["application_id", "query", "time/s"]
    with open(time_log_output_path, 'w', encoding='UTF8') as f:
        writer = csv.writer(f)
        writer.writerow(header)
        writer.writerows(execution_time_list)


def register_warehouse_tables(session, warehouse):
    """Attach the warehouse and register its current snapshots as views."""
    from nds_tpu.engine.column import from_arrow
    session.warehouse = warehouse
    for table in warehouse.tables():
        session.create_temp_view(table, from_arrow(warehouse.read(table)),
                                 base=True)


def register_temp_views(session, refresh_data_path):
    """Register the refresh CSVs as temp views
    (ref: nds/nds_maintenance.py:270-274)."""
    from nds_tpu.schema import get_maintenance_schemas
    refresh_tables = get_maintenance_schemas(True)
    for table, fields in refresh_tables.items():
        for path in (os.path.join(refresh_data_path, table),
                     os.path.join(refresh_data_path, table + ".dat")):
            if os.path.exists(path):
                session.read_raw_view(table, path, fields)
                break
        else:
            raise FileNotFoundError(
                f"refresh table {table} not found under {refresh_data_path}")


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument('warehouse_path',
                        help='warehouse path for Data Maintenance test.')
    parser.add_argument('refresh_data_path',
                        help='path to refresh data')
    parser.add_argument('maintenance_queries_folder',
                        help='folder contains all NDS Data Maintenance '
                        'queries. If "--maintenance_queries" is not set, all '
                        'queries under the folder will be executed.')
    parser.add_argument('time_log',
                        help='path to execution time log, only support local '
                        'path.',
                        default="")
    parser.add_argument('--maintenance_queries',
                        type=lambda s: s.split(','),
                        help='specify Data Maintenance query names by a '
                        'comma separated string. e.g. "LF_CR,LF_CS"')
    parser.add_argument('--property_file',
                        help='property file for engine configuration.')
    parser.add_argument('--json_summary_folder',
                        help='empty folder/path to save JSON summary files.')
    parser.add_argument('--warehouse_type',
                        choices=['iceberg', 'delta'],
                        default='iceberg',
                        help='type of the warehouse used for Data '
                        'Maintenance test (kept for reference CLI parity; '
                        'both map to the snapshot warehouse).')
    parser.add_argument('--device',
                        choices=['tpu', 'cpu'],
                        default='tpu',
                        help='execution device.')
    args = parser.parse_args()

    select_device(args.device)

    from nds_tpu.engine.session import Session  # noqa: E402
    from nds_tpu.warehouse import Warehouse  # noqa: E402

    valid_queries = get_valid_query_names(args.maintenance_queries)
    session = Session()
    warehouse = Warehouse(args.warehouse_path)
    register_warehouse_tables(session, warehouse)
    register_temp_views(session, args.refresh_data_path)
    query_dict = get_maintenance_queries(session,
                                         args.maintenance_queries_folder,
                                         valid_queries)
    run_query(session, query_dict, args.time_log, args.json_summary_folder,
              args.property_file)
