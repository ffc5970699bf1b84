# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Coverage sweep: run every query template through the engine on tiny data.

Writes a pass/fail table and groups failures by first error line so planner
gaps can be burned down in frequency order. Pass `--update-lst` to rewrite
nds_tpu/queries/templates/supported.lst with the passing set (the ratchet).
"""

import argparse
import os
import subprocess
import sys
import time
import traceback

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ["JAX_PLATFORMS"] = "cpu"
# virtual multi-device mesh for --mesh parity runs (must precede jax init)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
# same-machine dev loop: persistent compile cache cuts re-sweeps ~3x
os.environ.setdefault("NDS_TPU_COMP_CACHE", "force")
import jax  # noqa: E402  (force cpu after import too)
jax.config.update("jax_platforms", "cpu")

SCALE = os.environ.get("NDS_SWEEP_SCALE", "0.01")
CACHE = os.path.join(REPO, ".bench_cache", f"sf{SCALE}")
NDSGEN = os.path.join(REPO, "native", "ndsgen", "ndsgen")


def ensure_data():
    if not os.path.exists(NDSGEN):
        subprocess.run(["make", "-C", os.path.dirname(NDSGEN)], check=True)
    marker = os.path.join(CACHE, ".complete")
    if not os.path.exists(marker):
        os.makedirs(CACHE, exist_ok=True)
        subprocess.run([NDSGEN, "-scale", SCALE, "-dir", CACHE], check=True)
        with open(marker, "w"):
            pass
    return CACHE


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--queries", help="comma list like query5,query14_part1")
    ap.add_argument("--update-lst", action="store_true")
    ap.add_argument("--full-trace", action="store_true")
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="also run every query on an N-device mesh Session "
                         "and require row-for-row parity with single-device")
    args = ap.parse_args()

    from nds_tpu.queries import generate_query_streams, list_templates
    from nds_tpu.power import gen_sql_from_stream
    from nds_tpu.engine.session import Session
    from nds_tpu.schema import get_schemas

    data_dir = ensure_data()
    stream_dir = os.path.join(REPO, ".bench_cache", "sweep_stream")
    os.makedirs(stream_dir, exist_ok=True)
    stream_file = os.path.join(stream_dir, "query_0.sql")
    generate_query_streams(stream_dir, streams=1, rngseed=19620718,
                           scale=float(SCALE))

    queries = gen_sql_from_stream(stream_file)
    if args.queries:
        want = set(x.strip() for x in args.queries.split(","))
        queries = {k: v for k, v in queries.items() if k in want}

    session = Session()
    sessions = [session]
    if args.mesh:
        sessions.append(Session(conf={"mesh_shape": args.mesh}))
    schemas = get_schemas(use_decimal=True)
    for sess in sessions:
        for tname, fields in schemas.items():
            for path in (os.path.join(data_dir, tname),
                         os.path.join(data_dir, tname + ".dat")):
                if os.path.exists(path):
                    sess.read_raw_view(tname, path, fields)
                    break

    passed, failed = [], {}
    for qname, qtext in queries.items():
        t0 = time.perf_counter()
        try:
            res = session.sql(qtext)
            rows = res.collect()
            ms = (time.perf_counter() - t0) * 1000
            if args.mesh:
                mrows = sessions[1].sql(qtext).collect()
                if mrows != rows:
                    # unordered parity: ORDER BY keys can tie, and tied-row
                    # order is implementation-defined (the validation driver
                    # has --ignore_ordering for the same reason)
                    if sorted(map(repr, mrows)) != sorted(map(repr, rows)):
                        raise AssertionError(
                            f"mesh({args.mesh}) results diverge: "
                            f"{len(mrows)} vs {len(rows)} rows")
            passed.append((qname, ms))
            print(f"PASS {qname:22s} {ms:8.1f} ms  rows={res.num_rows}",
                  flush=True)
        except Exception as e:
            err = f"{type(e).__name__}: {e}"
            first = err.splitlines()[0][:110]
            failed.setdefault(first, []).append(qname)
            print(f"FAIL {qname:22s} {first}", flush=True)
            if args.full_trace:
                traceback.print_exc()

    print(f"\n=== {len(passed)} passed / {len(passed) + sum(len(v) for v in failed.values())} total ===")
    for err, qs in sorted(failed.items(), key=lambda kv: -len(kv[1])):
        print(f"[{len(qs):2d}] {err}\n     {' '.join(qs)}")

    if args.update_lst and passed:
        lst = os.path.join(REPO, "nds_tpu", "queries", "templates", "supported.lst")
        # a template is supported only if NO part of it failed (query14 with
        # a failing _part2 must not enter the ratchet via a passing _part1)
        failed_tpls = {q.split("_part")[0]
                       for qs in failed.values() for q in qs}
        names = sorted({q.split("_part")[0] for q, _ in passed} - failed_tpls,
                       key=lambda s: int(s.replace("query", "")))
        with open(lst, "w") as f:
            f.write("# queries the engine executes end-to-end (coverage ratchet)\n")
            for n in names:
                f.write(n + ".tpl\n")  # template filenames, ready for streams
        print(f"wrote {lst}: {len(names)} templates")


if __name__ == "__main__":
    main()
