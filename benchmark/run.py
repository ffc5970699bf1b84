#!/usr/bin/env python3
# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Run one cell of BENCHMARK.json once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip. The seed's data and query stream are made first,
by children that never see the device; only then does this process import
jax and the program, attach the device, load the tables, warm up every
statement of the cell's mix and run the closed loop for ``--seconds``. The
last line of standard output is the result object; every earlier line is one
JSON object too. See README.md beside this file.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare, datagen, manifest, window  # noqa: E402

CACHE_DIR = os.path.join(BENCH_DIR, ".cache")
MAX_WARM_PASSES = 3
EXIT_NO_ACCELERATOR = 3
EXIT_SETUP_FAILED = 4
EXIT_TRACE_UNREADABLE = 5


def say(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)


def die(code: int, message: str) -> None:
    print(message, file=sys.stderr, flush=True)
    sys.exit(code)


def reference_answers(config: dict, data: dict, queries: dict) -> tuple:
    """The plain reference's rows for each statement, computed once per
    (seed, statements) and kept beside the seed's data."""
    digest = hashlib.sha256(json.dumps(
        {n: [q["sql"], q["scans"]] for n, q in sorted(queries.items())},
        sort_keys=True).encode()).hexdigest()[:16]
    path = os.path.join(data["dir"], "reference", f"{digest}.json")
    if os.path.isfile(path):
        with open(path) as f:
            return json.load(f), True
    ref = manifest.load_module(os.path.join(BENCH_DIR, config["reference"]),
                               "benchmark_reference")
    rows = ref.answers(data["raw"], queries)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path + ".tmp", "w") as f:
        json.dump(rows, f)
    os.replace(path + ".tmp", path)
    return rows, False


def stream_path_ok(config: dict, rec: dict) -> bool:
    """Does the execution's streamed-scan evidence match what the
    configuration states (``stream_scans``: ``none`` | ``compiled``)?"""
    want = config.get("stream_scans")
    scans = rec["stream_scans"]
    if want == "none":
        return not scans
    if want == "compiled":
        return bool(scans) and all(
            s["path"] == "compiled" and not s.get("reason") for s in scans)
    return True


def cell_queries(stream_path: str, traffic: dict) -> tuple:
    """(names in the stream's order, {name: {"sql", "scans"}}, {name: traffic
    entry}) of the mix's statements in a seed's generated stream."""
    stream = datagen.stream_queries(stream_path)
    wanted = {q["name"]: q for q in traffic["queries"]}
    missing = [n for n in wanted if n not in stream]
    if missing:
        raise KeyError(f"the stream lacks {missing}")
    names = [n for n in stream if n in wanted]
    queries = {n: {"sql": stream[n], "scans": wanted[n]["scans"]}
               for n in names}
    return names, queries, wanted


def state_env(env: dict) -> None:
    """The configuration states its own stream knobs: whatever the caller
    exported is dropped first."""
    for key in [k for k in os.environ if k.startswith("NDS_TPU_STREAM_")]:
        del os.environ[key]
    os.environ.update({k: str(v) for k, v in env.items()})


def timed_call(prog, config: dict, queries: dict):
    """``execute(name, pass_index)`` for ``window.run_passes``: the timed
    entry, failed where the streamed-scan evidence is not what the
    configuration states."""
    def execute(name, _pass=0):
        rec = prog.execute(queries[name]["sql"])
        if rec["ok"] and not stream_path_ok(config, rec):
            rec["ok"] = False
            rec["error"] = (f"streamed scans {rec['stream_scans']} where the "
                            f"configuration states {config.get('stream_scans')}")
        return rec
    return execute


def executed_path(rec: dict) -> str:
    scans = rec["stream_scans"]
    if scans:
        return "/".join(sorted({s["path"] for s in scans})) + " stream"
    if any(p.startswith("replay.") for p in rec["phases"]):
        return "replay"
    return "eager"


def public(rec: dict) -> dict:
    """An execution's record without its rows and raw spans."""
    return {k: v for k, v in rec.items() if k not in ("rows", "spans")}


def main(argv=None) -> int:
    t_start = time.monotonic()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the configuration's rehearsal "
                    "scale: for the tests, never on the chip; the device "
                    "key then names cpu")
    args = ap.parse_args(argv)

    try:
        man = manifest.Manifest(ROOT)
        cell = man.cell(args.workload)
        config = man.config(cell["config"])
        traffic = man.traffic(cell["traffic"])
    except manifest.ManifestError as e:
        die(2, f"benchmark: {e}")
    if not os.path.isdir(os.path.join(ROOT, "nds_tpu")):
        die(2, f"benchmark: no program to measure under {ROOT}")
    shape = config["rehearsal"] if args.rehearse else config
    scale = str(shape["scale_factor"])
    env = dict(shape.get("env", {}))

    # -- set-up 1: the seed's data and stream, by children off the chip --------
    try:
        data = datagen.ensure(ROOT, CACHE_DIR, scale, args.seed)
    except datagen.DataError as e:
        die(EXIT_SETUP_FAILED, f"benchmark: data for seed {args.seed}: {e}")
    t_data = time.monotonic()
    try:
        names, queries, wanted = cell_queries(data["stream"], traffic)
    except KeyError as e:
        die(EXIT_SETUP_FAILED, f"benchmark: {e}")

    # -- set-up 2: this process takes the chip ---------------------------------
    state_env(env)
    from benchmark import program as program_mod
    try:
        prog = program_mod.Program(ROOT, cell["chips"],
                                   allow_cpu=args.rehearse)
    except program_mod.NoAccelerator as e:
        die(EXIT_NO_ACCELERATOR, f"benchmark: {e}")
    device = prog.device_info()
    t_attach = time.monotonic()
    load_rows = prog.load(data["parquet"], bool(config["use_decimal"]))
    t_load = time.monotonic()
    execute = timed_call(prog, config, queries)

    # -- set-up 3: warm up every statement until a pass compiles nothing -------
    # "compiles nothing": with the persistent cache on, no cache miss (a
    # program re-read from the cache is no compilation, though the program's
    # compile_ns() charges the read); with it off, compile_ns() unchanged.
    warm_passes = 0
    while True:
        warm = window.run_passes(names, execute, 0, max_passes=1)
        warm_passes += 1
        bad = [r for r in warm["records"] if not r["ok"]]
        if bad:
            die(EXIT_SETUP_FAILED, "benchmark: warm-up failed: "
                + "; ".join(f"{r['name']}: {r['error']}" for r in bad))
        compile_ms = sum(r["compile_ms"] for r in warm["records"])
        misses = sum(r["cache_misses"] for r in warm["records"])
        cache_on = sum(prog.cache_events.values()) > 0
        pending = [n for n in names
                   if prog.replay_pending(queries[n]["sql"])]
        say(event="warm_pass", index=warm_passes, compile_ms=compile_ms,
            cache_misses=misses, persistent_cache=cache_on,
            replay_pending=pending,
            walls_ms={r["name"]: (r["end_s"] - r["start_s"]) * 1e3
                      for r in warm["records"]})
        if not pending and (misses == 0 if cache_on else compile_ms == 0):
            break
        if warm_passes >= MAX_WARM_PASSES:
            die(EXIT_SETUP_FAILED,
                f"benchmark: pass {warm_passes} of the warm-up still "
                f"compiled ({misses} cache misses, {compile_ms:.1f} ms; "
                f"replay pending: {pending})")
    setup_s = time.monotonic() - t_start
    say(event="setup", setup_s=setup_s, data_cached=data["cached"],
        data_s=t_data - t_start, attach_s=t_attach - t_data,
        load_s=t_load - t_attach, warm_s=time.monotonic() - t_load,
        warm_passes=warm_passes, gen_data_s=data.get("gen_data_s"),
        transcode_s=data.get("transcode_s"), scale=scale, seed=args.seed, env=env,
        order=names, device=device)

    # -- the window --------------------------------------------------------------
    reduced = None
    if args.trace:
        from benchmark import xplane
        trace_dir = os.path.join(CACHE_DIR, "trace",
                                 f"{args.workload}_{args.seed}")
        shutil.rmtree(trace_dir, ignore_errors=True)

        def traced(name, _pass=0):
            with prog.annotation(name):
                return execute(name)
        with prog.profile(trace_dir):
            win = window.run_passes(names, traced, 0, max_passes=1)
    else:
        win = window.run_passes(names, execute, args.seconds)
    records = win["records"]
    memory_peak = prog.memory_peak_bytes()
    for r in records:
        r["phases"] = prog.phases(r.pop("spans"))
    prog.free()

    if args.trace:
        planes = xplane.read_planes(xplane.find_xplane(trace_dir))
        reduced = xplane.reduce_trace(planes, names)
        say(event="trace", planes=xplane.summary(planes),
            reduced=reduced or {})
        shutil.rmtree(trace_dir, ignore_errors=True)
        if reduced is None and device["platform"] != "cpu":
            die(EXIT_TRACE_UNREADABLE, "benchmark: the trace holds no "
                f"{xplane.OP_LINE!r} line on a device plane, or none of the "
                "statements' annotations: no device metric can be read")

    for r in records:
        say(event="query", path=executed_path(r), **public(r))
    attempted, failed = window.attempted_failed(records)

    # -- correct: every answer of the window against the plain reference -------
    t_ref = time.monotonic()
    reference, ref_cached = reference_answers(config, data, queries)
    verdict = compare.compare_all(records, reference, wanted)
    say(event="reference", seconds=time.monotonic() - t_ref,
        cached=ref_cached, answers=verdict["answers"], rows=verdict["rows"])

    # -- metrics -------------------------------------------------------------------
    device_out = dict(device, memory_peak_bytes=memory_peak)
    run = {"records": records, "queries": queries, "trace": reduced,
           "device": device_out, "load_rows": load_rows, "setup_s": setup_s,
           "peaks": None}
    if args.trace:
        from benchmark import peaks, scanbytes
        if device["platform"] != "cpu":
            run["peaks"] = peaks.peaks(device["kind"])
        stats_cache: dict = {}

        def stats_of(table):
            if table not in stats_cache:
                stats_cache[table] = scanbytes.table_stats(data["parquet"],
                                                           table)
            return stats_cache[table]
        for q in queries.values():
            q["scan_bytes"] = scanbytes.statement_scan_bytes(
                q["scans"], q["sql"], stats_of)
        say(event="scan_bytes",
            per_statement={n: q["scan_bytes"] for n, q in queries.items()})
        listed, section = man.per_layer(args.workload), "metrics"
        if reduced:
            device_out["busy_s"] = reduced["busy_s"]
            device_out["window_s"] = reduced["window_s"]
    else:
        listed, section = man.end_to_end(args.workload), "end_to_end"
    metrics = {}
    for m in listed:
        value = man.reader(m["name"], section)(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": verdict["correct"], "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device_out}
    if reduced:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["passes"] = win["passes"]
    result["compared"] = verdict["compared"]
    for line in compare.report_lines(verdict):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
