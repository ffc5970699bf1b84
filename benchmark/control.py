#!/usr/bin/env python3
# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Read the comparison's numbers for the control, over several seeds in one
process.

    python3 benchmark/control.py --workload <cell> --seeds 5,6,7 --seconds 10

The control is the program's own lower-precision arm (the configuration's
``control`` block: ``nds_transcode.py --floats`` and ``use_decimal=false``:
monetary columns stored as doubles instead of exact decimals, and on the chip
summed in single precision by the MXU segment-sum kernel), put in the
program's place at the cell's own size: same tables, same statements, same
timed entry, a short window. It has to come out NOT correct: this script
exits 0 when every seed failed the comparison, and 1 otherwise. One line per
seed gives each number compared beside its limit. The benchmark's own runs
never run this; the program's sound readings come from ``run.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare, datagen, manifest, window  # noqa: E402
from benchmark import run as bench_run  # noqa: E402


def control_tables(data: dict, control: dict) -> str:
    """The seed's raw files transcoded by the control's arguments, beside
    the exact tables."""
    out = os.path.join(data["dir"], "parquet_control")
    done = os.path.join(out, "done.json")
    if not os.path.isfile(done):
        datagen.transcode(ROOT, data["raw"], out,
                          os.path.join(data["dir"], "logs_control"),
                          control["transcode_args"])
        with open(done, "w") as f:
            json.dump({"args": control["transcode_args"]}, f)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    type=lambda s: [int(x) for x in s.split(",")])
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    man = manifest.Manifest(ROOT)
    cell = man.cell(args.workload)
    config = man.config(cell["config"])
    traffic = man.traffic(cell["traffic"])
    shape = config["rehearsal"] if args.rehearse else config
    scale = str(shape["scale_factor"])
    control = config["control"]

    seeds = []
    for seed in args.seeds:             # all children first: none has the chip
        data = datagen.ensure(ROOT, bench_run.CACHE_DIR, scale, seed)
        seeds.append((seed, data, control_tables(data, control)))

    bench_run.state_env(shape.get("env", {}))
    from benchmark import program as program_mod
    prog = program_mod.Program(ROOT, cell["chips"], allow_cpu=args.rehearse)
    verdicts = []
    for seed, data, tables in seeds:
        names, queries, wanted = bench_run.cell_queries(data["stream"],
                                                        traffic)
        prog.load(tables, bool(control["use_decimal"]))
        execute = bench_run.timed_call(prog, config, queries)
        window.run_passes(names, execute, 0, max_passes=1)       # warm-up
        win = window.run_passes(names, execute, args.seconds)
        prog.free()
        reference, _ = bench_run.reference_answers(config, data, queries)
        verdict = compare.compare_all(win["records"], reference, wanted)
        attempted, failed = window.attempted_failed(win["records"])
        print(json.dumps({"seed": seed, "scale": scale,
                          "device": prog.device_info(),
                          "correct": verdict["correct"],
                          "attempted": attempted, "failed": failed,
                          "answers": verdict["answers"],
                          "worst": verdict["worst"],
                          "compared": verdict["compared"]}), flush=True)
        verdicts.append(verdict["correct"])
    return 0 if not any(verdicts) else 1


if __name__ == "__main__":
    sys.exit(main())
