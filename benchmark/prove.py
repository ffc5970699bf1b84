#!/usr/bin/env python3
# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Run a list of benchmark runs one after another and keep what they print.

    python3 benchmark/prove.py --out chiprun_out/bench \\
        sf1_resident.power_scan_join:101:30:0 sf1_resident.power_scan_join:101:30:1 ...

Each argument is ``<workload>:<seed>:<seconds>:<trace>``. Every run is a child
process (this parent never imports jax, so the child can have the chip); its
standard output and error go to ``<out>/<n>_<workload>_<seed>_t<trace>.out``
/ ``.err``, and one summary line per run is printed: the result line plus the
set-up event's parts and the run's wall. Used to make the sets of runs the
bounds are set from, in one call on the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("runs", nargs="+")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    worst = 0
    for i, spec in enumerate(args.runs):
        workload, seed, seconds, trace = spec.split(":")
        base = os.path.join(args.out, f"{i:02d}_{workload}_{seed}_t{trace}")
        cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload",
               workload, "--seed", seed, "--seconds", seconds, "--trace",
               trace]
        t = time.monotonic()
        with open(base + ".out", "w") as out, open(base + ".err", "w") as err:
            rc = subprocess.run(cmd, stdout=out, stderr=err).returncode
        wall = time.monotonic() - t
        with open(base + ".out") as f:
            lines = [ln for ln in f.read().splitlines() if ln.strip()]
        summary = {"run": spec, "rc": rc, "wall_s": wall}
        for ln in lines:
            try:
                obj = json.loads(ln)
            except ValueError:
                continue
            if obj.get("event") in ("setup", "warm_pass", "reference"):
                summary.setdefault(obj["event"], []).append(
                    {k: v for k, v in obj.items() if k != "event"})
        try:
            summary["result"] = json.loads(lines[-1]) if lines else None
        except ValueError:
            summary["result"] = lines[-1][:500]
        if rc != 0:
            with open(base + ".err", errors="replace") as f:
                summary["stderr_tail"] = f.read()[-1500:]
            worst = worst or rc
        print(json.dumps(summary), flush=True)
    return worst


if __name__ == "__main__":
    sys.exit(main())
