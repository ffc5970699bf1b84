# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Price the two arms of ``ops.compact_indices`` by input bucket.

    python tools/compact_sweep.py [--log2 16,18,20,22,24] [--keep 0.1] [--reps 7]

For each input bucket (2**k rows) a mask keeps the given share of rows, and
each arm builds the indices of the kept rows at the bucket of their count:
``jnp.nonzero`` called eagerly (the form a stream-bounds region keeps) and
the sort arm (``ops._compact_sorted``). ``compile_s`` is the first call's wall less the
median warm call, with JAX's persistent compilation cache turned off in this
process, so every program compiles; ``run_ms`` is the median of ``--reps``
warm calls, each ending in ``block_until_ready``. The two arms' indices
must be equal at every bucket. One JSON line a bucket and arm, the
device named in each. Needs an accelerator; ``--cpu`` runs it on the CPU to
rehearse, and its times are then no device times.
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _timed(fn, mask, reps: int) -> tuple:
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(mask))
    first = time.perf_counter() - t0
    runs = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(mask))
        runs.append(time.perf_counter() - t0)
    run = statistics.median(runs)
    return out, max(first - run, 0.0), run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--log2", default="16,17,18,19,20,21,22,23,24")
    ap.add_argument("--keep", type=float, default=0.1)
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from nds_tpu.engine import ops as E
    jax.config.update("jax_enable_compilation_cache", False)
    dev = jax.devices()[0]
    if dev.platform == "cpu" and not args.cpu:
        print("compact_sweep: no accelerator (pass --cpu to rehearse)",
              file=sys.stderr)
        return 3
    for k in (int(x) for x in args.log2.split(",")):
        plen = 1 << k
        rng = np.random.default_rng(k)
        host = rng.random(plen) < args.keep
        mask = jax.device_put(jnp.asarray(host), dev)
        cap = E.bucket_len(int(host.sum()))
        arms = {
            "nonzero": lambda m: jnp.nonzero(
                m, size=cap, fill_value=plen)[0],
            "sort": lambda m: E._compact_sorted(m, cap),
        }
        outs = {}
        for arm, fn in arms.items():
            out, compile_s, run = _timed(fn, mask, args.reps)
            outs[arm] = np.asarray(out)
            print(json.dumps({
                "arm": arm, "plen": plen, "cap": cap, "kept": int(host.sum()),
                "run_ms": round(run * 1e3, 3),
                "ns_per_row": round(run * 1e9 / plen, 2),
                "compile_s": round(compile_s, 2),
                "device": dev.device_kind, "platform": dev.platform}),
                flush=True)
        if not np.array_equal(outs["nonzero"], outs["sort"]):
            print(f"compact_sweep: the arms differ at 2**{k}", file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
