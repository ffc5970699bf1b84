# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""A seed's data and query stream, made by children that never see the chip.

The parent (``run.py``) has not imported jax when this runs, and every child
gets ``JAX_PLATFORMS=cpu``: one process at a time, and none of them attaches
the device. The steps are the repository's own drivers, in the README's
order: ``make -C native/ndsgen`` -> ``nds_gen_data.py`` (``--rngseed``) ->
``nds_transcode.py`` (the Load Test) -> ``nds_gen_query_stream.py``
(``--rngseed``). The steps run one after another, except that the transcode
is split over ``TRANSCODE_CHILDREN`` children at once, each given its share
of the tables (``--tables``), with the stream generator beside them: one
transcode child takes 65 s of every new seed's set-up, the tables are
independent, and the stream needs no data.

Cached under ``benchmark/.cache/data/sf<scale>_seed<seed>/`` -- the data
depends on the scale and the seed alone, so the cells of one checkout share
it and a seed that was seen costs no second Load. A ``done.json`` written
last marks a complete entry; anything else there is wiped and made again.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

# the generator's chunk count: fixed, so that a seed gives the same files on
# any host (the files' names and row order follow it)
GEN_PARALLEL = 8


# children the transcode is split over. On the chip's 13-core host one child
# takes 65 s, four 43 s, eight 28 s, twelve 26 s (my chip run, PR 25)
TRANSCODE_CHILDREN = 8


class DataError(Exception):
    pass


def start_child(name: str, cmd: list, root: str, log_dir: str):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"          # a child never takes the chip
    env.pop("NDS_TPU_LEDGER", None)
    os.makedirs(log_dir, exist_ok=True)
    log = open(os.path.join(log_dir, f"{name}.log"), "w")
    return subprocess.Popen(cmd, cwd=root, env=env, stdout=log,
                            stderr=subprocess.STDOUT), log


def run_child(name: str, cmd: list, root: str, log_dir: str) -> float:
    """One child to its end; its output in ``<log_dir>/<name>.log``."""
    t = time.monotonic()
    proc, log = start_child(name, cmd, root, log_dir)
    rc = proc.wait()
    log.close()
    if rc != 0:
        with open(log.name, errors="replace") as f:
            tail = f.read()[-3000:]
        raise DataError(f"{name}: exit {rc}\n--- tail of {log.name} ---\n"
                        f"{tail}")
    return time.monotonic() - t


def transcode(root: str, raw: str, out: str, log_dir: str,
              extra_args=()) -> float:
    """``nds_transcode.py`` over all of ``raw``'s tables, as
    TRANSCODE_CHILDREN children at once: tables dealt out largest first to
    the share that is lightest so far. Returns the wall in seconds."""
    def raw_bytes(table):
        d = os.path.join(raw, table)
        return sum(os.path.getsize(os.path.join(d, f)) for f in os.listdir(d))
    tables = sorted((t for t in os.listdir(raw)
                     if os.path.isdir(os.path.join(raw, t))),
                    key=raw_bytes, reverse=True)
    shares = [[0, []] for _ in range(TRANSCODE_CHILDREN)]
    for t in tables:
        share = min(shares, key=lambda s: s[0])
        share[0] += raw_bytes(t)
        share[1].append(t)
    t0 = time.monotonic()
    children = []
    for i, (_n, names) in enumerate(s for s in shares if s[1]):
        cmd = [sys.executable, os.path.join(root, "nds_transcode.py"), raw,
               out, os.path.join(log_dir, f"load_report_{i}.txt"),
               "--tables"] + names + list(extra_args)
        children.append((f"transcode_{i}",)
                        + start_child(f"transcode_{i}", cmd, root, log_dir))
    failed = []
    for name, proc, log in children:      # wait for every child, then judge
        rc = proc.wait()
        log.close()
        if rc != 0:
            failed.append(f"{name}: exit {rc} (see {log.name})")
    if failed:
        raise DataError("; ".join(failed))
    return time.monotonic() - t0


def ensure(root: str, cache_dir: str, scale: str, seed: int) -> dict:
    """Make (or find) the seed's parquet tables, raw files and stream.
    Returns {"parquet", "raw", "stream", "dir", "cached", "gen_data_s",
    "transcode_s", ...}."""
    entry = os.path.join(cache_dir, "data", f"sf{scale}_seed{seed}")
    done = os.path.join(entry, "done.json")
    if os.path.isfile(done):
        with open(done) as f:
            info = json.load(f)
        info["cached"] = True
        return info
    shutil.rmtree(entry, ignore_errors=True)
    os.makedirs(entry)
    logs = os.path.join(entry, "logs")
    py = sys.executable
    raw = os.path.join(entry, "raw")
    pq = os.path.join(entry, "parquet")
    streams = os.path.join(entry, "streams")
    t_make = run_child(
        "make_ndsgen", ["make", "-C", os.path.join(root, "native", "ndsgen")],
        root, logs)
    t_gen = run_child(
        "gen_data", [py, os.path.join(root, "nds_gen_data.py"), "local",
                     scale, str(GEN_PARALLEL), raw, "--rngseed", str(seed)],
        root, logs)
    t_stream = time.monotonic()
    stream_child, stream_log = start_child(
        "gen_stream", [py, os.path.join(root, "nds_gen_query_stream.py"),
                       "--streams", "1", "--rngseed", str(seed), scale,
                       streams], root, logs)
    try:
        t_load = transcode(root, raw, pq, logs)
    finally:
        rc = stream_child.wait()
        stream_log.close()
    t_stream = time.monotonic() - t_stream
    if rc != 0:
        raise DataError(f"gen_stream: exit {rc} (see {stream_log.name})")
    info = {"dir": entry, "raw": raw, "parquet": pq,
            "stream": os.path.join(streams, "query_0.sql"),
            "scale": scale, "seed": seed,
            "make_s": t_make, "gen_data_s": t_gen, "transcode_s": t_load,
            "gen_stream_s": t_stream}
    with open(done + ".tmp", "w") as f:
        json.dump(info, f)
    os.replace(done + ".tmp", done)
    info["cached"] = False
    return info


def stream_queries(stream_path: str) -> "dict[str, str]":
    """{query name: text between its markers, markers included}, in the
    stream's order. The name is the template's (``query3``); a template that
    holds two statements is not split here -- a mix that wants one brings a
    parser of its own."""
    with open(stream_path) as f:
        text = f.read()
    out = {}
    for block in text.split("-- start")[1:]:
        name = block[block.find("template") + 9: block.find(".tpl")]
        out[name] = "-- start" + block
    return out
