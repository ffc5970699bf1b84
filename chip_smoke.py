#!/usr/bin/env python3
# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Chip smoke: Load and Power at scale factor 1 on one TPU, through the
normal drivers, checked against the plain CPU arm.

    python chip_smoke.py              # one chip; exit 0 only if every phase passed
    python chip_smoke.py --rehearse   # CPU, SF0.01: the same script, no contract line
    python chip_smoke.py --chips 4    # the sharded streamed path alone, on four chips

What it drives, in the README's "Running" order, each step a child process
and one at a time: ``make -B -C native/ndsgen`` -> ``nds_gen_data.py`` ->
``nds_transcode.py`` (Load Test, ``Tld``) -> ``nds_gen_query_stream.py`` ->
``nds_power.py`` on the chip in two phases, each run twice (a cold pass and a
second pass in a new process, so the compile cache shows; ``--chips 4`` makes
one pass per arm) -> the same stream
through ``nds_power.py --device cpu`` (plain XLA, eager, no replay, no
Pallas: the reference) -> ``nds_validate.py`` on the output folders.

  resident  defaults: every table on the device
  streamed  the same data with NDS_TPU_STREAM_BYTES / NDS_TPU_STREAM_CHUNK_ROWS
            lowered so that store_sales, catalog_sales and web_sales are
            host-resident ChunkedTables of >= 4 chunks, under
            NDS_TPU_STREAM_STRICT=1

This process never imports jax or nds_tpu: a parent that has touched JAX
holds the chip and its children could not have it. The device line comes from
a short-lived probe child, run FIRST so that a missing chip costs seconds,
and again from the ledger ``meta`` record the Power child itself wrote.

Every earlier line of output is one JSON object; times in them are smoke
timings on a shared host, not benchmark numbers. On success the last line is
exactly ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
On any failure the script says what failed and exits non-zero without it.
"""

import argparse
import glob
import json
import os
import shutil
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 7
# one or two queries of each class ROADMAP.md Queue 1 item 1 names: star
# scan (q3, q42), fan-out join (q25), outer join (q93), window (q57),
# three-channel union (q56), count-only (q96). All seven passed on a v5e
# (PR 22), but a cold run of them takes ~2700 s there, 2230 s of it XLA
# compile (q57 608 s, q56 395 s, q25 131 + 299 s), and the smoke has to
# end within 1200 s without counting on a compile cache. So by default
# the three heaviest compiles are DROPPED (queries before scale) and the
# four that remain take ~750 s cold; --full runs all seven.
FULL_QUERIES = ["query3", "query42", "query25", "query93", "query57",
                "query56", "query96"]
DROPPED = ["query25", "query57", "query56"]
QUERIES = [q for q in FULL_QUERIES if q not in DROPPED]
# --chips 4: one star scan, one fan-out join that takes the hash exchange
SHARD_QUERIES = ["query3", "query25"]
FACTS = ("store_sales", "catalog_sales", "web_sales")
# scale -> (NDS_TPU_STREAM_BYTES, NDS_TPU_STREAM_CHUNK_ROWS): the documented
# operator knobs, lowered so the three sales facts stream in >= 4 chunks
# (defaults 8 GiB / 4 Mi rows never stream at these scales). SF1 arrow
# bytes: web_sales 233 MB, catalog_sales 467 MB, store_sales 711 MB; the
# largest dimension (customer_demographics, 137 MB) stays on the device.
STREAM_KNOBS = {"1": (200_000_000, 131072), "0.01": (2_000_000, 1024)}
# --chips 4: the capacity the streamed executor's admission arithmetic is
# told (NDS_TPU_HBM_BYTES), measured on virtual devices to give P=4 on q25
SHARD_HBM_MODEL_BYTES = 2 << 30
NOTE = "smoke timing on a shared host, not a benchmark number"
PROBE = ("import json, jax; d = jax.devices(); "
         "print(json.dumps({'platform': d[0].platform, "
         "'kind': d[0].device_kind, 'count': len(d), "
         "'local': jax.local_device_count()}))")
DRIVERS = ("nds_gen_data.py", "nds_transcode.py", "nds_gen_query_stream.py",
           "nds_power.py", "nds_validate.py", "native/ndsgen/ndsgen.cc")


class SmokeFailure(Exception):
    pass


def say(**fields):
    print(json.dumps(fields), flush=True)


class Smoke:
    def __init__(self, args):
        self.args = args
        self.rehearse = args.rehearse
        self.scale = "0.01" if args.rehearse else "1"
        self.queries = args.queries or (
            SHARD_QUERIES if args.chips == 4
            else FULL_QUERIES if args.full else QUERIES)
        self.work = os.path.abspath(args.workdir)
        self.out = os.path.abspath(args.out)
        self.t0 = time.monotonic()
        self.failures = []
        self.device = None

    # -- children ----------------------------------------------------------

    def left(self):
        return self.args.deadline - (time.monotonic() - self.t0)

    def child_env(self, platform, extra=None):
        """Environment of one child. ``platform``: 'chip' leaves
        JAX_PLATFORMS as the caller set it (unset or tpu on the chip; the
        drivers pin tpu themselves when it is unset) or cpu under
        --rehearse; 'cpu' pins the host for work that never needs the
        chip. Engine knobs a caller may have exported are dropped so each
        phase states its own."""
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("NDS_TPU_STREAM_", "NDS_TPU_REPLAY",
                                    "NDS_TPU_LEDGER", "NDS_TPU_HBM_BYTES"))}
        if platform == "cpu" or self.rehearse:
            env["JAX_PLATFORMS"] = "cpu"
        if self.rehearse and self.args.chips == 4:
            # the guide's second rehearsal: four virtual CPU devices
            env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env.update(extra or {})
        return env

    def run(self, name, cmd, env, cap_s):
        """Run one child to its end with output in ``<out>/<name>.log``;
        kill its whole process group at the time limit."""
        timeout = min(cap_s, self.left())
        if timeout <= 0:
            raise SmokeFailure(f"{name}: no time left before the "
                               f"{self.args.deadline:.0f} s deadline")
        log = os.path.join(self.out, f"{name}.log")
        t = time.monotonic()
        with open(log, "w") as f:
            proc = subprocess.Popen(cmd, cwd=HERE, env=env, stdout=f,
                                    stderr=subprocess.STDOUT,
                                    start_new_session=True)
            try:
                rc = proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                rc = f"killed at its {timeout:.0f} s limit"
            finally:
                if proc.poll() is None:     # time limit, SIGTERM, ^C
                    os.killpg(proc.pid, signal.SIGKILL)
                    proc.wait()
        wall = time.monotonic() - t
        if rc != 0:
            with open(log, errors="replace") as f:
                tail = f.read()[-3000:]
            print(f"--- tail of {log} ---\n{tail}\n--- end ---", flush=True)
            raise SmokeFailure(f"{name}: exit {rc} after {wall:.0f} s")
        return wall

    def py(self, name, script, argv, env, cap_s):
        return self.run(name, [sys.executable, os.path.join(HERE, script)]
                        + argv, env, cap_s)

    # -- steps ---------------------------------------------------------------

    def probe(self):
        """The device, asked by a child that exits at once."""
        env = self.child_env("chip")
        try:
            got = subprocess.run([sys.executable, "-c", PROBE], env=env,
                                 capture_output=True, text=True,
                                 timeout=min(180, max(self.left(), 1)))
        except subprocess.TimeoutExpired:
            raise SmokeFailure("device probe: no answer in 180 s")
        if got.returncode:
            raise SmokeFailure("device probe failed: "
                               + got.stderr.strip()[-800:])
        dev = json.loads(got.stdout.strip().splitlines()[-1])
        say(event="device", **dev)
        if not self.rehearse and dev["platform"] != "tpu":
            raise SmokeFailure(
                f"JAX found platform {dev['platform']!r}, not a TPU "
                f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})")
        if self.args.chips == 4 and dev["local"] != 4:
            raise SmokeFailure(f"--chips 4 needs four local devices, JAX "
                               f"found {dev['local']}")
        self.device = {"platform": dev["platform"], "kind": dev["kind"],
                       "count": dev["count"]}

    def load(self):
        """Build the generator, generate raw data, transcode (Load Test),
        generate the query stream. Everything from SEED; nothing cached."""
        cpu = self.child_env("cpu")
        self.run("make_ndsgen",
                 ["make", "-B", "-C", os.path.join(HERE, "native", "ndsgen")],
                 cpu, 300)
        parallel = 2 if self.rehearse else max(2, min(8, os.cpu_count() or 2))
        raw = os.path.join(self.work, "raw")
        self.pq = os.path.join(self.work, "parquet")
        t_gen = self.py("gen_data", "nds_gen_data.py",
                        ["local", self.scale, str(parallel), raw,
                         "--rngseed", str(SEED)], cpu, 600)
        report = os.path.join(self.out, "load_report.txt")
        self.py("transcode", "nds_transcode.py", [raw, self.pq, report],
                cpu, 900)
        with open(report) as f:
            tld = [float(ln.split(":")[1]) for ln in f
                   if ln.startswith("Load Test Time")][0]
        shutil.rmtree(raw, ignore_errors=True)
        streams = os.path.join(self.work, "streams")
        self.py("gen_stream", "nds_gen_query_stream.py",
                ["--streams", "1", "--rngseed", str(SEED), self.scale,
                 streams], cpu, 120)
        self.stream = os.path.join(streams, "query_0.sql")
        say(event="load", scale=self.scale, seed=SEED,
            gen_data_s=round(t_gen, 1), Tld_s=round(tld, 1), note=NOTE)

    def power(self, name, platform, extra_env=None, outputs=False,
              queries=None):
        """One nds_power.py process over the smoke's queries; returns
        {query: summary} and the ledger's meta record."""
        js = os.path.join(self.out, name, "json")
        shutil.rmtree(os.path.join(self.out, name), ignore_errors=True)
        os.makedirs(os.path.dirname(js))
        ledger = os.path.join(self.out, name, "ledger.jsonl")
        argv = [self.pq, self.stream, os.path.join(self.out, name, "time.csv"),
                "--sub_queries", ",".join(queries or self.queries),
                "--json_summary_folder", js, "--ledger", ledger,
                "--trace-dir", os.path.join(self.out, name, "traces")]
        if outputs:
            argv += ["--output_prefix", os.path.join(self.work, "out", name)]
        if platform == "cpu":
            argv += ["--device", "cpu"]
        wall = self.py(name, "nds_power.py", argv,
                       self.child_env(platform, extra_env), 1500)
        summaries = {}
        for path in glob.glob(os.path.join(js, "*.json")):
            with open(path) as f:
                s = json.load(f)
            summaries[s["query"]] = s
        with open(ledger) as f:
            records = [json.loads(ln) for ln in f if ln.strip()]
        meta = next(r for r in records if r["kind"] == "meta")
        end = records[-1]
        if end["kind"] != "end" or end.get("status") != "completed":
            self.fail(f"{name}: ledger does not end in a completed `end` "
                      f"record: {end}")
        ran_on = {"platform": meta.get("platform"),
                  "kind": meta.get("device_kind"),
                  "count": meta.get("device_count")}
        if platform == "chip" and ran_on != self.device:
            self.fail(f"{name}: the Power process ran on {ran_on}, the "
                      f"probe found {self.device}")
        return summaries, meta, wall

    def fail(self, what):
        print(f"FAILED: {what}", flush=True)
        self.failures.append(what)

    @staticmethod
    def executed_path(summary):
        scans = summary.get("streamedScans")
        if scans:
            if all(s["path"] == "compiled" for s in scans):
                return "compiled stream"
            return "eager stream"
        phases = (summary.get("trace") or {}).get("phases", {})
        if any(p.startswith("replay.") for p in phases):
            return "replay"
        return "eager"

    def chip_phase(self, phase, extra_env=None, strict_stream=False,
                   want_shards=None, two_pass=True):
        """Cold pass then second pass (two processes) of one phase on the
        chip, the per-query checks, and the per-query lines. With
        ``two_pass`` off there is one pass, reported as the second."""
        try:
            cold, cold_wall = None, None
            if two_pass:
                cold, _meta, cold_wall = self.power(f"{phase}_cold", "chip",
                                                    extra_env)
            warm, meta, warm_wall = self.power(phase, "chip", extra_env,
                                               outputs=True)
        except SmokeFailure as e:
            self.fail(str(e))
            return None
        all_scans = []
        say(event="phase", phase=phase, env=extra_env or {},
            platform=meta.get("platform"), device_kind=meta.get("device_kind"),
            device_count=meta.get("device_count"),
            segment_kernels=meta.get("pallas"),
            hbm_model_bytes=meta.get("hbm_model_bytes"),
            hbm_limit_bytes=meta.get("hbm_limit_bytes"),
            cold_process_s=cold_wall and round(cold_wall, 1),
            second_process_s=round(warm_wall, 1), note=NOTE)
        for q in self.queries:
            c, w = (cold or warm).get(q), warm.get(q)
            if c is None or w is None:
                self.fail(f"{phase}/{q}: no JSON summary")
                continue
            for label, s in (("cold", c), ("second", w)):
                if s["queryStatus"] != ["Completed"]:
                    self.fail(f"{phase}/{q} ({label} pass): queryStatus "
                              f"{s['queryStatus']} {s.get('exceptions')}")
            scans = w.get("streamedScans", [])
            all_scans += scans
            if strict_stream:
                if not scans:
                    self.fail(f"{phase}/{q}: no streamed scan ran")
                for s in scans:
                    if s["path"] != "compiled" or s.get("reason"):
                        self.fail(f"{phase}/{q}: scan of {s['table']} took "
                                  f"path={s['path']} reason="
                                  f"{s.get('reason')!r}")
                    if s["table"] in FACTS and s["chunks"] < 4:
                        self.fail(f"{phase}/{q}: {s['table']} streamed in "
                                  f"{s['chunks']} chunks, fewer than 4")
                    if want_shards and (
                            s.get("shards") != want_shards
                            or not s.get("collectives", 0) > 0):
                        self.fail(f"{phase}/{q}: scan of {s['table']} ran "
                                  f"shards={s.get('shards', 1)} collectives="
                                  f"{s.get('collectives')}, wanted "
                                  f"shards={want_shards} with collectives")
            say(event="query", phase=phase, query=q,
                status=w["queryStatus"][0],
                cold_ms=cold and c["queryTimes"][0],
                second_ms=w["queryTimes"][0],
                cold_compile_ms=cold and c.get("compileMs"),
                second_compile_ms=w.get("compileMs"),
                hostSyncs=w.get("hostSyncs"), path=self.executed_path(w),
                peak_bytes_in_use=w.get("peakHbmCumulativeBytes"),
                scans=[{k: s[k] for k in ("table", "chunks", "path",
                                          "partitions", "shards",
                                          "collectives", "bytesIci",
                                          "bytesH2d", "reason")
                        if k in s} for s in scans],
                note=NOTE)
        return all_scans

    def validate(self, phase, against="cpu"):
        """nds_validate.py on two output folders; patches
        queryValidationStatus into the phase's summaries."""
        argv = [os.path.join(self.work, "out", against),
                os.path.join(self.work, "out", phase), self.stream,
                "--ignore_ordering", "--sub_queries", ",".join(self.queries),
                "--json_summary_folder", os.path.join(self.out, phase, "json")]
        try:
            self.py(f"validate_{phase}", "nds_validate.py", argv,
                    self.child_env("cpu"), 600)
        except SmokeFailure as e:
            self.fail(f"{phase} does not validate against {against}: {e}")
            return
        say(event="validate", phase=phase, against=against,
            queries=self.queries, result="Pass")

    def stream_env(self):
        nbytes, rows = STREAM_KNOBS[self.scale]
        say(event="threshold", note="streaming threshold LOWERED from the "
            "defaults (8 GiB, 4 Mi rows) so the sales facts stream at this "
            "scale", NDS_TPU_STREAM_BYTES=nbytes,
            NDS_TPU_STREAM_CHUNK_ROWS=rows, NDS_TPU_STREAM_STRICT=1)
        return {"NDS_TPU_STREAM_BYTES": str(nbytes),
                "NDS_TPU_STREAM_CHUNK_ROWS": str(rows),
                "NDS_TPU_STREAM_STRICT": "1"}

    def cpu_reference(self):
        try:
            ref, _meta, wall = self.power(
                "cpu", "cpu", {"NDS_TPU_PALLAS": "off",
                               "NDS_TPU_REPLAY": "off"}, outputs=True)
        except SmokeFailure as e:
            self.fail(str(e))
            return False
        for q in self.queries:
            if ref.get(q, {}).get("queryStatus") != ["Completed"]:
                self.fail(f"cpu/{q}: queryStatus "
                          f"{ref.get(q, {}).get('queryStatus')}")
        say(event="reference", arm="nds_power.py --device cpu (XLA:CPU, "
            "eager, no replay, no Pallas)", process_s=round(wall, 1))
        return True

    # -- the two modes -------------------------------------------------------

    def one_chip(self):
        self.chip_phase("resident")
        self.chip_phase("streamed", self.stream_env(), strict_stream=True)
        if self.cpu_reference():
            self.validate("resident")
            self.validate("streamed")

    def four_chips(self):
        """The sharded streamed path and what it is compared with, and no
        other phase: the streamed phase under NDS_TPU_STREAM_SHARDS=4, the
        same queries single-device, outputs compared bit for bit."""
        env = self.stream_env()
        if not self.rehearse:
            # at SF1 every survivor bound fits 16 GiB, so nothing would
            # partition and the sharded join would need no exchange: tell
            # the admission arithmetic 2 GiB (both arms), and the proof
            # partitions the fan-out join, whose keys are then
            # hash-exchanged across the shards
            env["NDS_TPU_HBM_BYTES"] = str(SHARD_HBM_MODEL_BYTES)
            say(event="capacity", NDS_TPU_HBM_BYTES=SHARD_HBM_MODEL_BYTES,
                note="capacity MODEL lowered from its 16 GiB default so "
                "that the fan-out join partitions and takes the hash "
                "exchange; the device's memory is what it is")
        self.chip_phase("streamed_1", env, strict_stream=True,
                        two_pass=False)
        scans = self.chip_phase(
            "streamed_4", {**env, "NDS_TPU_STREAM_SHARDS": "4"},
            strict_stream=True, want_shards=4, two_pass=False)
        if not self.rehearse and "query25" in self.queries and not any(
                s.get("partitions", 1) > 1 and s["collectives"] > s["chunks"]
                for s in scans or ()):
            self.fail("streamed_4: no scan took the per-chunk hash "
                      "exchange (partitions > 1, collectives > chunks)")
        a, b = (os.path.join(self.work, "out", p)
                for p in ("streamed_1", "streamed_4"))
        diff = subprocess.run(["diff", "-r", a, b], capture_output=True,
                              text=True)
        if diff.returncode:
            self.fail("sharded output is not bit for bit the single-device "
                      "output:\n" + (diff.stdout + diff.stderr)[-1500:])
        else:
            say(event="compare", a="streamed_1", b="streamed_4",
                result="bit for bit identical", queries=self.queries)

    def main(self):
        missing = [p for p in DRIVERS
                   if not os.path.exists(os.path.join(HERE, p))]
        if missing:
            raise SmokeFailure(f"not a checkout of nds-tpu: {missing} "
                               f"missing beside {__file__}")
        shutil.rmtree(self.work, ignore_errors=True)
        os.makedirs(self.work)
        os.makedirs(self.out, exist_ok=True)
        say(event="start", mode="rehearse" if self.rehearse else "chip",
            chips=self.args.chips or 1, scale=self.scale, seed=SEED,
            queries=self.queries, out=self.out)
        if self.queries == QUERIES:
            say(event="cut", dropped=DROPPED, reason="their cold XLA "
                "compile alone (131-608 s each on a v5e) does not fit the "
                "1200 s limit; queries are dropped before scale; --full "
                "runs all seven (~2700 s cold)")
        self.probe()
        self.load()
        if self.args.chips == 4:
            self.four_chips()
        else:
            self.one_chip()
        if self.failures:
            raise SmokeFailure(f"{len(self.failures)} check(s) failed: "
                               + "; ".join(self.failures))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU at SF0.01: relaxes only the "
                    "platform == tpu check and the scale, and never prints "
                    "the final contract line")
    ap.add_argument("--chips", type=int, choices=[1, 4], default=None,
                    help="4: run only the sharded streamed phase and its "
                    "single-device twin (needs four local devices)")
    ap.add_argument("--full", action="store_true",
                    help="all seven queries, one or two per class: ~2700 s "
                    "cold on a v5e, so give --deadline as well")
    ap.add_argument("--queries", type=lambda s: s.split(","),
                    help="comma separated override of the query list")
    ap.add_argument("--workdir", default=os.path.join(HERE, ".chip_smoke_work"),
                    help="scratch for data and outputs; emptied at start, "
                    "removed at the end")
    ap.add_argument("--out", default=os.path.join(HERE, "chiprun_out",
                                                  "chip_smoke"),
                    help="logs, ledgers and JSON summaries of every child")
    ap.add_argument("--deadline", type=float, default=1150.0,
                    help="seconds after which no further child is started "
                    "and a running one is killed")
    ap.add_argument("--keep", action="store_true",
                    help="leave the work directory in place")
    args = ap.parse_args()
    smoke = Smoke(args)
    # die through the finally blocks, so no child outlives the script
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        smoke.main()
    except SmokeFailure as e:
        print(f"chip_smoke FAILED after {time.monotonic() - smoke.t0:.0f} s: "
              f"{e}", flush=True)
        return 1
    finally:
        if not args.keep:
            shutil.rmtree(smoke.work, ignore_errors=True)
    say(event="done", wall_s=round(time.monotonic() - smoke.t0, 1), note=NOTE)
    if args.rehearse:
        print("rehearsal passed; the contract line is printed only on a TPU",
              flush=True)
        return 0
    print(json.dumps({"ok": True, "device": smoke.device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
