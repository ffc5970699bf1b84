# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""The unattended multi-arm evidence-campaign CLI — ROADMAP item 1 as
one command.

A campaign is a declarative arm matrix (built-in preset or JSON file;
see ``nds_tpu/obs/campaign.py`` for the model): each arm is an env
overlay over bench.py — Pallas segment kernels on/off, prefetch depth,
warm/cold chunk store, 1/2/4/8 stream shards, encoded upload on/off —
run in order into per-arm ledger + trace artifacts under one campaign
directory with a schema-versioned manifest. Kill-proof and rerunnable:
rerunning the same command skips arms whose ledgers carry a clean
terminal record, resumes the partial arm off its own ledger, and
REFUSES (loudly) to resume a ledger recorded under different knobs.
Arm failures are classified via the fault-matrix ``bench-child`` seam
and never abort the remaining arms.

The cross-arm report reuses the existing evidence math end to end —
``tools/bench_compare.py`` for round aggregation/ratios and
``tools/trace_report.py`` for phase/roofline rendering — and keys every
row on the arm name RECORDED in the ledger (bench.py's campaign stamp),
not the file path. Named delta lines answer the deferred questions
directly: pallas-kernel delta (base vs pallas-off), prefetch stall
hidden vs exposed (base vs prefetch-off), warm-vs-cold store, per-shard
ICI GB/s vs the ICI roofline, and static-roofline % / unexplained ms
from the perf_audit cost model.

Usage:
    python tools/campaign.py --preset sf10-full --dry-run   # print the matrix
    python tools/campaign.py --preset sf10-full             # run / resume
    python tools/campaign.py --preset sf10-full --report    # cross-arm table
    python tools/campaign.py --matrix arms.json --dir out/  # custom matrix
    python tools/campaign.py --preset sf10-full --gate BASELINE.jsonl
    python tools/campaign.py --preset sf10-full --audit-ab --audit-perf
    python tools/campaign.py --preset sf10-full --emit-perf
"""

import argparse
import importlib.util
import json
import os
import shlex
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools._ledger_load import campaign_mod  # noqa: E402  (stdlib-only)


def _load_by_path(name, relpath):
    mod = sys.modules.get(name)
    if mod is None:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(REPO, relpath))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return mod


def _bench_compare():
    return _load_by_path("_nds_bench_compare", "tools/bench_compare.py")


def _trace_report():
    return _load_by_path("_nds_trace_report", "tools/trace_report.py")


def _matrix(args):
    C = campaign_mod()
    if args.matrix:
        with open(args.matrix) as f:
            return json.load(f), os.path.basename(args.matrix)
    name = args.preset or "sf10-full"
    if name not in C.PRESETS:
        known = ", ".join(sorted(C.PRESETS))
        raise C.CampaignError(f"unknown preset {name!r} (known: {known})")
    return C.PRESETS[name], name


def dry_run_lines(arms, campaign_dir):
    """The exact matrix the run would execute: per arm, the env overlay
    (sorted k=v; '' marked as unset), the effective fingerprint, and the
    ledger path — what the operator signs off on before burning device
    hours."""
    C = campaign_mod()
    lines = [f"# campaign dry-run: {len(arms)} arms -> {campaign_dir}"]
    for arm in arms:
        overlay = ", ".join(
            f"{k}={'<unset>' if v == '' else v}"
            for k, v in sorted(arm.env.items())) or "(inherit)"
        lines.append(f"arm {arm.name}")
        lines.append(f"  env:         {overlay}")
        lines.append(f"  fingerprint: {C.arm_fingerprint(arm)}")
        lines.append("  ledger:      "
                     + C.arm_paths(campaign_dir, arm.name)["ledger"])
    return lines


# ---------------------------------------------------------------------------
# cross-arm report
# ---------------------------------------------------------------------------


def _arm_rounds(arms, campaign_dir):
    """``[(arm_name, round)]`` for every arm whose ledger loaded with
    measured queries, labeled by RECORDED provenance when present."""
    bc = _bench_compare()
    out = []
    for arm in arms:
        path = campaign_mod().arm_paths(campaign_dir, arm.name)["ledger"]
        if not os.path.exists(path) or os.path.getsize(path) == 0:
            continue
        try:
            r = bc.load_round(path)
        except Exception as exc:
            print(f"# arm {arm.name}: unreadable ledger ({exc}); "
                  "skipped from report", file=sys.stderr)
            continue
        if not r["times"]:
            continue
        out.append((bc.round_label(r, fallback=arm.name), r))
    return out


def _delta(rounds_by, a, b):
    """Geomean ratio b/a over common queries, or None."""
    bc = _bench_compare()
    if a not in rounds_by or b not in rounds_by:
        return None
    cmp = bc.compare(rounds_by[a], rounds_by[b])
    return cmp.get("geomean_ratio"), len(cmp["common"])


def report_lines(arms, campaign_dir, primary):
    """The merged cross-arm report: the bench_compare multi-round table,
    per-arm roofline/stall/static columns off trace_report's collectors,
    and the named mechanism deltas ROADMAP item 1 asks for."""
    bc = _bench_compare()
    tr = _trace_report()
    C = campaign_mod()
    pairs = _arm_rounds(arms, campaign_dir)
    if not pairs:
        return ["# campaign report: no arm has a readable ledger yet"]
    rounds_by = dict(pairs)
    order = [n for n, _ in pairs]
    if primary in rounds_by:                 # primary leads the table
        order.remove(primary)
        order.insert(0, primary)
    lines = bc.format_multi([rounds_by[n] for n in order])
    lines.append("")
    # per-arm evidence columns the pairwise table does not carry:
    # prefetch stall, ICI GB/s vs the ICI roofline, and the static
    # cost-model denominator (roofline % / unexplained ms)
    lines.append("| arm | pf-stall ms | ici GB/s | %ICI roof "
                 "| static-roofline % | unexplained ms |")
    lines.append("|---|---|---|---|---|---|")
    for name in order:
        agg = None
        try:
            agg = tr.collect_from_ledger(rounds_by[name]["path"])
        except Exception as exc:
            print(f"# arm {name}: trace-report columns unavailable "
                  f"({exc})", file=sys.stderr)
        if not agg:
            lines.append(f"| {name} | - | - | - | - | - |")
            continue
        pq = agg["per_query"]
        stall = sum(r["pf_stall"] for r in pq.values())
        ici = sum(r["ici"] for r in pq.values())
        # collective wall = the exchange pass + the reduce inside
        # materialize, same attribution trace_report's table uses
        coll_ms = sum(r["phases"].get("stream.exchange", 0.0)
                      + r["phases"].get("stream.materialize", 0.0)
                      for r in pq.values() if r["ici"] > 0)
        if ici > 0 and coll_ms > 0:
            gbs = ici / 1e9 / (coll_ms / 1e3)
            ici_cell = f"{gbs:.1f}"
            roof_cell = f"{100 * gbs / tr.ROOFLINE_ICI_GBS:.0f}%"
        else:
            ici_cell = roof_cell = "-"
        walls = tr._static_walls(pq)
        if walls:
            explained = sum(walls[q][0] for q in walls)
            measured = sum(pq[q]["total_ms"] for q in walls)
            pct = (f"{100 * explained / measured:.0f}%"
                   if measured > 0 else "-")
            unexp = f"{max(measured - explained, 0.0):.0f}"
        else:
            pct = unexp = "-"
        lines.append(f"| {name} | {stall:.0f} | {ici_cell} | {roof_cell} "
                     f"| {pct} | {unexp} |")
    lines.append("")
    # named mechanism deltas: each line prices ONE landed mechanism as
    # primary-vs-ablation geomean ratio (>1 = the ablated arm is slower,
    # i.e. the mechanism wins)
    named = (("pallas-kernel delta", primary, "pallas-off",
              "pallas kernels ablated"),
             ("prefetch overlap delta", primary, "prefetch-off",
              "prefetch ring ablated (stall exposed)"),
             ("warm-vs-cold store delta", primary, "store-cold",
              "chunk store ablated"),
             ("encoded-upload delta", primary, "encoded-off",
              "encoded wire ablated"))
    for title, a, b, note in named:
        d = _delta(rounds_by, a, b)
        if d and d[0]:
            lines.append(f"# {title}: {b} runs x{d[0]:.3f} vs {a} over "
                         f"{d[1]} common queries ({note})")
    if primary in rounds_by and "prefetch-off" in rounds_by:
        # stall hidden vs exposed: the ring's pf-stall ms is time the
        # driver WAITED with prefetch on; with the ring off that wait
        # is serialized into the wall instead of recorded
        def _stall(n):
            try:
                agg = tr.collect_from_ledger(rounds_by[n]["path"])
            except Exception as exc:
                print(f"# arm {n}: stall column unavailable ({exc})",
                      file=sys.stderr)
                return None
            if not agg:
                return None
            return sum(r["pf_stall"] for r in agg["per_query"].values())
        on, off = _stall(primary), _stall("prefetch-off")
        if on is not None and off is not None:
            lines.append(f"# prefetch stall: {on:.0f} ms recorded-hidden "
                         f"({primary}) vs {off:.0f} ms with the ring off "
                         "(serialized into wall)")
    shard_arms = sorted((n for n in rounds_by if n.startswith("shards-")),
                        key=lambda n: int(n.split("-")[1]))
    for n in shard_arms:
        d = _delta(rounds_by, primary, n)
        if d and d[0]:
            lines.append(f"# shard scaling: {n} runs x{d[0]:.3f} vs "
                         f"{primary} (ici GB/s and %ICI roof per arm in "
                         "the table above)")
    return lines


# ---------------------------------------------------------------------------
# per-arm checks (gate / audits / emit-perf)
# ---------------------------------------------------------------------------


def run_gate(arms, campaign_dir, baseline, threshold):
    """The two-round regression gate, per completed arm vs one
    baseline — bench_compare's own ``main`` so the thresholds, coverage
    rules and output stay identical to CI's."""
    bc = _bench_compare()
    worst = 0
    for name, r in _arm_rounds(arms, campaign_dir):
        print(f"## gate: {name} vs {os.path.basename(baseline)}")
        rc = bc.main([baseline, r["path"], "--gate",
                      "--threshold", str(threshold)])
        worst = max(worst, rc)
    return worst


def run_audits(arms, campaign_dir, ab=False, perf=False):
    """--audit-ab / --audit-perf per arm: record the pinned A/B
    mini-sweep UNDER THE ARM'S ENV (subprocess — the sweep imports jax,
    and each arm needs its own knob set), then cross-validate the
    recorded ledger against the static audits."""
    C = campaign_mod()
    worst = 0
    for arm in arms:
        paths = C.arm_paths(campaign_dir, arm.name)
        os.makedirs(paths["dir"], exist_ok=True)
        ab_path = os.path.join(paths["dir"], "ab.jsonl")
        env = C.arm_env(arm)
        env["NDS_CAMPAIGN_ARM"] = arm.name
        steps = [["--record-ab", ab_path]]
        if ab:
            steps.append(["--audit-ab", ab_path])
        if perf:
            steps.append(["--audit-perf", ab_path])
        for step in steps:
            cmd = [sys.executable,
                   os.path.join(REPO, "tools", "bench_compare.py")] + step
            print(f"## arm {arm.name}: {' '.join(step)}")
            rc = subprocess.call(cmd, env=env)
            if rc != 0:
                print(f"## arm {arm.name}: {step[0]} FAILED (rc {rc})")
                worst = max(worst, rc)
                break
    return worst


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="run, resume and report a multi-arm bench campaign "
        "(see nds_tpu/obs/campaign.py for the arm model)")
    ap.add_argument("--preset", help="built-in arm matrix "
                    "(default sf10-full; see --list-presets)")
    ap.add_argument("--matrix", help="JSON arm-matrix file "
                    "{v, env, arms:[{name, env}]}")
    ap.add_argument("--dir", help="campaign directory (default "
                    ".bench_cache/campaign_<preset> under the repo)")
    ap.add_argument("--dry-run", action="store_true",
                    help="print the exact arm matrix, env overlays, "
                    "fingerprints and artifact paths; run nothing")
    ap.add_argument("--list-presets", action="store_true")
    ap.add_argument("--bench-cmd", help="override the per-arm command "
                    "(default: this python + bench.py); shell-split, "
                    "never shell-interpreted")
    ap.add_argument("--primary", default="base",
                    help="the arm deltas/emit-perf key off (default "
                    "'base', else the first arm)")
    ap.add_argument("--report", action="store_true",
                    help="render the cross-arm report from existing "
                    "arm ledgers; run nothing")
    ap.add_argument("--gate", metavar="BASELINE",
                    help="after the run, gate every completed arm "
                    "against BASELINE (bench_compare --gate, two-round "
                    "contract per arm)")
    ap.add_argument("--threshold", type=float, default=1.10)
    ap.add_argument("--audit-ab", action="store_true",
                    help="record + cross-validate the pinned A/B sweep "
                    "per arm (exec/mem audit bounds)")
    ap.add_argument("--audit-perf", action="store_true",
                    help="cross-validate each arm's A/B ledger against "
                    "the perf_audit static cost model")
    ap.add_argument("--emit-perf", metavar="PATH", nargs="?",
                    const="",
                    help="regenerate the roofline table from the primary "
                    "arm's ledger (default: chiprun_out/BENCH_PERF.md)")
    args = ap.parse_args(argv)
    C = campaign_mod()

    if args.list_presets:
        for name in sorted(C.PRESETS):
            p = C.PRESETS[name]
            print(f"{name}: {len(p['arms'])} arms — {p['description']}")
        return 0

    try:
        matrix, name = _matrix(args)
        campaign_dir = os.path.abspath(
            args.dir or os.path.join(REPO, ".bench_cache",
                                     f"campaign_{name}"))
        arms = C.expand_arms(matrix, campaign_dir)
    except C.CampaignError as exc:
        print(f"campaign: {exc}", file=sys.stderr)
        return 2

    if args.dry_run:
        for ln in dry_run_lines(arms, campaign_dir):
            print(ln)
        return 0

    primary = args.primary if any(a.name == args.primary for a in arms) \
        else arms[0].name

    rc = 0
    if not args.report:
        bench_cmd = shlex.split(args.bench_cmd) if args.bench_cmd else None
        try:
            manifest = C.run_campaign(arms, campaign_dir,
                                      bench_cmd=bench_cmd, preset=name)
        except C.CampaignError as exc:
            print(f"campaign: {exc}", file=sys.stderr)
            return 2
        failed = manifest.get("failedArms", 0)
        print(f"# campaign {name}: "
              f"{manifest.get('completedArms', 0)}/{len(arms)} arms "
              f"complete, {failed} failed -> {campaign_dir}")
        if failed:
            rc = 1

    if args.audit_ab or args.audit_perf:
        rc = max(rc, run_audits(arms, campaign_dir,
                                ab=args.audit_ab, perf=args.audit_perf))

    lines = report_lines(arms, campaign_dir, primary)
    report_path = os.path.join(campaign_dir, "report.md")
    if os.path.isdir(campaign_dir):
        with open(report_path, "w") as f:
            f.write("\n".join(lines) + "\n")
    for ln in lines:
        print(ln)

    if args.gate:
        rc = max(rc, run_gate(arms, campaign_dir, args.gate,
                              args.threshold))

    if args.emit_perf is not None:
        ledger = C.arm_paths(campaign_dir, primary)["ledger"]
        if os.path.exists(ledger):
            bc = _bench_compare()
            bc.emit_perf(bc.load_round(ledger), args.emit_perf)
            print(f"# roofline table regenerated from arm {primary} -> "
                  f"{args.emit_perf or 'chiprun_out/BENCH_PERF.md'}")
        else:
            print(f"# --emit-perf: primary arm {primary} has no ledger "
                  "yet", file=sys.stderr)
            rc = max(rc, 1)
    return rc


if __name__ == "__main__":
    sys.exit(main())
