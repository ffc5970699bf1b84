# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Differential validation of the static cost auditor (exactness).

The perf auditor (``nds_tpu/analysis/perf_audit.py``) prices every
statement's data movement — h2d upload bytes, ICI wire bytes — from
the same planner decomposition the exec/mem audits walk. Unlike the bound-shaped audits, its headline predictions
claim EQUALITY: the compiled chunk pipeline pads every chunk to one
capacity and always ships a validity byte per column, so
``bytes_h2d = chunks x chunk_cap x sum(width + 1)`` is a closed form,
and the sharded collectives move trace-accounted aval bytes the model
reproduces arithmetically. A cost model that silently drifts from the
engine turns every roofline number in ``tools/trace_report.py`` and
every campaign denominator into fiction — so the model is differentially
checked, mirroring ``tools/mem_audit_diff.py``:

* replay the ``tests/test_synccount.py`` A/B templates through the real
  engine on the chunked toy session, cold and warm, under the forced
  partition count;
* build the static predictions from a :class:`PerfAuditor` whose
  :class:`MemModel` carries the toy session's REAL row counts and chunk
  geometry, and whose ``wire_cols`` override carries the REAL per-column
  wire widths (:func:`perf_audit.wire_column_widths` on the live arrow
  data — the same codec plan the runtime caches);
* fail when measured ``StreamEvent.bytes_h2d`` differs from the
  prediction (sorted multiset comparison per statement, so a multi-scan
  statement — the ab12 scalar-subquery chain prices TWO store_sales
  pipelines, both at the statement-level pruning — compares order-free),
  when the
  warm sight differs from the cold (the chunk store caches the encoding,
  not the buffers: re-upload must be byte-identical), or when a
  predicted compiled scan produced no byte evidence at all.

Two mini-sweeps extend the check to the other arms:

* **sharded** (``_STREAM_AB_SHARDED`` on a forced 2-shard mesh):
  measured ``StreamEvent.bytes_ici`` must EQUAL the model's
  exchange+reduce byte arithmetic for ici-exact scans and dominate it
  (lower bound) where outer-build bitmap psums ride the reduce;
* **encoded-off** (``NDS_TPU_ENCODED=0``): the same h2d equality at
  plain widths — the arm that catches a width table hard-coded to the
  encoded path.

``--inject-drift`` zeroes every predicted byte total
before comparing: a fixture that MUST fail in the h2d and ICI
directions (``tests/test_analysis.py`` asserts both directions). Run
after any change to ``engine/table.py`` chunk shapes,
``io/columnar.py`` codec selection, ``parallel/exchange.py`` collective
accounting, ``engine/stream.py`` upload/exchange paths, or the
mem-model width tables: the cost model and the engine are kept in
lockstep the same way the other four auditors track their subsystems.
"""

import argparse
import importlib.util
import os
import sys
from contextlib import contextmanager

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the sharded sweep needs a multi-device mesh: force the virtual CPU
# devices BEFORE jax initializes (no-op when the caller already did)
if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

# the encoded-off re-check subset: a plain scan, a join, the partitioned
# fan-out and the two-pipeline scalar-subquery chain — the shapes whose
# width accounting differs most between the encoded and plain paths
_ENCODED_OFF_SUBSET = (0, 2, 7, 11)


def _load_ab_module():
    path = os.path.join(REPO, "tests", "test_synccount.py")
    spec = importlib.util.spec_from_file_location("_synccount_fixtures_pf",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@contextmanager
def _encoded_off():
    """Force the unencoded upload path (NDS_TPU_ENCODED=0) for one arm."""
    old = os.environ.get("NDS_TPU_ENCODED")
    os.environ["NDS_TPU_ENCODED"] = "0"
    try:
        yield
    finally:
        if old is None:
            os.environ.pop("NDS_TPU_ENCODED", None)
        else:
            os.environ["NDS_TPU_ENCODED"] = old


def _session_params(session):
    """(row bounds, chunk_rows) off the live toy session — the
    cardinality + chunk geometry a live audit would read off the
    catalog (the toy passes chunk_rows to ChunkedTable directly, NOT
    via env, so the model must take it from the table)."""
    bounds = {}
    chunk_rows = None
    for name, t in session.catalog.items():
        bounds[name.lower()] = int(t.nrows) if isinstance(t.nrows, int) \
            else int(t.arrow.num_rows)
        if name.lower() == "store_sales":
            chunk_rows = getattr(t, "chunk_rows", None)
    return bounds, chunk_rows


def _wire_cols(session):
    """The streamed table's REAL wire widths under the CURRENT env —
    computed from the live arrow data with the same codec plan the
    runtime caches, which is what makes the h2d prediction an equality
    instead of a bound."""
    from nds_tpu.analysis.perf_audit import wire_column_widths
    return {"store_sales":
            wire_column_widths(session.catalog["store_sales"])}


def predict(queries, bounds, chunk_rows, wire):
    """PerfReports under the CALLER's env (run inside the same forced
    contexts as the evidence sweep, so the model's partition/shard/
    codec choices and the runtime's agree by construction)."""
    from nds_tpu.analysis.mem_audit import MemModel
    from nds_tpu.analysis.perf_audit import PerfAuditor
    model = MemModel(row_bounds=bounds, chunk_rows=chunk_rows)
    auditor = PerfAuditor(streamed={"store_sales"}, model=model,
                          wire_cols=wire)
    return [auditor.audit_sql(sql, query=f"ab{i + 1}")
            for i, (sql, _must) in enumerate(queries)]


def _run_sweep(mod, session, indices):
    """Cold+warm evidence per template: the byte fields of every
    compiled StreamEvent."""
    from nds_tpu.listener import drain_stream_events
    queries = mod._STREAM_AB_QUERIES
    drain_stream_events()
    out = []
    for i in indices:
        sql, _must = queries[i]
        runs = {}
        for sight in ("cold", "warm"):
            session.sql(sql).collect()
            events = drain_stream_events()
            comp = [e for e in events if e.path == "compiled"]
            runs[sight] = {
                "h2d": [e.bytes_h2d for e in comp if e.bytes_h2d >= 0],
                "ici": [e.bytes_ici for e in comp if e.bytes_ici >= 0],
                "chunks": [e.chunks for e in comp],
                "n_compiled": len(comp),
            }
        out.append({"idx": i, "sql": sql, **runs})
    return out


def _check_h2d(rep, ev, inject, problems):
    """The headline equality: measured upload bytes == prediction, per
    compiled scan (sorted multisets: event order vs scan-walk order is
    not part of the contract), identical cold and warm."""
    preds = sorted(((c.bytes_h2d, c.bytes_h2d_min, c.h2d_exact)
                    for c in rep.scans if c.compiled), reverse=True)
    if inject:
        preds = [(0, 0, True) for _ in preds]
    for sight in ("cold", "warm"):
        got = sorted(ev[sight]["h2d"], reverse=True)
        if not inject and len(got) != len(preds):
            problems.append(
                f"{sight} reported {len(got)} compiled byte events, the "
                f"model priced {len(preds)} compiled scans (model drift)")
            continue
        for (pred, pmin, exact), g in zip(preds, got):
            if exact and g != pred:
                problems.append(
                    f"{sight} uploaded {g} bytes, static prediction "
                    f"{pred} (EXACTNESS LOST: the chunk-shape closed "
                    "form no longer matches the engine)")
            elif not exact and not (pmin <= g <= pred):
                problems.append(
                    f"{sight} uploaded {g} bytes outside the static "
                    f"band [{pmin}, {pred}]")
    if not inject and ev["cold"]["h2d"] != ev["warm"]["h2d"]:
        problems.append(
            f"warm upload {ev['warm']['h2d']} differs from cold "
            f"{ev['cold']['h2d']}: the warm chunk store must re-upload "
            "byte-identical chunks (it caches the encoding, not the "
            "device buffers)")


def compare(reports, evidence, inject=False):
    """Base-arm exactness: per-statement h2d equality + warm identity.
    Returns (ok, lines)."""
    ok = True
    lines = []
    for ev in evidence:
        rep = reports[ev["idx"]]
        head = (f"[{rep.query}] h2d={rep.bytes_h2d:,}B "
                f"exact={rep.h2d_exact}")
        problems = []
        if not rep.h2d_exact and not inject:
            problems.append(
                "prediction is not exact despite live wire widths "
                "(the width override stopped reaching the model)")
        _check_h2d(rep, ev, inject, problems)
        if problems:
            ok = False
            lines.append(f"MISMATCH {head}")
            lines.extend(f"    {p}" for p in problems)
        else:
            lines.append(f"ok {head} :: warm uploads "
                         f"{ev['warm']['h2d']} == static")
    return ok, lines


def compare_sharded(reports, evidence, n_shards, inject=False):
    """Sharded-arm: h2d equality unchanged + measured ICI wire bytes ==
    the exchange+reduce arithmetic (equality for ici-exact scans, lower
    bound where outer-build bitmap psums ride the reduce)."""
    ok, lines = compare(reports, evidence, inject=inject)
    for ev in evidence:
        rep = reports[ev["idx"]]
        preds = sorted(((c.bytes_ici, c.ici_exact)
                        for c in rep.scans if c.compiled and c.shards > 1),
                       reverse=True)
        if inject:
            preds = [(0, True) for _ in preds]
        problems = []
        for sight in ("cold", "warm"):
            got = sorted(ev[sight]["ici"], reverse=True)
            if not inject and len(got) != len(preds):
                problems.append(
                    f"{sight} reported {len(got)} sharded byte events, "
                    f"the model priced {len(preds)} sharded scans "
                    "(model drift)")
                continue
            for (pred, exact), g in zip(preds, got):
                if exact and g != pred:
                    problems.append(
                        f"{sight} moved {g} ICI bytes, static "
                        f"prediction {pred} (EXACTNESS LOST: the "
                        "collective aval arithmetic no longer matches "
                        "parallel/exchange.py)")
                elif not exact and g < pred:
                    problems.append(
                        f"{sight} moved {g} ICI bytes < static lower "
                        f"bound {pred}")
        if problems:
            ok = False
            lines.append(f"MISMATCH [{rep.query}] sharded S={n_shards}")
            lines.extend(f"    {p}" for p in problems)
        else:
            lines.append(f"ok [{rep.query}] sharded :: warm ici "
                         f"{ev['warm']['ici']} == static")
    return ok, lines


def run_diff(inject_drift=False):
    """Full harness: base arm (all templates, forced partitions),
    sharded arm, encoded-off arm."""
    import numpy as np
    mod = _load_ab_module()
    queries = mod._STREAM_AB_QUERIES
    all_idx = list(range(len(queries)))

    # -- base arm -----------------------------------------------------------
    with mod._forced_stream_partitions():
        session = mod._chunked_star_session(np.random.default_rng(42))
        bounds, chunk_rows = _session_params(session)
        reports = predict(queries, bounds, chunk_rows,
                          _wire_cols(session))
        evidence = _run_sweep(mod, session, all_idx)
    ok, lines = compare(reports, evidence, inject=inject_drift)

    # -- sharded arm --------------------------------------------------------
    import jax
    with mod._forced_stream_partitions():
        with mod._forced_stream_shards() as n_shards:
            if len(jax.local_devices()) >= n_shards:
                session = mod._chunked_star_session(
                    np.random.default_rng(42))
                bounds, chunk_rows = _session_params(session)
                s_reports = predict(queries, bounds, chunk_rows,
                                    _wire_cols(session))
                s_ev = _run_sweep(
                    mod, session,
                    list(getattr(mod, "_STREAM_AB_SHARDED", ())))
            else:
                s_ev = None
    if s_ev is not None:
        ok_s, lines_s = compare_sharded(s_reports, s_ev, n_shards,
                                        inject=inject_drift)
        ok = ok and ok_s
        lines.extend(lines_s)
    else:
        lines.append("# sharded arm skipped: no multi-device mesh")

    # -- encoded-off arm ----------------------------------------------------
    with _encoded_off():
        with mod._forced_stream_partitions():
            session = mod._chunked_star_session(np.random.default_rng(42))
            bounds, chunk_rows = _session_params(session)
            e_reports = predict(queries, bounds, chunk_rows,
                                _wire_cols(session))
            e_ev = _run_sweep(mod, session, list(_ENCODED_OFF_SUBSET))
    ok_e, lines_e = compare(e_reports, e_ev, inject=inject_drift)
    ok = ok and ok_e
    lines.append(f"# encoded-off arm: {len(e_ev)} templates re-checked "
                 "at plain widths (NDS_TPU_ENCODED=0)")
    lines.extend(lines_e)
    return ok, lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="differential validation: static perf-audit byte "
        "predictions vs runtime StreamEvent evidence (exactness)")
    ap.add_argument("--inject-drift", action="store_true",
                    help="zero every predicted byte total before "
                    "comparing: the harness must FAIL "
                    "(model-drift self-test)")
    args = ap.parse_args(argv)
    ok, lines = run_diff(inject_drift=args.inject_drift)
    for ln in lines:
        print(ln)
    if args.inject_drift:
        if ok:
            print("# DRIFT FIXTURE FAILED TO FAIL: the harness cannot "
                  "detect a drifted cost model")
            return 1
        print("# drift fixture correctly rejected (harness is live)")
        return 0
    if ok:
        print("# perf-audit differential: every measured byte "
              "count matches its static prediction")
        return 0
    print("# perf-audit differential FAILED: update the static cost "
          "model in nds_tpu/analysis/perf_audit.py in lockstep with "
          "the engine")
    return 1


if __name__ == "__main__":
    sys.exit(main())
