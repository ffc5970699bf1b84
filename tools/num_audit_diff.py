# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Numeric-safety differential harness: static value-range verdicts vs
runtime boundary-value execution, in lockstep.

``analysis/num_audit.py`` PROVES, per corpus statement, that every codec
fits its narrow width, every literal rebase and accumulator stays inside
int64 / f64-exact range, and the hash route bits fit the mixed width.
A static proof that nothing ever checks against the live engine is a
comment with extra steps.  This harness is the check:

* build adversarial boundary-value tables under REAL catalog names —
  FOR spans at the exact int16 edge (span 2^15 - 1) over a 10^9 rebase
  base, an all-negative span, a julian-date base, decimal(7,2) at its
  ±(10^7 - 1)/100 extremes, a 4096-distinct dictionary column at full
  code space, and a hot-hash join key carrying half the fact table —
  plus an off-catalog extremes table (int32-edge FOR span, max-scale
  decimal(16,10) at MAX_DEC_SCALE);

* drive a fixed query set over those tables through THREE arms — base
  (compiled streaming), sharded (NDS_TPU_STREAM_SHARDS=2), and
  encoded-off (NDS_TPU_ENCODED=0) — and
  demand bit-for-bit equality of every arm against the plain-width
  eager reference (resident tables, encoding disabled).  The first two
  queries aim literals OUTSIDE the encoded domain in both wrap
  directions, so the saturating rebase in engine/exprs.py is on the
  line every run;

* audit the same statements with :class:`NumAuditor` parameterized by
  the toy session's REAL row counts and demand exact agreement between
  the static verdict (every check proven) and the runtime overflow-flag
  evidence (no ``bound-bucket overflow`` rerun on any stream event);

* re-run the executable claim checks (kernel + codec) so the harness
  fails the moment a numeric comment in engine/kernels.py or
  io/columnar.py stops being true.

``--inject-drift`` is the MUST-fail self-test, in BOTH directions:

* direction A (static too optimistic): the sweep reruns under
  ``NDS_TPU_STREAM_ACC_ROWS=1024`` so the accumulator provably
  overflows at runtime while the static verdicts still say proven —
  the harness must flag the contradiction;
* direction B (static too pessimistic / widened ranges): the audit
  reruns with every row bound inflated x10^9 so the accumulator proofs
  fail statically while the runtime stays clean — the harness must
  flag that contradiction too.

With ``--inject-drift`` the exit code is 0 only when BOTH directions
are correctly rejected.  Run by tier-1 via tests/test_analysis.py.
"""

from __future__ import annotations

import argparse
import contextlib
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

_DATE_BASE = 2450815          # julian-style dimension base (big rebase)
_TICKET_BASE = 1_000_000_000  # 10^9 FOR base under an int16-width span
_NEG_BASE = -40_000           # all-negative FOR span
_N_FACT = 8192                # 4 chunks at 2048 — edges, not volume
_N_ITEMS = 4096               # DICT_MAX_VALUES: full dictionary code space
_HOT_KEY = 7                  # hot-hash join key (half the fact rows)


@contextlib.contextmanager
def _env(**kv):
    """Set env vars for one arm, always restoring the previous values."""
    old = {k: os.environ.get(k) for k in kv}
    try:
        for k, v in kv.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = str(v)
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _boundary_tables(rng):
    """Adversarial arrow tables under real catalog names (so the static
    auditor prices them) plus one off-catalog extremes table."""
    from decimal import Decimal

    import numpy as np
    import pyarrow as pa

    span16 = (1 << 15) - 1
    n = _N_FACT
    # hot-hash key: half the fact table lands on one join key
    item_sk = rng.integers(1, _N_ITEMS + 1, n)
    item_sk[: n // 2] = _HOT_KEY
    rng.shuffle(item_sk)
    # decimal(7,2): random cents plus both exact extremes
    cents = rng.integers(-(10 ** 7 - 1), 10 ** 7, n)
    cents[0], cents[1] = 10 ** 7 - 1, -(10 ** 7 - 1)
    price = pa.array([Decimal(int(c)) / 100 for c in cents],
                     pa.decimal128(7, 2))
    store_sales = pa.table({
        "ss_sold_date_sk": pa.array(
            _DATE_BASE + rng.integers(0, 365, n), pa.int64()),
        "ss_item_sk": pa.array(item_sk, pa.int64()),
        # span EXACTLY 2^15 - 1 over a 10^9 base: the int16 FOR edge,
        # with both endpoints pinned live
        "ss_ticket_number": pa.array(
            _TICKET_BASE + np.concatenate(
                ([0, span16], (np.arange(n - 2) * 131) % (span16 + 1))),
            pa.int64()),
        # all-negative span at the same int16 edge, endpoints pinned
        "ss_quantity": pa.array(
            _NEG_BASE + np.concatenate(
                ([0, span16], (np.arange(n - 2) * 37) % (span16 + 1))),
            pa.int64()),
        "ss_ext_sales_price": price,
    })
    item = pa.table({
        "i_item_sk": pa.array(np.arange(1, _N_ITEMS + 1), pa.int64()),
        # exactly 4096 distinct strings: full dict code space, top
        # code 4095 is a live value-table index
        "i_item_id": pa.array([f"AAAA{i:012d}" for i in range(_N_ITEMS)]),
        "i_brand_id": pa.array(
            1 + np.arange(_N_ITEMS) % 11, pa.int64()),
    })
    date_dim = pa.table({
        "d_date_sk": pa.array(
            _DATE_BASE + np.arange(365), pa.int64()),
        "d_year": pa.array(1998 + (np.arange(365) // 183), pa.int64()),
        "d_moy": pa.array(1 + np.arange(365) % 12, pa.int64()),
    })
    # off-catalog extremes (runtime-equality only, no static verdict):
    # int32-edge FOR span and a max-scale decimal at MAX_DEC_SCALE = 10
    big = (1 << 31) - 2
    x = np.arange(512)
    extremes = pa.table({
        "x_key": pa.array(x % 7, pa.int64()),
        "x_for32": pa.array((x * (big // 511)).clip(0, big), pa.int64()),
        "x_dec": pa.array(
            [Decimal(int(v)) / (10 ** 10)
             for v in (x % 9 - 4) * (10 ** 15)], pa.decimal128(16, 10)),
    })
    return {"store_sales": store_sales, "item": item,
            "date_dim": date_dim, "edge_extremes": extremes}


# (sql, static) — static=True statements run through NumAuditor too
# (catalog names only); the extremes statement is runtime-equality only.
_AB_QUERIES = (
    # rebase saturation, wrap-downward direction: base 10^9 > 0 with a
    # NEGATIVE literal (raw - base wraps positive without the clamp)
    ("select count(*) c, min(ss_ticket_number) mn, "
     "max(ss_ticket_number) mx from store_sales "
     "where ss_ticket_number > -5", True),
    # rebase saturation, wrap-upward direction: base -40000 < 0 with a
    # large POSITIVE literal, plus the exact top-of-span literal
    ("select count(*) c, sum(ss_quantity) q from store_sales "
     "where ss_quantity < 100000 "
     "and ss_ticket_number >= 1000032766", True),
    # full-code-space dict group + decimal(7,2) extremes through the
    # hot-hash join key
    ("select i_item_id, count(*) c, sum(ss_ext_sales_price) s "
     "from store_sales, item where ss_item_sk = i_item_sk "
     "group by i_item_id order by i_item_id limit 40", True),
    # star join over the julian-base date FOR column
    ("select d_year, i_brand_id, sum(ss_ext_sales_price) s "
     "from store_sales, item, date_dim "
     "where ss_item_sk = i_item_sk and ss_sold_date_sk = d_date_sk "
     "group by d_year, i_brand_id "
     "order by d_year, i_brand_id limit 60", True),
    # encoded-space decimal compare one cent under the extreme
    ("select count(*) c from store_sales "
     "where ss_ext_sales_price >= 99999.98", True),
    # int-AVG precision lane + FOR-edge min/max
    ("select avg(ss_quantity) a, min(ss_quantity) mn, "
     "max(ss_quantity) mx from store_sales", True),
    # off-catalog extremes: int32-edge FOR sum + max-scale decimal
    ("select x_key, count(*) c, sum(x_for32) s, min(x_dec) mn, "
     "max(x_dec) mx from edge_extremes group by x_key "
     "order by x_key", False),
)

_ARMS = (
    ("base", {}),
    ("sharded", {"NDS_TPU_STREAM_SHARDS": "2"}),
    ("encoded-off", {"NDS_TPU_ENCODED": "0"}),
)


def _make_session(tables, chunked):
    from nds_tpu.engine.session import Session
    from nds_tpu.engine.table import ChunkedTable
    s = Session()
    for name, tbl in tables.items():
        if chunked and name in ("store_sales", "edge_extremes"):
            s.create_temp_view(name, ChunkedTable(tbl, chunk_rows=2048),
                               base=True, arrow=tbl)
        else:
            s.create_temp_view(name, tbl, base=True)
    return s


def reference(tables):
    """Plain-width eager reference: resident tables, encoding OFF."""
    with _env(NDS_TPU_ENCODED="0"):
        s = _make_session(tables, chunked=False)
        return [s.sql(sql).collect() for sql, _static in _AB_QUERIES]


def run_arm(name, env_kv, tables):
    """One arm of the sweep: chunked session under the arm's env;
    returns per-query collected rows + drained stream events."""
    from nds_tpu.listener import drain_stream_events
    results, events = [], []
    with _env(**env_kv):
        s = _make_session(tables, chunked=True)
        drain_stream_events()
        for sql, _static in _AB_QUERIES:
            results.append(s.sql(sql).collect())
            events.append(drain_stream_events())
    return {"name": name, "results": results, "events": events}


def static_verdicts(row_bounds, inflate=1):
    """NumAuditor reports for the catalog-name statements, parameterized
    by the toy session's REAL row counts (``inflate`` is the drift
    fixture: corrupted cardinalities widen every range)."""
    from nds_tpu.analysis.mem_audit import MemModel
    from nds_tpu.analysis.num_audit import NumAuditor
    bounds = {k: v * inflate for k, v in row_bounds.items()}
    auditor = NumAuditor(streamed={"store_sales"},
                         model=MemModel(row_bounds=bounds))
    return [auditor.audit_sql(sql, file="num_audit_diff",
                              query=f"nq{i + 1}")
            for i, (sql, static) in enumerate(_AB_QUERIES) if static]


def _overflowed(events) -> bool:
    return any(e.reason == "bound-bucket overflow" for e in events)


def compare(expect, arms, reports, base_arm, lines=None):
    """Bit-for-bit equality per arm + static/runtime verdict agreement.
    Returns (ok, lines)."""
    ok = True
    lines = [] if lines is None else lines
    for arm in arms:
        for i, (sql, _static) in enumerate(_AB_QUERIES):
            if arm["results"][i] == expect[i]:
                lines.append(f"ok: nq{i + 1} [{arm['name']}] "
                             f"bit-identical to plain-width eager "
                             f"({len(expect[i])} rows)")
            else:
                ok = False
                lines.append(f"MISMATCH: nq{i + 1} [{arm['name']}] "
                             f"diverges from plain-width eager")
    # verdict agreement on the base arm: a statement the auditor proves
    # must never take the overflow rerun, and a clean runtime must never
    # carry an unproven accumulator check
    si = [i for i, (_s, static) in enumerate(_AB_QUERIES) if static]
    for r, i in zip(reports, si):
        proven = r.proven
        over = _overflowed(base_arm["events"][i])
        if proven and over:
            ok = False
            lines.append(f"MISMATCH: nq{i + 1} statically proven but the "
                         f"runtime took the bound-bucket overflow rerun")
        elif not proven and not over:
            bad = [c for c in r.checks if not c.proven]
            what = f"{bad[0].kind} {bad[0].subject}" if bad else "?"
            ok = False
            lines.append(f"MISMATCH: nq{i + 1} statically unproven "
                         f"({what}) against a clean runtime")
        else:
            lines.append(f"ok: nq{i + 1} static verdict "
                         f"{'proven' if proven else 'unproven'} agrees "
                         f"with runtime overflow evidence")
    return ok, lines


def _claim_lines():
    from nds_tpu.analysis.num_audit import (codec_claim_checks,
                                            kernel_claim_checks)
    ok, lines = True, []
    for c in kernel_claim_checks() + codec_claim_checks():
        if c.proven:
            lines.append(f"ok: claim {c.subject}")
        else:
            ok = False
            lines.append(f"MISMATCH: claim {c.subject}: {c.detail}")
    return ok, lines


def run_diff(inject_drift=False):
    """Full harness.  Normal mode: (ok, lines).  Inject mode: runs BOTH
    drift directions and succeeds only when each is rejected."""
    import numpy as np

    tables = _boundary_tables(np.random.default_rng(1729))
    bounds = {k: t.num_rows for k, t in tables.items()}
    expect = reference(tables)
    arms = []
    lines = []
    for name, env_kv in _ARMS:
        if name == "sharded":
            import jax
            if jax.device_count() < 2:
                lines.append("# sharded arm skipped: no multi-device "
                             "mesh")
                continue
        arms.append(run_arm(name, env_kv, tables))
    base_arm = arms[0]
    reports = static_verdicts(bounds)

    if not inject_drift:
        ok, lines = compare(expect, arms, reports, base_arm, lines)
        cok, clines = _claim_lines()
        return ok and cok, lines + clines

    # direction A — static too optimistic: force the runtime overflow
    # rerun with an explicit accumulator ceiling far below the survivor
    # counts; the (still proven) static verdicts must be contradicted
    with _env(NDS_TPU_STREAM_ACC_ROWS="1024"):
        over_arm = run_arm("base+acc-ceiling", {}, tables)
    ok_a, lines_a = compare(expect, [over_arm], reports, over_arm)
    rejected_a = not ok_a and any(
        "overflow rerun" in ln for ln in lines_a)
    lines.append(
        "inject-drift A (runtime overflow vs proven static): "
        + ("correctly rejected" if rejected_a else "NOT DETECTED"))

    # direction B — widened static ranges: row bounds inflated x10^9
    # make the accumulator proofs fail while the runtime stays clean
    drift_reports = static_verdicts(bounds, inflate=10 ** 9)
    ok_b, lines_b = compare(expect, [base_arm], drift_reports, base_arm)
    rejected_b = not ok_b and any(
        "statically unproven" in ln for ln in lines_b)
    lines.append(
        "inject-drift B (widened static ranges vs clean runtime): "
        + ("correctly rejected" if rejected_b else "NOT DETECTED"))
    return rejected_a and rejected_b, lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--inject-drift", action="store_true",
                    help="self-test: force disagreement in both "
                         "directions (runtime overflow under a proven "
                         "verdict; widened static ranges against a "
                         "clean runtime) — both MUST be rejected")
    args = ap.parse_args(argv)
    ok, lines = run_diff(inject_drift=args.inject_drift)
    print("\n".join(lines))
    if args.inject_drift:
        print("inject-drift: both directions rejected" if ok
              else "inject-drift: a drifted verdict survived")
        return 0 if ok else 1
    print("num-audit-diff: static verdicts and runtime evidence agree"
          if ok else "num-audit-diff: DRIFT")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
