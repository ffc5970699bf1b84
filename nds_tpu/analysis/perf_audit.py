# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Static cost auditor: price every statement's data movement before it runs.

The mechanism era closed with four abstract interpreters proving syncs
(``exec_audit``), memory (``mem_audit``), plans (``plan_audit``) and
concurrency (``conc_audit``) — but none of them prices *data movement*,
so a measured campaign number can only be compared with other measured
numbers. This module is the fifth interpreter: composing the exec and
mem walks (one decomposition, zero new AST logic), it derives for every
statement

1. **Predicted h2d bytes** — what the streamed scan pipeline uploads.
   The compiled chunk path pads every chunk to ONE physical capacity
   and always carries a validity byte per column
   (``engine/table.py padded_chunks``), so the upload is a closed form::

       bytes_h2d = n_chunks x chunk_cap x sum(width_data + 1)

   over the pruned columns at their WIRE widths (encoded codes when the
   ``io/columnar.py`` codec plan narrows them). The formula is EXACT by
   construction whenever the model knows the real rows and wire widths
   (``tools/perf_audit_diff.py`` feeds both from the live toy session
   and requires equality with ``StreamEvent.bytes_h2d``); against the
   SF10 catalog widths it is an upper bound (the runtime may encode
   narrower than the static proof). Warm runs re-upload every chunk —
   the chunk store caches the ENCODING, not the device buffers — so the
   prediction is sight-invariant, and the prefetch ring moves the same
   bytes earlier, never different bytes. The eager chunk loop instead
   uploads unencoded bucket-padded chunks with validity only on
   null-bearing columns: priced as a [min, max] band, never exact.

2. **Per-stage HBM traffic** — the roofline denominator of the chunk
   program, stage by stage (scan / filter / partition / probe /
   exchange / accumulate). Each per-chunk dispatch re-reads the chunk
   (one mask+compact pass, one hash pass when partitioned, one read per
   extra partition dispatch). This is a *model* (XLA fusion may do
   better) — it feeds the roofline wall, not an equality check.

3. **Predicted ICI bytes** — exact from the collective budget's shapes
   (``parallel/exchange.py`` accounts trace-time aval bytes; this
   module reproduces the same arithmetic): the per-chunk hash-exchange
   moves ``S x cap_ex x (sum(width_data + 1) + 5)`` bytes (data +
   validity per column, the partition-id plane, the validity plane) and
   the one cross-shard reduce moves ``20 x P`` (count all-gather +
   overflow/histogram psums). Outer-build bitmap psums ride on top —
   priced zero (a lower bound) and flagged inexact.

4. **A roofline lower-bound wall** — ``max`` of the three byte totals
   over their link rates (``NDS_TPU_ROOFLINE_H2D_GBS`` /
   ``_HBM_GBS`` / ``_ICI_GBS``), with a ranked static bottleneck tag:
   ``h2d-bound`` / ``hbm-bound`` / ``ici-bound`` for the slowest wall,
   ``sync-bound`` when exec_audit reports no finite sync bound (the
   eager loop's O(chunks) host reads dominate any byte wall). The wall
   is a LOWER bound on the statement's wall time by construction:
   measured minus wall = named overhead, the number
   ``tools/trace_report.py`` surfaces as ``unexplained ms``.

Lockstep (the standing rule): every prediction that maps to runtime
evidence is differentially checked. ``tools/perf_audit_diff.py``
replays the ``tests/test_synccount.py`` A/B sweep — base, forced-
partition, 2-shard and encoded-off arms — and fails when measured
``StreamEvent.bytes_h2d`` / ``bytes_ici`` disagree with the static
prediction (equality for exact predictions, band membership for
bounds); ``--inject-drift`` must fail. ``tools/bench_compare.py --audit-perf`` re-checks a
campaign ledger's recorded evidence against the same predictions, so
every Power Run lands pre-wired to its static denominator.
"""

from __future__ import annotations

import math
import os
from dataclasses import dataclass, field

from nds_tpu.analysis import Finding
from nds_tpu.analysis.exec_audit import (CLASS_COMPILED, CLASS_DEVICE,
                                         CLASS_EAGER, CLASS_UNKNOWN,
                                         ExecAuditor, ExecReport,
                                         ScanVerdict, _AUDIT_SEED)
from nds_tpu.analysis.mem_audit import (MemAuditor, MemModel, ScanBound,
                                        _bucket)
from nds_tpu.queries import (TEMPLATE_DIR, instantiate_template,
                             list_templates, load_template)

# ---------------------------------------------------------------------------
# roofline link rates
# ---------------------------------------------------------------------------

# Default sustained link rates (GB/s) the roofline walls divide by.
# HBM and ICI share their defaults with tools/trace_report.py's measured
# roofline columns (v5e-class: 819 GB/s HBM, 186 GB/s combined ICI);
# H2D is new here — a PCIe-class host link (the streamed upload path).
# All three are env knobs so a different part's numbers drop in without
# code changes, and the static and measured rooflines stay comparable
# because they read the SAME knobs.
DEFAULT_ROOFLINE_GBS = {"h2d": 32.0, "hbm": 819.0, "ici": 186.0}


def roofline_gbs() -> dict:
    """``{"h2d","hbm","ici"} -> GB/s`` from ``NDS_TPU_ROOFLINE_*_GBS``
    (read at call time; :class:`PerfAuditor` freezes a copy at
    construction, the same build-time env discipline every model
    follows)."""
    out = {}
    for k, dflt in DEFAULT_ROOFLINE_GBS.items():
        try:
            out[k] = float(os.environ.get(f"NDS_TPU_ROOFLINE_{k.upper()}_GBS",
                                          str(dflt)))
        except ValueError:
            out[k] = dflt
    return out


# the four static bottleneck tags (the corpus histogram is pinned in
# tier-1 by tests/test_analysis.py, like exec_audit's classification pin)
BOUND_H2D = "h2d-bound"
BOUND_HBM = "hbm-bound"
BOUND_ICI = "ici-bound"
BOUND_SYNC = "sync-bound"

# HBM stage names, pipeline order (DESIGN.md "Static cost model")
STAGES = ("scan", "filter", "partition", "probe", "exchange", "accumulate")


# ---------------------------------------------------------------------------
# report types
# ---------------------------------------------------------------------------


@dataclass
class ScanCost:
    """The priced data movement of one >HBM streamed scan."""

    alias: str
    table: str
    compiled: bool             # chunk pipeline (True) or eager loop
    rows: int                  # streamed row bound the chunks slice
    chunks: int                # ceil(rows / chunk_rows)
    chunk_cap: int             # uniform padded capacity per chunk
    n_cols: int = 0            # pruned column count on the wire
    width: int = 0             # wire bytes/row incl. validity (pruned)
    priced: bool = True        # False = unknown table, default width
    bytes_h2d: int = 0         # upload prediction (compiled: exact form)
    bytes_h2d_min: int = 0     # lower edge (eager band; == bytes_h2d
    #                            when the prediction is a point)
    h2d_exact: bool = False    # True only when rows AND wire widths are
    #                            the real ones (harness-supplied)
    partitions: int = 1        # grace partition count (mem model choice)
    shards: int = 1            # mesh shard count
    exchange: bool = False     # per-chunk hash-exchange pass active
    cap_ex: int = 0            # exchange bucket capacity per (shard,dest)
    bytes_ici: int = 0         # collective wire bytes (exchange + reduce)
    ici_exact: bool = False    # False when outer-build bitmaps ride the
    #                            reduce (priced 0: lower bound) or widths
    #                            are the static stand-ins
    stages: dict = field(default_factory=dict)  # stage -> HBM bytes

    @property
    def bytes_hbm(self) -> int:
        return sum(self.stages.values())

    def to_dict(self) -> dict:
        return {
            "alias": self.alias, "table": self.table,
            "compiled": self.compiled, "rows": int(self.rows),
            "chunks": int(self.chunks), "chunk_cap": int(self.chunk_cap),
            "n_cols": int(self.n_cols), "width": int(self.width),
            "priced": self.priced,
            "bytes_h2d": int(self.bytes_h2d),
            "bytes_h2d_min": int(self.bytes_h2d_min),
            "h2d_exact": self.h2d_exact,
            "partitions": int(self.partitions), "shards": int(self.shards),
            "exchange": self.exchange, "cap_ex": int(self.cap_ex),
            "bytes_ici": int(self.bytes_ici), "ici_exact": self.ici_exact,
            "stages": {k: int(v) for k, v in self.stages.items()},
            "bytes_hbm": int(self.bytes_hbm),
        }


@dataclass
class PerfReport:
    """Byte totals + roofline wall of one template statement."""

    file: str
    query: str
    classification: str        # exec_audit's routing classification
    bytes_h2d: int = 0
    bytes_h2d_min: int = 0
    h2d_exact: bool = False
    bytes_hbm: int = 0
    bytes_ici: int = 0
    ici_exact: bool = False
    wall_h2d_ms: float = 0.0
    wall_hbm_ms: float = 0.0
    wall_ici_ms: float = 0.0
    roofline_ms: float = 0.0   # max of the three walls: the static
    #                            lower bound on the statement's wall
    bound: str = BOUND_SYNC    # ranked bottleneck tag
    scans: tuple = ()          # ScanCosts, exec/mem walk order
    stages: dict = field(default_factory=dict)  # aggregated stage bytes
    detail: str = ""

    def to_dict(self) -> dict:
        return {
            "file": self.file, "query": self.query,
            "classification": self.classification,
            "bytes_h2d": int(self.bytes_h2d),
            "bytes_h2d_min": int(self.bytes_h2d_min),
            "h2d_exact": self.h2d_exact,
            "bytes_hbm": int(self.bytes_hbm),
            "bytes_ici": int(self.bytes_ici),
            "ici_exact": self.ici_exact,
            "wall_h2d_ms": round(self.wall_h2d_ms, 6),
            "wall_hbm_ms": round(self.wall_hbm_ms, 6),
            "wall_ici_ms": round(self.wall_ici_ms, 6),
            "roofline_ms": round(self.roofline_ms, 6),
            "bound": self.bound,
            "scans": [s.to_dict() for s in self.scans],
            "stages": {k: int(v) for k, v in self.stages.items()},
            "detail": self.detail,
        }


# ---------------------------------------------------------------------------
# live wire widths (the harness's exactness hook)
# ---------------------------------------------------------------------------


def wire_column_widths(table, canonical_types: dict | None = None) -> dict:
    """``{lowercase column -> wire bytes/row incl. validity}`` of the
    padded streamed chunks the engine actually uploads for ``table`` (an
    arrow Table or an engine ``ChunkedTable``) — the live twin of the
    :class:`MemModel` width tables, exact by construction because the
    dtype selection mirrors ``padded_chunks``: strings ride int32
    dictionary codes, int-path columns the SAME ``plan_column_codec``
    plan the runtime caches (narrow FOR/dict codes when the data
    proves them), everything else the plain device lowering
    (int32/date -> 4, int64/double/scaled decimal -> 8) — plus the
    always-present validity byte. ``tools/perf_audit_diff.py`` and
    ``tools/bench_compare.py --audit-perf`` feed these into
    :class:`PerfAuditor` as the ``wire_cols`` override, which is what
    upgrades the h2d/ICI predictions from bounds to equalities."""
    from nds_tpu import types as _t
    from nds_tpu.io.columnar import encoded_enabled, plan_column_codec
    arrow = getattr(table, "arrow", table)
    ctypes = dict(canonical_types
                  or getattr(table, "canonical_types", None) or {})
    enc = encoded_enabled()
    out = {}
    for name in arrow.column_names:
        ct = ctypes.get(name) or _t.arrow_to_canonical(
            arrow.schema.field(name).type)
        kind = _t.device_kind(ct)
        if kind == "str":
            w = 4                          # int32 dictionary codes
        else:
            got = plan_column_codec(arrow[name], ct) if enc else None
            if got is not None:
                w = got[0].dtype.itemsize  # narrow FOR/dict codes
            elif kind in ("i32", "date"):
                w = 4
            else:
                w = 8                      # i64 / f64 / scaled decimal
        out[name.lower()] = int(w) + 1     # + the validity byte
    return out


# ---------------------------------------------------------------------------
# the auditor
# ---------------------------------------------------------------------------


class PerfAuditor:
    """Host-only static cost model over the planner's decomposition.

    Composes :class:`ExecAuditor` (routing, shards, collective budgets)
    and :class:`MemAuditor` (row bounds, partition plan, widths)
    rather than walking the AST a third time: one decomposition, three
    interpretations. ``wire_cols`` optionally maps a table name to its
    REAL per-column wire widths (:func:`wire_column_widths`) — the
    differential harnesses pass it so the byte predictions become
    equalities; without it the model prices the conservative static
    widths and every prediction is an upper bound. Roofline link rates
    are frozen at construction from ``NDS_TPU_ROOFLINE_*_GBS``."""

    def __init__(self, streamed=None, model: MemModel | None = None,
                 base_tables=None, catalog: dict | None = None,
                 wire_cols: dict | None = None):
        self.model = model or MemModel()
        self.mem = MemAuditor(streamed=streamed, model=self.model,
                              base_tables=base_tables)
        self.exec = ExecAuditor(catalog=catalog, streamed=streamed,
                                base_tables=base_tables,
                                mem_model=self.model)
        self.streamed = self.mem.streamed
        self.wire_cols = {t.lower(): {c.lower(): int(w)
                                      for c, w in cols.items()}
                          for t, cols in (wire_cols or {}).items()}
        self.rates = roofline_gbs()
        # NDS_TPU_STREAM_EXCHANGE gate, frozen at construction like the
        # executor freezes it at pipeline build (the lockstep rule)
        self.exchange_on = os.environ.get("NDS_TPU_STREAM_EXCHANGE",
                                          "1") != "0"

    # -- entry point --------------------------------------------------------

    def audit_sql(self, sql: str, file: str = "<sql>",
                  query: str = "<sql>") -> PerfReport:
        """Price one SQL statement's data movement."""
        er = self.exec.audit_sql(sql, file=file, query=query)
        mr = self.mem.audit_sql(sql, file=file, query=query)
        if er.classification == CLASS_UNKNOWN:
            return PerfReport(file, query, er.classification,
                              detail=er.detail or mr.detail)
        costs = self._scan_costs(er, mr, self.mem.needed)
        return self._assemble(file, query, er, mr, costs)

    # -- per-scan pricing ---------------------------------------------------

    def _scan_costs(self, er: ExecReport, mr, needed) -> list:
        """Pair the exec verdicts with the mem bounds (both walk the
        same decomposition; pair by index, falling back to table-name
        matching) and price each streamed scan. EVERY pipeline of the
        statement — expression-subquery pipelines included — prunes at
        the STATEMENT-level needed set: the planner computes pruning
        once per statement, so the ab12-class scalar-subquery chain
        uploads the same columns in both of its store_sales pipelines
        (the differential harness pins this byte-exactly)."""
        bounds = list(mr.scans)
        pairs = []
        for i, sv in enumerate(er.scans):
            sb = None
            if i < len(bounds) and bounds[i] is not None \
                    and bounds[i].table == sv.table:
                sb = bounds[i]
                bounds[i] = None
            else:
                for j, b in enumerate(bounds):
                    if b is not None and b.table == sv.table:
                        sb = b
                        bounds[j] = None
                        break
            pairs.append((sv, sb))
        return [self._scan_cost(sv, sb, needed) for sv, sb in pairs]

    def _pruned_widths(self, table: str, needed):
        """``(cols, exact, priced)``: the pruned wire width per column.
        ``cols`` applies the planner's proper-subset pruning rule to the
        table's ACTUAL columns (the ``wire_cols`` override when the
        harness supplies real widths, the static encoded/plain catalog
        widths otherwise)."""
        exact = False
        cols = self.wire_cols.get(table)
        if cols is not None:
            exact = True
        else:
            cols = (self.model.enc_widths if self.model.encoded
                    else self.model.widths).get(table, {})
        if not cols:
            return {"?": 9}, False, False   # unknown table: one wide col
        if needed is not None:
            kept = {c: w for c, w in cols.items() if c in needed}
            if kept and len(kept) < len(cols):
                cols = kept
        return dict(cols), exact, True

    def _plain_width(self, table: str, needed):
        """``(width, n_cols)`` of the UNENCODED pruned row — what the
        eager chunk loop uploads (``from_arrow``: no narrow codecs,
        bucket-padded per chunk)."""
        cols = self.model.widths.get(table, {})
        if not cols:
            return 9, 1
        if needed is not None:
            kept = {c: w for c, w in cols.items() if c in needed}
            if kept and len(kept) < len(cols):
                cols = kept
        return sum(cols.values()), len(cols)

    def _scan_cost(self, sv: ScanVerdict, sb: ScanBound | None,
                   needed) -> ScanCost:
        model = self.model
        rows = sb.rows if sb is not None \
            else (model.table_rows(sv.table) or 1)
        n_chunks = max(1, math.ceil(rows / model.chunk_rows))
        cap = model.chunk_cap()
        P = max(1, sb.partitions if sb is not None else 1)
        S = max(1, sv.shards)
        cols, exact_w, priced = self._pruned_widths(sv.table, needed)
        width = sum(cols.values())
        n_cols = len(cols)
        cost = ScanCost(sv.alias, sv.table, sv.compiled, rows, n_chunks,
                        cap, n_cols=n_cols, width=width, priced=priced,
                        partitions=P, shards=S)

        chunk_bytes = cap * width
        if sv.compiled:
            # the closed form: every chunk at ONE capacity, every column
            # data + validity — identical cold/warm (the chunk store
            # caches the encoding, not the buffers) and prefetch-
            # invariant (the ring changes WHEN bytes move, not how many)
            cost.bytes_h2d = cost.bytes_h2d_min = n_chunks * chunk_bytes
            cost.h2d_exact = exact_w
        else:
            # eager loop: unencoded chunks, each bucket-padded to its own
            # length, validity only where nulls exist -> a [min,max] band
            pw, pn = self._plain_width(sv.table, needed)
            last = rows - (n_chunks - 1) * model.chunk_rows
            padded = (n_chunks - 1) * _bucket(model.chunk_rows) \
                + _bucket(max(last, 1))
            cost.bytes_h2d = padded * pw
            cost.bytes_h2d_min = padded * max(pw - pn, 1)

        # -- ICI: exchange (per chunk) + the one cross-shard reduce ------
        if sv.compiled and S > 1:
            exch = (P > 1 and sv.a2a_chunk > 0 and self.exchange_on)
            reduce_bytes = 20 * P          # all_gather counts (8P) +
            #                                psum flags (4P) + hist (8P)
            n_builds = max(0, sv.coll_final - 3)
            if exch:
                cost.exchange = True
                cost.cap_ex = _bucket(max((cap // S) // S, 1)
                                      * model.skew)
                exch_bytes = S * cost.cap_ex * (width + 5)
                cost.bytes_ici = n_chunks * exch_bytes + reduce_bytes
            else:
                cost.bytes_ici = reduce_bytes
            # outer-build bitmap psums ride the reduce; their padded
            # length is the build side's device table length, which the
            # composed walk does not surface — priced 0 (lower bound)
            cost.ici_exact = exact_w and n_builds == 0

        # -- HBM stage model (roofline denominator) ----------------------
        stages = dict.fromkeys(STAGES, 0)
        if sv.compiled:
            stages["scan"] = n_chunks * chunk_bytes
            # mask + compact re-read per chunk
            stages["filter"] = n_chunks * chunk_bytes
            if P > 1:
                # radix hash pass re-reads the chunk
                stages["partition"] = n_chunks * chunk_bytes
                # every extra per-partition dispatch re-reads the chunk
                stages["probe"] = (P - 1) * n_chunks * chunk_bytes
            if cost.exchange:
                # pack write + exchanged read around the all-to-all
                stages["exchange"] = 2 * n_chunks * S * cost.cap_ex \
                    * (width + 5)
            if sb is not None:
                acc = sb.part_bytes * P if (sb.part_bytes is not None
                                            and P > 1) else sb.acc_bytes
                stages["accumulate"] = int(acc or 0)
        else:
            # eager loop: each uploaded chunk is read once; survivors
            # concatenate on host (no device accumulator to price)
            stages["scan"] = cost.bytes_h2d
        cost.stages = {k: v for k, v in stages.items() if v}
        return cost

    # -- statement assembly -------------------------------------------------

    def _assemble(self, file, query, er: ExecReport, mr,
                  costs: list) -> PerfReport:
        rep = PerfReport(file, query, er.classification)
        rep.scans = tuple(costs)
        rep.bytes_h2d = sum(c.bytes_h2d for c in costs)
        rep.bytes_h2d_min = sum(c.bytes_h2d_min for c in costs)
        rep.h2d_exact = bool(costs) and all(c.h2d_exact for c in costs)
        rep.bytes_ici = sum(c.bytes_ici for c in costs)
        rep.ici_exact = all(c.ici_exact for c in costs
                            if c.bytes_ici) if any(c.bytes_ici
                                                   for c in costs) else False
        stages: dict = {}
        for c in costs:
            for k, v in c.stages.items():
                stages[k] = stages.get(k, 0) + v
        if er.classification == CLASS_DEVICE:
            # device-resident statement: one pass over the resident peak
            # is the floor of its HBM traffic
            stages["scan"] = stages.get("scan", 0) + int(mr.peak_bytes)
        rep.stages = stages
        rep.bytes_hbm = sum(stages.values())
        # walls: bytes / (GB/s x 1e9) in ms == bytes / rate / 1e6
        rep.wall_h2d_ms = rep.bytes_h2d / self.rates["h2d"] / 1e6
        rep.wall_hbm_ms = rep.bytes_hbm / self.rates["hbm"] / 1e6
        rep.wall_ici_ms = rep.bytes_ici / self.rates["ici"] / 1e6
        rep.roofline_ms = max(rep.wall_h2d_ms, rep.wall_hbm_ms,
                              rep.wall_ici_ms)
        rep.bound = self._bound_tag(er, rep)
        return rep

    @staticmethod
    def _bound_tag(er: ExecReport, rep: PerfReport) -> str:
        """Ranked static bottleneck: ``sync-bound`` when exec_audit has
        no finite statement sync bound (the eager loop's O(chunks) host
        reads dominate any byte wall — routing is the bottleneck, not a
        link), else the slowest wall, ties resolved in pipeline order
        (h2d feeds HBM feeds ICI)."""
        if er.classification == CLASS_EAGER or er.sync_bound is None:
            return BOUND_SYNC
        walls = ((rep.wall_h2d_ms, BOUND_H2D),
                 (rep.wall_hbm_ms, BOUND_HBM),
                 (rep.wall_ici_ms, BOUND_ICI))
        best, tag = 0.0, BOUND_SYNC
        for w, t in walls:
            if w > best:
                best, tag = w, t
        return tag


# ---------------------------------------------------------------------------
# corpus driver + lint-gate findings
# ---------------------------------------------------------------------------


def audit_perf_template_text(text: str, file: str,
                             auditor: PerfAuditor | None = None) -> list:
    """Instantiate one template (pinned seed, shared with the other
    auditors) and price each statement; returns PerfReports."""
    import numpy as np
    auditor = auditor or PerfAuditor()
    sql = instantiate_template(text, np.random.default_rng(_AUDIT_SEED))
    stmts = [s for s in sql.split(";") if s.strip()]
    base = os.path.basename(file)
    out = []
    for i, stmt in enumerate(stmts):
        qname = base[:-4] if base.endswith(".tpl") else base
        if len(stmts) > 1:
            qname = f"{qname}_part{i + 1}"
        out.append(auditor.audit_sql(stmt, file=base, query=qname))
    return out


def audit_perf_corpus(template_dir: str | None = None,
                      streamed=None) -> list:
    """PerfReports for every template in templates.lst order."""
    template_dir = template_dir or TEMPLATE_DIR
    auditor = PerfAuditor(streamed=streamed)
    reports: list = []
    for name in list_templates(template_dir):
        reports.extend(audit_perf_template_text(
            load_template(name, template_dir), name, auditor))
    return reports


def reports_to_findings(reports) -> list:
    """Lint-gate findings from perf reports. The byte totals themselves
    are a report (``--perf-report``), not findings; the gate catches the
    two ways the cost model can silently stop modeling:

    * ``cost-model-gap`` — a compiled streamed scan priced at the
      unknown-table default width: the model cannot see the table's
      columns, so every byte prediction for the statement is fiction;
    * ``roofline-degenerate`` — a compiled-stream statement whose
      roofline wall is zero: nothing was priced at all, which means the
      composed walk and the routing drifted apart.
    """
    findings = []
    for r in reports:
        for s in r.scans:
            if s.compiled and not s.priced:
                findings.append(Finding(
                    r.file, r.query, "cost-model-gap", "error",
                    f"streamed scan {s.table!r} priced at the unknown-"
                    "table default width: the static cost model cannot "
                    "see its columns, so the statement's byte "
                    "predictions are unfounded"))
        if r.classification == CLASS_COMPILED and r.roofline_ms <= 0:
            findings.append(Finding(
                r.file, r.query, "roofline-degenerate", "error",
                "compiled-stream statement with a zero roofline wall: "
                "the cost model priced no data movement (model drift "
                "against the exec/mem decomposition)"))
    return findings


def perf_audit_findings(template_dir: str | None = None) -> list:
    """The lint pass entry point (tools/lint.py seventh pass)."""
    return reports_to_findings(audit_perf_corpus(template_dir))


def bottleneck_counts(reports) -> dict:
    """``tag -> statement count`` histogram of the static bottleneck
    tags (the pinned corpus cost story)."""
    counts: dict = {}
    for r in reports:
        counts[r.bound] = counts.get(r.bound, 0) + 1
    return counts


def corpus_walls(template_dir: str | None = None) -> dict:
    """``query -> (roofline_ms, bound)`` for the whole corpus — the
    static denominator ``tools/trace_report.py`` renders next to the
    measured roofline columns."""
    return {r.query: (r.roofline_ms, r.bound)
            for r in audit_perf_corpus(template_dir)}


def _mb(n: int) -> str:
    return f"{n / 1e6:,.1f}"


def format_perf_report(reports) -> str:
    """The per-template cost table (``tools/lint.py --perf-report``):
    predicted byte totals, the roofline wall and the bottleneck tag —
    what a measured campaign number is compared against."""
    rates = roofline_gbs()
    lines = ["# perf-audit: per-statement static cost model",
             "# rates GB/s: "
             + ", ".join(f"{k}={rates[k]:g}" for k in ("h2d", "hbm",
                                                       "ici")),
             f"{'template':<18} {'class':<16} {'h2d-MB':>10} "
             f"{'hbm-MB':>10} {'ici-MB':>9} {'roof-ms':>9}  bound"]
    for r in reports:
        lines.append(
            f"{r.query:<18} {r.classification:<16} "
            f"{_mb(r.bytes_h2d):>10} {_mb(r.bytes_hbm):>10} "
            f"{_mb(r.bytes_ici):>9} {r.roofline_ms:>9.2f}  {r.bound}")
    counts = bottleneck_counts(reports)
    summary = ", ".join(f"{k}: {v}" for k, v in sorted(counts.items()))
    lines.append(f"# {len(reports)} statements — {summary}")
    return "\n".join(lines)
