# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Static numeric-safety auditor: value-range/precision proofs on host.

The encoded execution path does all of its hot arithmetic in deliberately
narrow integer spaces — int16/int32 frame-of-reference offsets, sorted-dict
codes, ``lit - base`` literal rebasing folded at trace time, int64
accumulators over SF-scale row counts — and every failure mode there is
*silent* wraparound, not a crash. This module is the sixth abstract interpreter over the
planner's decomposition (sibling to plan/exec/mem/conc/perf) and proves,
host-only and per statement:

(a) **codec fit** — every column a streamed chunk scan uploads narrow
    provably fits its chosen width: the static value interval's span sits
    inside the FOR int16/int32 window exactly like
    ``io/columnar.plan_column_codec`` requires, and the model's priced
    encoded width (:func:`mem_audit.encoded_type_width`) never under-prices
    the statically provable codec width;
(b) **accumulator fit** — no SUM/COUNT/AVG accumulator can exceed its
    carrying range at the audited scale factor: the pre-aggregation row
    bound (the SAME union-find join formula ``mem_audit._audit_graph``
    enforces, via the shared helpers) times the argument's interval
    magnitude stays below int64 for the exact integer/decimal lanes
    (``ops.agg_sum`` / the ``kernels.segment_sum_exact`` limb path) and
    below the f64-exact-integer range (2^53) for the float-accumulated
    integer AVG lane (``ops._agg_avg_impl``);
(c) **hash-bit budget** — the partition/shard routing of ``hash_mix``
    consumes ``log2(P)`` low bits plus the next ``log2(S)`` bits
    (``engine/stream.py``: ``pids = h & (P-1)``,
    ``dest = (h >> log2(P)) & (S-1)``): the windows are disjoint by
    construction and the audit proves their sum never exceeds the mixed
    32-bit width at any legal (P, S) — the env readers clamp both knobs to
    the partition search ceiling (:data:`mem_audit._MAX_PARTITIONS`), so
    8 + 8 bits is the legal maximum;
(d) **scale preservation** — decimal scales survive encoded-space
    comparison and aggregate rescaling exactly: every ``× 10^Δ`` scale
    unification the engine performs in int64 (``exprs._align_decimals`` /
    ``_unify``) is proven not to overflow at the operands' static bounds,
    and decimal SUM keeps its argument scale (``dec(38, s)``) while AVG
    divides the exact int64 sum once in f64.

Interval abstraction: one ``[lo, hi]`` integer interval per column in
SCALED space (a ``decimal(p, s)`` column is the integer interval
``±(10^p - 1)`` at scale ``s`` — its device representation), seeded from
schema dtypes, the spec-fixed value domains
(:data:`mem_audit.SPEC_INT_DOMAINS` / ``ROW_BOUND_DOMAINS``) and the
table row bounds; intervals propagate through projections, set ops,
CASE/COALESCE and int64 arithmetic (each ``+``/``-``/``×`` site itself
checked against int64), while division and double columns drop to the f64
lane whose sums are tolerance-contract approximate by engine semantics
(``ops.agg_sum`` f64 path) and are not gated.

Anything unprovable is a ``num-overflow`` / ``num-precision`` finding
gated against the shrink-only baseline (``tools/lint.py`` eighth pass),
and every numeric claim ``io/columnar.py`` + ``engine/kernels.py`` make
in comments is an executable check here (:func:`kernel_claim_checks` /
:func:`codec_claim_checks` — rule ``num-claim``), not reviewer prose.

Lockstep (the standing rule): ``tools/num_audit_diff.py`` builds
adversarial boundary-value tables (FOR spans at the 2^15/2^31 edges,
4096-distinct dictionaries, max-scale decimals, hot hash keys), drives
the A/B sweep across base/sharded/encoded-off arms demanding
bit-for-bit equality with the plain-width reference, and requires exact
agreement between these static verdicts and the runtime overflow-flag
evidence (``StreamEvent.reason``); ``tools/bench_compare.py --audit-num``
re-checks a recorded campaign ledger's evidence the same way.
"""

from __future__ import annotations

import math
import os
from dataclasses import dataclass
from fractions import Fraction

import numpy as np

from nds_tpu.analysis import Finding
from nds_tpu.analysis.exec_audit import (CLASS_COMPILED, CLASS_UNKNOWN,
                                         ExecAuditor, _AUDIT_SEED,
                                         _conjuncts_of, _has_subquery)
from nds_tpu.analysis.mem_audit import (ROW_BOUND_DOMAINS, SPEC_INT_DOMAINS,
                                        MemAuditor, MemModel, _batch_unique_side,
                                        _bucket, _equi_sides, _table_pk,
                                        statement_needed_names,
                                        stream_partitions_env,
                                        stream_shards_env)
from nds_tpu.queries import (TEMPLATE_DIR, instantiate_template,
                             list_templates, load_template)
from nds_tpu.schema import (decimal_precision_scale, get_schemas, is_decimal,
                            is_string)
from nds_tpu.sql import ast as A
from nds_tpu.sql.parser import AGG_FUNCS, ParseError, parse

# ---------------------------------------------------------------------------
# numeric ranges (the carrying capacities every proof compares against)
# ---------------------------------------------------------------------------

I64_MAX = (1 << 63) - 1        # int64 accumulators / threshold scalars
F64_EXACT = 1 << 53            # largest range where every int is exact f64
FOR16_SPAN = 1 << 15           # plan_column_codec: int16 FOR iff span < 2^15
FOR32_SPAN = (1 << 31) - 1     # int32 FOR iff span < 2^31 - 1 (8 B logical)
HASH_BITS = 32                 # stream._hash_mix produces a uint32
# mirror of engine/exprs._MAX_DEC_SCALE (jax-free here by design; the
# lockstep unit test pins the two constants equal)
MAX_DEC_SCALE = 10


# ---------------------------------------------------------------------------
# exact threshold math (value space -> stored/encoded space)
# ---------------------------------------------------------------------------
#
# Ordered comparisons against a rational boundary q reduce to integer
# thresholds on the stored representation:
#
#   v <  q   <=>   v <= ceil(q) - 1
#   v <= q   <=>   v <= floor(q)
#   v >  q   <=>   v >= floor(q) + 1
#   v >= q   <=>   v >= ceil(q)
#   v =  q   <=>   v == q     (only when q is integral, else FALSE)
#   v <> q   <=>   v != q     (only when q is integral, else TRUE)


def value_cmp(op: str, q: Fraction):
    """Entry kind + integer threshold of ``value OP q`` in VALUE space:
    ``("ieq"|"ine"|"ile"|"ige", T)`` or ``("true",)`` / ``("false",)``."""
    if op == "=":
        return ("ieq", int(q)) if q.denominator == 1 else ("false",)
    if op == "<>":
        return ("ine", int(q)) if q.denominator == 1 else ("true",)
    if op == "<":
        return ("ile", math.ceil(q) - 1)
    if op == "<=":
        return ("ile", math.floor(q))
    if op == ">":
        return ("ige", math.floor(q) + 1)
    if op == ">=":
        return ("ige", math.ceil(q))
    raise ValueError(f"not a comparison op: {op}")


_EPOCH = np.datetime64("1970-01-01", "D")


def parse_days(text: str) -> int | None:
    """Days-since-epoch of a date string, or None when unparseable —
    numerically identical to ``engine/exprs._parse_date`` (both go
    through ``np.datetime64``)."""
    try:
        return int((np.datetime64(str(text), "D") - _EPOCH).astype(int))
    except Exception:
        return None


# ---------------------------------------------------------------------------
# the interval abstraction
# ---------------------------------------------------------------------------

# the float lane marker: doubles, divisions, AVG results — engine f64
# semantics, tolerance-contract approximate, never gated for exactness
F64 = "f64"


class IVal:
    """Closed integer interval ``[lo, hi]`` in scaled space: the abstract
    value of one int-lane column/expression, where a decimal at scale
    ``s`` is represented by its scaled int64 (``value × 10^s``) exactly
    like ``engine/column.py`` lowers it. Host Python ints — the analysis
    itself can never wrap.

    ``mass`` (optional) bounds ``Σ|v|`` over ALL rows of the producing
    relation — the key that keeps re-aggregation proofs linear: a SUM
    output column carries ``mass = rows × max|arg|``, and any later
    SUM/AVG over those group sums accumulates ``≤ Σ|group sums| ≤ mass``
    (triangle inequality) instead of multiplying by the outer row bound
    again. Mass survives subsetting (filters, group-by, DISTINCT,
    outer-join null extension — nulls add zero) and concatenation
    (masses add across UNION branches / CASE arms), but NOT replication:
    resolving a column in a multi-part join scope strips it."""

    __slots__ = ("lo", "hi", "scale", "mass")

    def __init__(self, lo: int, hi: int, scale: int = 0, mass=None):
        self.lo, self.hi, self.scale = int(lo), int(hi), int(scale)
        self.mass = None if mass is None else int(mass)

    @property
    def span(self) -> int:
        return self.hi - self.lo

    @property
    def abs_max(self) -> int:
        return max(abs(self.lo), abs(self.hi))

    def union(self, other: "IVal") -> "IVal":
        """Value union with additive mass: sound for concatenation
        (UNION arms) and per-row selection (CASE/COALESCE arms) alike;
        conservative for intersect/except (true mass only shrinks)."""
        s = max(self.scale, other.scale)
        a, b = self.at_scale(s), other.at_scale(s)
        mass = a.mass + b.mass \
            if a.mass is not None and b.mass is not None else None
        return IVal(min(a.lo, b.lo), max(a.hi, b.hi), s, mass)

    def at_scale(self, s: int) -> "IVal":
        """Rescaled interval (×10^Δ, Δ ≥ 0) — caller checks int64 fit."""
        if s == self.scale:
            return self
        m = 10 ** (s - self.scale)
        return IVal(self.lo * m, self.hi * m, s,
                    None if self.mass is None else self.mass * m)

    def __repr__(self):
        return f"IVal({self.lo}, {self.hi}, s={self.scale})"


# value domains the dsdgen generator fixes but the schema types do not
# express (customer_demographics is the full cartesian product; each
# dependents counter is generated in 0..6). Interval-only knowledge —
# deliberately NOT added to mem_audit.SPEC_INT_DOMAINS, which also
# prices encoded widths; kept slack by an order of magnitude.
NUM_INT_DOMAINS = {
    "cd_dep_count": 100,
    "cd_dep_employed_count": 100,
    "cd_dep_college_count": 100,
    "c_birth_year": 10_000,          # calendar year (generator: 1924-92)
    "c_birth_month": 100,
    "c_birth_day": 100,
}

# sequential-surrogate FK columns whose value domain is the referenced
# dimension's row bound (dsdgen generates dimension surrogate keys as
# 1..N): the ROW_BOUND_DOMAINS mechanism, extended num-audit-locally for
# group keys that appear WITHOUT their dimension joined (query77 groups
# catalog sales/returns by call-center key alone)
NUM_FK_DOMAINS = {
    "cs_call_center_sk": "call_center",
    "cr_call_center_sk": "call_center",
}


def column_interval(col: str, t: str, row_bounds: dict) -> IVal | None:
    """The static seed interval of one catalog column, or None when the
    type carries no provable bound (plain int64, strings, doubles). The
    SAME static knowledge :func:`mem_audit.encoded_type_width` prices
    from — by construction the two can only drift if one changes."""
    if is_decimal(t):
        p, s = decimal_precision_scale(t)
        m = 10 ** p - 1
        return IVal(-m, m, s)
    if is_string(t) or t == "double":
        return None
    dom = SPEC_INT_DOMAINS.get(col)
    if dom is None:
        dom = NUM_INT_DOMAINS.get(col)
    if dom is None and col in ROW_BOUND_DOMAINS:
        dom = row_bounds.get(ROW_BOUND_DOMAINS[col])
    if dom is None and col in NUM_FK_DOMAINS:
        dom = row_bounds.get(NUM_FK_DOMAINS[col])
    if dom is not None:
        return IVal(0, int(dom), 0)
    if t in ("int32", "date"):
        # storage-sound: the device lowering is int32
        return IVal(-(1 << 31), (1 << 31) - 1, 0)
    return None                    # plain int64: unbounded


def codec_width_verdict(iv: IVal | None, logical_bytes: int):
    """``(code_bytes, mode)`` the FOR codec provably chooses for a column
    whose whole-table values sit inside ``iv`` — the static mirror of the
    ``plan_column_codec`` width rules (span < 2^15 ⇒ int16 codes;
    span < 2^31 - 1 on an 8-byte logical ⇒ int32) — or None when no
    narrow width is provable without data (the dict codec needs a
    distinct count only the runtime has)."""
    if iv is None:
        return None
    if iv.span < FOR16_SPAN:
        return 2, "for-int16"
    if iv.span < FOR32_SPAN and logical_bytes == 8:
        return 4, "for-int32"
    return None


# ---------------------------------------------------------------------------
# report types
# ---------------------------------------------------------------------------


@dataclass
class NumCheck:
    """One discharged (or failed) numeric-safety obligation."""

    kind: str                  # codec | rebase | agg | arith | scale | hash-bits | claim
    subject: str               # column / expression / site description
    proven: bool
    rule: str = "num-overflow"  # finding rule when unproven
    detail: str = ""

    def to_dict(self) -> dict:
        return {"kind": self.kind, "subject": self.subject,
                "proven": self.proven, "rule": self.rule,
                "detail": self.detail}


@dataclass
class NumReport:
    """All numeric-safety checks of one template statement."""

    file: str
    query: str
    classification: str
    checks: tuple = ()
    detail: str = ""

    @property
    def proven(self) -> bool:
        return all(c.proven for c in self.checks)

    @property
    def proven_safe(self) -> bool:
        """Statement is compiled-stream AND every check proved: the static
        verdict the runtime overflow-flag evidence must agree with (a
        proven-safe statement showing an overflow rerun — or an unproven
        one that the differential arms never trip — is model drift)."""
        return self.classification == CLASS_COMPILED and self.proven

    def to_dict(self) -> dict:
        return {"file": self.file, "query": self.query,
                "classification": self.classification,
                "proven": self.proven, "proven_safe": self.proven_safe,
                "checks": [c.to_dict() for c in self.checks],
                "detail": self.detail}


# ---------------------------------------------------------------------------
# the interpreter
# ---------------------------------------------------------------------------


class _NRel:
    """One FROM part of the interval walk: per-alias column intervals plus
    the row bound / source / uniqueness metadata the shared join-bound
    formula needs. ``uniq`` holds frozensets of bare column names each of
    which is a unique key of the relation (base-table PK, a plain GROUP
    BY key set, DISTINCT output, or the empty frozenset for a single-row
    relation); ``mass_safe`` is set per SELECT once the join shape proves
    this part's rows are never replicated (see ``_mark_mass_safety``)."""

    __slots__ = ("cols", "rows", "source", "chunked", "single_row",
                 "uniq", "mass_safe")

    def __init__(self, alias: str, cols: dict, rows: int, source=None,
                 chunked=False, single_row=False, uniq=None):
        self.cols = {alias.lower(): dict(cols)}
        self.rows = max(int(rows), 1)
        self.source = source
        self.chunked = chunked
        self.single_row = single_row
        self.uniq = set(uniq or ())
        self.mass_safe = False

    @property
    def alias(self) -> str:
        return next(iter(self.cols))

    def colset(self) -> set:
        return {f"{a}.{c}" for a, cols in self.cols.items() for c in cols}

    def lookup(self, ref: A.ColumnRef):
        """(found, ival) — found distinguishes a known column with an
        unbounded interval (None) from an unresolved reference."""
        name = ref.name.lower()
        if ref.table:
            cols = self.cols.get(ref.table.lower())
            if cols is not None and name in cols:
                return True, cols[name]
            return False, None
        for cols in self.cols.values():
            if name in cols:
                return True, cols[name]
        return False, None


class NumAuditor:
    """Host-only value-range/precision interpreter.

    Composes :class:`ExecAuditor` (routing classification) and
    :class:`MemAuditor` (partition/shard choices per streamed scan) over
    the same decomposition — the perf_audit pattern — and walks the AST
    once more carrying interval + scale abstractions. ``streamed`` /
    ``model`` / ``base_tables`` follow the sibling auditors."""

    def __init__(self, streamed=None, model: MemModel | None = None,
                 base_tables=None, catalog: dict | None = None):
        self.model = model or MemModel()
        self.mem = MemAuditor(streamed=streamed, model=self.model,
                              base_tables=base_tables)
        self.exec = ExecAuditor(catalog=catalog, streamed=streamed,
                                base_tables=base_tables,
                                mem_model=self.model)
        self.streamed = self.mem.streamed
        self.base_tables = self.mem.base_tables
        self.ivals = {
            t: {f.name.lower(): column_interval(
                f.name.lower(), f.type, self.model.row_bounds)
                for f in fields}
            for t, fields in get_schemas(use_decimal=True).items()}
        # device f64 lanes: doubles and every column with no int seed
        # still EXIST in the scope (interval None = unbounded int lane;
        # doubles are tracked as the f64 marker)
        self.kinds = {
            t: {f.name.lower(): f.type for f in fields}
            for t, fields in get_schemas(use_decimal=True).items()}

    # -- entry point --------------------------------------------------------

    def audit_sql(self, sql: str, file: str = "<sql>",
                  query: str = "<sql>") -> NumReport:
        er = self.exec.audit_sql(sql, file=file, query=query)
        if er.classification == CLASS_UNKNOWN:
            return NumReport(file, query, er.classification,
                             detail=er.detail)
        mr = self.mem.audit_sql(sql, file=file, query=query)
        try:
            stmt = parse(sql)
        except ParseError as e:
            return NumReport(file, query, CLASS_UNKNOWN, detail=str(e))
        self._checks: list = []
        self._seen: set = set()
        self._needed = statement_needed_names(stmt)
        try:
            if isinstance(stmt, A.Query):
                self._walk_query(stmt, self._base_env())
            elif isinstance(stmt, (A.InsertInto, A.CreateTempView)):
                self._walk_query(stmt.query, self._base_env())
            # DeleteFrom: no narrow arithmetic — nothing to prove
        except RecursionError:
            return NumReport(file, query, er.classification,
                             detail="recursion limit")
        for s in mr.scans:
            self._check_hash_bits(s.table, s.partitions, s.shards)
        return NumReport(file, query, er.classification,
                         checks=tuple(self._checks))

    # -- check plumbing -----------------------------------------------------

    def _check(self, kind: str, subject: str, proven: bool,
               detail: str = "", rule: str = "num-overflow") -> None:
        key = (kind, subject, proven, detail)
        if key in self._seen:
            return
        self._seen.add(key)
        self._checks.append(NumCheck(kind, subject, proven, rule, detail))

    def _check_hash_bits(self, table: str, partitions: int,
                         shards: int) -> None:
        p_bits = max(int(partitions).bit_length() - 1, 0)
        s_bits = max(int(shards).bit_length() - 1, 0)
        ok = p_bits + s_bits <= HASH_BITS
        self._check(
            "hash-bits", f"{table} P={partitions} S={shards}", ok,
            f"route bits {p_bits}+{s_bits} "
            + ("fit" if ok else "EXCEED") + f" the mixed {HASH_BITS}-bit "
            "hash (disjoint windows: pids = h & (P-1), "
            "dest = (h >> log2 P) & (S-1))")

    # -- environment --------------------------------------------------------

    def _base_env(self) -> dict:
        env = {}
        for name, cols in self.kinds.items():
            ivs = {}
            for c, t in cols.items():
                if t == "double":
                    ivs[c] = F64
                else:
                    ivs[c] = self.ivals[name].get(c)
            rows = self.model.table_rows(name) or 1
            pk = _table_pk(name)
            uniq = {frozenset(pk)} if pk else set()
            env[name] = (ivs, rows, name in self.base_tables, name, uniq)
        return env

    # -- query / set-op walk ------------------------------------------------

    def _walk_query(self, q: A.Query, env: dict):
        env = dict(env)
        for cname, cq in q.ctes:
            cols, rows, uniq = self._walk_query(cq, env)
            env[cname.lower()] = (cols, rows, False, None, uniq)
        cols, rows, uniq = self._walk_body(q.body, env)
        if q.limit is not None:
            rows = min(rows, max(int(q.limit), 0))
        return cols, max(rows, 1), uniq

    def _walk_body(self, body, env: dict):
        if isinstance(body, A.SetOp):
            lcols, lrows, _lu = self._walk_body(body.left, env)
            rcols, rrows, _ru = self._walk_body(body.right, env)
            rows = lrows if body.op in ("intersect", "except") \
                else lrows + rrows
            # positional interval union (set-op columns align by position;
            # a length mismatch would have failed plan_audit already);
            # concatenation voids any uniqueness, masses add
            cols = {}
            rvals = list(rcols.values())
            for i, (name, liv) in enumerate(lcols.items()):
                riv = rvals[i] if i < len(rvals) else None
                if isinstance(liv, IVal) and isinstance(riv, IVal):
                    cols[name] = liv.union(riv)
                elif liv == F64 and riv == F64:
                    cols[name] = F64
                else:
                    cols[name] = None
            return cols, rows, set()
        if isinstance(body, A.Query):
            return self._walk_query(body, env)
        return self._walk_select(body, env)

    # -- SELECT -------------------------------------------------------------

    def _walk_select(self, sel: A.Select, env: dict):
        where = _conjuncts_of(sel.where)
        parts, preds, outer_mult = self._flatten_from(sel.from_, env)
        conjuncts = list(preds) + list(where)
        for c in conjuncts:
            self._walk_subqueries(c, env)
        if sel.having is not None:
            self._walk_subqueries(sel.having, env)
        for item in sel.items:
            if not isinstance(item.expr, A.Star):
                self._walk_subqueries(item.expr, env)

        if parts:
            self._mark_mass_safety(parts, conjuncts)
            rows = self._join_rows(parts, conjuncts) * outer_mult
            self._check_conjuncts(parts, conjuncts)
        else:
            rows = 1
        preagg_rows = max(rows, 1)

        # aggregate accumulator proofs at THIS select's pre-agg row bound
        agg_exprs = list(i.expr for i in sel.items
                         if not isinstance(i.expr, A.Star))
        if sel.having is not None:
            agg_exprs.append(sel.having)
        has_agg = False
        for e in agg_exprs:
            for call in self._agg_calls(e):
                has_agg = True
                self._check_agg(call, parts, preagg_rows)

        if sel.group_by is not None:
            gb = sel.group_by
            dom = 1
            for e in gb.exprs:
                d = self._domain(e, parts, rows)
                dom = min(dom * max(d, 1), max(rows, 1))
            n_sets = max(len(gb.sets), 1) if gb.kind != "plain" else 1
            rows = min(rows, max(dom, 1)) * n_sets
        elif has_agg and all(self._agg_only(i.expr) for i in sel.items
                             if not isinstance(i.expr, A.Star)):
            rows = 1

        # projection: output intervals
        cols: dict = {}
        for i, item in enumerate(sel.items):
            e = item.expr
            if isinstance(e, A.Star):
                qual = e.table and e.table.lower()
                for p in parts:
                    for a, pc in p.cols.items():
                        if qual is None or a == qual:
                            cols.update(pc)
                continue
            if item.alias:
                name = item.alias.lower()
            elif isinstance(e, A.ColumnRef):
                name = e.name.lower()
            else:
                name = f"_c{i}"
            cols[name] = self._ival(e, parts, preagg_rows)
        if sel.distinct and cols:
            d = 1
            for iv in cols.values():
                card = iv.span + 1 if isinstance(iv, IVal) else rows
                d = min(d * max(card, 1), max(rows, 1))
            rows = min(rows, max(d, 1))

        # output uniqueness: a plain GROUP BY whose keys survive the
        # projection is a unique key set; DISTINCT makes the whole row
        # unique; a keyless aggregate yields the single-row frozenset()
        uniq: set = set()
        if sel.group_by is not None and sel.group_by.kind == "plain":
            names = [e.name.lower() for e in sel.group_by.exprs
                     if isinstance(e, A.ColumnRef)]
            if len(names) == len(sel.group_by.exprs) \
                    and all(n in cols for n in names):
                uniq.add(frozenset(names))
        elif sel.group_by is None and has_agg and rows == 1:
            uniq.add(frozenset())
        if sel.distinct and cols:
            uniq.add(frozenset(cols))
        return cols, max(rows, 1), uniq

    def _agg_only(self, e) -> bool:
        """True when every value path of the item flows through an
        aggregate (keyless aggregate ⇒ single output row, mirroring
        ``mem_audit._has_aggregate_items``)."""
        if isinstance(e, A.FuncCall) and e.name.lower() in AGG_FUNCS:
            return True
        if isinstance(e, A.ColumnRef):
            return False
        kids = [c for c in vars(e).values() if isinstance(c, A.Expr)] \
            if hasattr(e, "__dataclass_fields__") else []
        return all(self._agg_only(c) for c in kids) if kids else True

    def _domain(self, e, parts, rows: int) -> int:
        """Distinct-value bound of one group key: at most the key's
        interval width AND the producing part's rows (a dimension column
        cannot take more values than the dimension has rows)."""
        if isinstance(e, A.ColumnRef):
            for p in parts:
                found, iv = p.lookup(e)
                if found:
                    if isinstance(iv, IVal):
                        return min(iv.span + 1, p.rows, max(rows, 1))
                    return p.rows
        return rows

    # -- FROM flattening ----------------------------------------------------

    def _flatten_from(self, node, env: dict, outer_mult: int = 1):
        """(parts, join conjuncts, outer multiplier). Outer joins flatten
        into the same part list with their ON conjuncts as edges plus a
        sound row multiplier: ×2 covers the null-extended extras of a
        LEFT/RIGHT join even when its batch is PK-unique (pairs + extras
        ≤ 2 × max side), ×4 covers FULL (pairs + both extras)."""
        if node is None:
            return [], [], outer_mult
        if isinstance(node, A.TableRef):
            return [self._table_rel(node, env)], [], outer_mult
        if isinstance(node, A.SubqueryRef):
            cols, rows, uniq = self._walk_query(node.query, env)
            return [_NRel(node.alias, cols, rows, single_row=rows == 1,
                          uniq=uniq)], [], outer_mult
        if isinstance(node, A.Join):
            lp, lj, outer_mult = self._flatten_from(node.left, env,
                                                    outer_mult)
            rp, rj, outer_mult = self._flatten_from(node.right, env,
                                                    outer_mult)
            conjs = _conjuncts_of(node.condition)
            if node.kind == "full":
                outer_mult *= 4
            elif node.kind in ("left", "right"):
                outer_mult *= 2
            # semi/anti never grow the left side; flattening both sides
            # with the ON edges keeps the bound sound (result ≤ joined)
            return lp + rp, lj + rj + conjs, outer_mult
        if isinstance(node, A.Query):          # parenthesized join tree
            return self._flatten_from(getattr(node.body, "from_", None),
                                      env, outer_mult)
        return [], [], outer_mult

    def _table_rel(self, node: A.TableRef, env: dict) -> _NRel:
        name = node.name.lower()
        alias = (node.alias or node.name).lower()
        ivs, rows, is_base, source, uniq = env.get(
            name, ({}, 1, False, None, set()))
        return _NRel(alias, ivs, rows, source=source if is_base else None,
                     chunked=is_base and name in self.streamed,
                     single_row=rows == 1 and not is_base, uniq=uniq)

    # -- the shared join-row bound (mem_audit._audit_graph formula) ---------

    def _join_rows(self, parts, conjuncts) -> int:
        """UNCLAMPED joined-row bound of one flattened graph: per
        component the largest member row bound, times the enforced
        ``bucket × fanout`` for every equi batch with no PK-unique side;
        components multiply. Identical arithmetic to
        ``mem_audit._audit_graph`` via the shared helpers — but without
        the accumulator clamp, because an overflow-rerun statement
        re-aggregates the SAME rows eagerly, so the accumulator ceiling
        never bounds what a SUM can see."""
        part_cols = [p.colset() for p in parts]
        sources = [p.source for p in parts]
        batches: dict = {}
        for c in conjuncts:
            if _has_subquery(c):
                continue
            e = _equi_sides(c, part_cols)
            if e is not None:
                batches.setdefault(tuple(sorted(e[:2])), []).append(e)
        parent = list(range(len(parts)))

        def find(x):
            while parent[x] != x:
                parent[x] = parent[parent[x]]
                x = parent[x]
            return x

        for (a, b) in batches:
            parent[find(a)] = find(b)
        comp_rows: dict = {}
        for i, p in enumerate(parts):
            r = find(i)
            base = 1 if p.single_row else max(p.rows, 1)
            comp_rows[r] = max(comp_rows.get(r, 1), base)
        chunked_idx = [i for i, p in enumerate(parts) if p.chunked]
        keep = max(chunked_idx, key=lambda i: parts[i].rows) \
            if chunked_idx else -1
        for (a, b), batch in batches.items():
            if not (_batch_unique_side(part_cols, sources, keep, a, b,
                                       batch)
                    or self._subq_unique_side(parts, a, b, batch)):
                r = find(a)
                comp_rows[r] = _bucket(comp_rows[r]) * self.model.fanout
        rows = 1
        for r in comp_rows.values():
            rows *= r
        return rows

    @staticmethod
    def _batch_keys(side: int, batch) -> set:
        keys = set()
        for (li, ri, lk, rk) in batch:
            k = lk if li == side else (rk if ri == side else None)
            if k is not None:
                keys.add(k)
        return keys

    def _side_unique(self, part: _NRel, keys: set) -> bool:
        """True when ``keys`` (bare column names) cover a unique key of
        the relation: a declared uniqueness set (GROUP BY keys, DISTINCT
        output, frozenset() for single-row) or the base-table PK — so a
        join on those keys matches each opposite row at most once."""
        if part.single_row:
            return True
        if any(u <= keys for u in part.uniq):
            return True
        pk = _table_pk(part.source)
        return pk is not None and set(pk) <= keys

    def _subq_unique_side(self, parts, a: int, b: int, batch) -> bool:
        """The derived-relation extension of ``_batch_unique_side``: a
        subquery part unique on its batch keys (its GROUP BY output)
        bounds the edge's multiplicity exactly like a base PK. Chunked
        parts are excluded for the same masked-PK-plan reason."""
        for side in (a, b):
            p = parts[side]
            if p.chunked or p.source:
                continue               # base tables: _batch_unique_side
            if self._side_unique(p, self._batch_keys(side, batch)):
                return True
        return False

    def _mark_mass_safety(self, parts, conjuncts) -> None:
        """Mark the parts whose rows provably appear at most once in the
        joined relation, so their columns' ``mass`` bounds survive: a
        single part trivially; in a two-part graph, a part is safe when
        the OPPOSITE side is unique on its join keys (each row matches
        ≤ 1 opposite row; the join conjunction can only filter further),
        including the no-edge cross join against a single-row relation.
        Wider graphs conservatively strip mass."""
        for p in parts:
            p.mass_safe = len(parts) == 1
        if len(parts) != 2:
            return
        part_cols = [p.colset() for p in parts]
        batch = []
        for c in conjuncts:
            if _has_subquery(c):
                continue
            e = _equi_sides(c, part_cols)
            if e is not None:
                batch.append(e)
        for i in (0, 1):
            other = parts[1 - i]
            parts[i].mass_safe = self._side_unique(
                other, self._batch_keys(1 - i, batch))

    # -- conjunct checks: codec fit, literal rebase, compare rescale --------

    def _check_conjuncts(self, parts, conjuncts) -> None:
        for p in parts:
            if p.chunked and p.source:
                self._check_codecs(p)
        for c in conjuncts:
            if _has_subquery(c):
                continue
            if isinstance(c, A.BinaryOp) and c.op in ("=", "<>", "<",
                                                      "<=", ">", ">="):
                self._check_compare(c, parts)
            elif isinstance(c, A.Between):
                self._check_between(c, parts)
            elif isinstance(c, A.InList):
                self._check_inlist(c, parts)

    def _check_codecs(self, rel: _NRel) -> None:
        table = rel.source
        kinds = self.kinds.get(table, {})
        enc = self.model.enc_widths.get(table, {}) if self.model.encoded \
            else {}
        for col, t in kinds.items():
            if self._needed is not None and col not in self._needed:
                continue
            iv = self.ivals.get(table, {}).get(col)
            logical = 4 if t in ("int32", "date") else 8
            verdict = codec_width_verdict(iv, logical)
            if verdict is None:
                continue
            width, mode = verdict
            # codes = value - lo ∈ [0, span] fit the chosen dtype by the
            # span rule itself; the obligation left is that the model's
            # priced encoded width never UNDER-prices the provable codec
            priced = enc.get(col)
            ok = priced is None or priced >= width + 1
            self._check(
                "codec", f"{table}.{col}", ok,
                f"{mode}: span {iv.span} codes fit {width} B"
                + ("" if ok else
                   f" but the model prices {priced} B — encoded width "
                   "model under-prices the provable codec"))

    def _chunk_for_col(self, ref: A.ColumnRef, parts):
        """(table, col, interval, verdict) when ``ref`` resolves to a
        streamed chunk column with a provable FOR width."""
        for p in parts:
            found, iv = p.lookup(ref)
            if not found:
                continue
            if not (p.chunked and p.source) or not isinstance(iv, IVal):
                return None
            t = self.kinds.get(p.source, {}).get(ref.name.lower())
            logical = 4 if t in ("int32", "date") else 8
            v = codec_width_verdict(iv, logical)
            return (p.source, ref.name.lower(), iv, v) if v else None
        return None

    def _lit_fraction(self, lit, scale: int):
        """Scaled-space Fraction of a literal (the exact boundary an
        encoded compare rebases), or None for non-numeric literals."""
        if isinstance(lit, A.DateLiteral):
            d = parse_days(lit.text)
            return None if d is None else Fraction(d) * 10 ** scale
        if not isinstance(lit, A.Literal):
            return None
        v = lit.value
        if isinstance(v, bool) or v is None:
            return None
        if isinstance(v, str):
            d = parse_days(v)
            return None if d is None else Fraction(d) * 10 ** scale
        try:
            return Fraction(v) * 10 ** scale
        except (TypeError, ValueError):
            return None

    def _check_rebase(self, table: str, col: str, iv: IVal, op: str,
                      q: Fraction) -> None:
        """Prove the FOR-rebased threshold arithmetic exact: the
        value-space threshold (:func:`value_cmp`) and its worst-case
        rebase ``T - base`` (base ∈ [lo, hi]) must fit int64 — the
        bound under which the saturating trace-time fold
        (``exprs._encoded_compare_views``) is exact."""
        entry = value_cmp(op, q)
        if entry[0] in ("true", "false"):
            self._check("rebase", f"{table}.{col} {op} {q}", True,
                        f"degenerate: folds to {entry[0]}")
            return
        t = entry[1]
        worst = max(abs(t - iv.lo), abs(t - iv.hi), abs(t))
        ok = worst <= I64_MAX
        self._check(
            "rebase", f"{table}.{col} {op} {q}", ok,
            f"threshold {t}, rebased |T - base| ≤ {worst} "
            + ("fits int64" if ok else "OVERFLOWS int64"))

    def _check_compare(self, c: A.BinaryOp, parts) -> None:
        sides = ((c.left, c.right, c.op),
                 (c.right, c.left,
                  {"<": ">", "<=": ">=", ">": "<", ">=": "<=",
                   "=": "=", "<>": "<>"}[c.op]))
        for ref, other, op in sides:
            if not isinstance(ref, A.ColumnRef):
                continue
            got = self._chunk_for_col(ref, parts)
            if got is not None and isinstance(other,
                                              (A.Literal, A.DateLiteral)):
                table, col, iv, _v = got
                q = self._lit_fraction(other, iv.scale)
                if q is not None:
                    self._check_rebase(table, col, iv, op, q)
            break
        # decimal-scale unification of a column-column compare: the
        # smaller-scale side multiplies by 10^Δ in int64
        # (exprs._align_decimals) — prove it cannot wrap
        if isinstance(c.left, A.ColumnRef) and \
                isinstance(c.right, A.ColumnRef):
            la = self._ival(c.left, parts, 1)
            ra = self._ival(c.right, parts, 1)
            if isinstance(la, IVal) and isinstance(ra, IVal) \
                    and la.scale != ra.scale:
                s = max(la.scale, ra.scale)
                worst = max(la.at_scale(s).abs_max, ra.at_scale(s).abs_max)
                ok = worst <= I64_MAX
                self._check(
                    "scale",
                    f"{c.left.name.lower()} {c.op} {c.right.name.lower()}",
                    ok,
                    f"rescale to s={s}: |v| ≤ {worst} "
                    + ("fits int64" if ok else "OVERFLOWS int64"))

    def _check_between(self, c: A.Between, parts) -> None:
        if not isinstance(c.expr, A.ColumnRef):
            return
        got = self._chunk_for_col(c.expr, parts)
        if got is None:
            return
        table, col, iv, _v = got
        for lit, op in ((c.low, ">="), (c.high, "<=")):
            q = self._lit_fraction(lit, iv.scale)
            if q is not None:
                self._check_rebase(table, col, iv, op, q)

    def _check_inlist(self, c: A.InList, parts) -> None:
        if not isinstance(c.expr, A.ColumnRef):
            return
        got = self._chunk_for_col(c.expr, parts)
        if got is None:
            return
        table, col, iv, _v = got
        for it in c.items:
            q = self._lit_fraction(it, iv.scale)
            if q is not None:
                self._check_rebase(table, col, iv, "=", q)

    # -- aggregates ---------------------------------------------------------

    def _agg_calls(self, e):
        """Aggregate FuncCalls of one expression tree, not descending
        into subqueries (those run their own select walk)."""
        if isinstance(e, (A.InSubquery, A.ScalarSubquery, A.Exists,
                          A.QuantifiedCompare)):
            return
        if isinstance(e, A.FuncCall) and e.name.lower() in AGG_FUNCS:
            yield e
            return                     # engine rejects nested aggregates
        if hasattr(e, "__dataclass_fields__"):
            for v in vars(e).values():
                if isinstance(v, A.Expr):
                    yield from self._agg_calls(v)
                elif isinstance(v, (list, tuple)):
                    for x in v:
                        if isinstance(x, A.Expr):
                            yield from self._agg_calls(x)

    def _check_agg(self, call: A.FuncCall, parts, rows: int) -> None:
        name = call.name.lower()
        subject = self._edesc(call)
        if name == "count":
            ok = rows <= I64_MAX
            self._check("agg", subject, ok,
                        f"≤ {rows:,} rows in an int64 count")
            return
        if name not in ("sum", "avg"):
            return                     # min/max/stddev: no exact-integer
            #                            accumulation to prove
        arg = call.args[0] if call.args else None
        if arg is None:
            return
        iv = self._ival(arg, parts, rows)
        if iv == F64:
            # engine f64 lane (doubles, divisions): approximate by the
            # tolerance contract (ops.agg_sum f64 path) — nothing exact
            # to prove, and nothing silently wrong to gate
            return
        if not isinstance(iv, IVal):
            self._check("agg", subject, False,
                        "argument interval unprovable: accumulator range "
                        "cannot be bounded at the audited scale")
            return
        bound = rows * iv.abs_max
        if iv.mass is not None:
            # mass is a bound on Σ|v| over ALL producing rows, and it
            # only survives subset/concat paths — so it bounds the
            # accumulator directly, without the row multiplication
            bound = min(bound, iv.mass)
        if name == "sum" or iv.scale > 0:
            # exact int64 accumulation (ops._agg_sum_impl; the decimal
            # AVG divides the exact int64 sum once in f64)
            ok = bound <= I64_MAX
            self._check(
                "agg", subject, ok,
                f"{rows:,} rows × |v| ≤ {iv.abs_max:,} (s={iv.scale}) "
                + ("fits int64" if ok else "OVERFLOWS int64"))
        else:
            # integer AVG accumulates f64 terms (ops._agg_avg_impl):
            # exact only inside the f64 integer range
            ok = bound < F64_EXACT
            self._check(
                "agg", subject, ok,
                f"{rows:,} rows × |v| ≤ {iv.abs_max:,} "
                + ("within" if ok else "EXCEEDS")
                + " the f64-exact integer range (2^53)",
                rule="num-precision")

    # -- expression intervals ------------------------------------------------

    def _edesc(self, e) -> str:
        if isinstance(e, A.ColumnRef):
            return e.name.lower()
        if isinstance(e, A.Literal):
            return repr(e.value)
        if isinstance(e, A.FuncCall):
            inner = "*" if e.star else ", ".join(
                self._edesc(a) for a in e.args[:2])
            return f"{e.name.lower()}({inner})"
        if isinstance(e, A.Cast):
            return self._edesc(e.expr)
        if isinstance(e, A.BinaryOp):
            return (f"{self._edesc(e.left)} {e.op} "
                    f"{self._edesc(e.right)}")
        if isinstance(e, A.Case):
            return "case"
        return type(e).__name__.lower()

    def _ival(self, e, parts, rows: int):
        """Abstract value of one expression: IVal (int lane), F64 (float
        lane) or None (unbounded int lane). Each int64 arithmetic site is
        itself checked — the engine computes +,-,× in int64 and WRAPS."""
        if isinstance(e, A.Literal):
            v = e.value
            if isinstance(v, bool) or v is None or isinstance(v, str):
                return None
            if isinstance(v, int):
                # a zero literal has zero mass (Σ|0| = 0 over any rows):
                # keeps COALESCE(x, 0) / CASE ... ELSE 0 mass-bounded
                return IVal(v, v, 0, mass=0 if v == 0 else None)
            if isinstance(v, float):
                return F64
            # Decimal: exact scaled integer
            q = Fraction(v)
            s = 0
            while q.denominator != 1 and s < MAX_DEC_SCALE:
                q *= 10
                s += 1
            return IVal(int(q), int(q), s) if q.denominator == 1 else F64
        if isinstance(e, A.DateLiteral):
            d = parse_days(e.text)
            return None if d is None else IVal(d, d, 0)
        if isinstance(e, A.IntervalLiteral):
            return IVal(e.amount, e.amount, 0) if e.unit == "day" else None
        if isinstance(e, A.ColumnRef):
            for p in parts:
                found, iv = p.lookup(e)
                if found:
                    if isinstance(iv, IVal) and iv.mass is not None \
                            and not p.mass_safe:
                        # the join shape could replicate this part's
                        # rows — Σ|v| over the joined rows is unbounded
                        # by the source mass, so strip it
                        return IVal(iv.lo, iv.hi, iv.scale)
                    return iv
            return None
        if isinstance(e, A.UnaryOp):
            iv = self._ival(e.operand, parts, rows)
            if e.op == "-" and isinstance(iv, IVal):
                return IVal(-iv.hi, -iv.lo, iv.scale, iv.mass)
            return iv if e.op == "-" else None
        if isinstance(e, A.Cast):
            t = e.target.lower()
            iv = self._ival(e.expr, parts, rows)
            if t in ("double", "float"):
                return F64
            if is_decimal(t):
                _p, s = decimal_precision_scale(t)
                if isinstance(iv, IVal) and s >= iv.scale:
                    out = iv.at_scale(s)
                    ok = out.abs_max <= I64_MAX
                    self._check("scale", self._edesc(e), ok,
                                f"cast rescale to s={s}: |v| ≤ "
                                f"{out.abs_max:,} "
                                + ("fits int64" if ok
                                   else "OVERFLOWS int64"))
                    return out
                return None            # down-scale / unbounded: unknown
            return iv
        if isinstance(e, A.Case):
            # a null arm (explicit ELSE NULL or missing ELSE) contributes
            # no value: nulls are excluded from aggregates and compares
            arms = [r for _c, r in e.branches]
            if e.else_ is not None:
                arms.append(e.else_)
            arms = [a for a in arms
                    if not (isinstance(a, A.Literal) and a.value is None)]
            out = None
            for iv in (self._ival(a, parts, rows) for a in arms):
                if iv == F64:
                    return F64
                if not isinstance(iv, IVal):
                    return None
                out = iv if out is None else out.union(iv)
            return out
        if isinstance(e, A.FuncCall):
            return self._func_ival(e, parts, rows)
        if isinstance(e, A.BinaryOp):
            return self._arith_ival(e, parts, rows)
        if isinstance(e, A.ScalarSubquery):
            return None                # walked separately; value unknown
        return None

    def _func_ival(self, e: A.FuncCall, parts, rows: int):
        name = e.name.lower()
        if name == "count":
            return IVal(0, max(rows, 1), 0)
        if name in ("sum", "min", "max"):
            arg = self._ival(e.args[0], parts, rows) if e.args else None
            if not isinstance(arg, IVal):
                return arg
            if name in ("min", "max"):
                return arg
            # a (possibly windowed) SUM over these rows: |any partial
            # sum| ≤ Σ|v| — the argument's mass when it has one, else
            # rows × max|v|; that same quantity is the result's mass
            mass = arg.mass if arg.mass is not None \
                else rows * arg.abs_max
            return IVal(-mass if arg.lo < 0 else 0,
                        mass if arg.hi > 0 else 0, arg.scale, mass)
        if name in ("avg", "stddev", "stddev_samp", "var_samp",
                    "variance"):
            return F64
        if name == "coalesce":
            out = None
            for a in e.args:
                iv = self._ival(a, parts, rows)
                if iv == F64:
                    return F64
                if not isinstance(iv, IVal):
                    return None
                out = iv if out is None else out.union(iv)
            return out
        if name == "abs" and e.args:
            iv = self._ival(e.args[0], parts, rows)
            if isinstance(iv, IVal):
                return IVal(0, iv.abs_max, iv.scale, iv.mass)
            return iv
        return None

    def _arith_ival(self, e: A.BinaryOp, parts, rows: int):
        if e.op not in ("+", "-", "*", "/", "%"):
            return None                # comparison / boolean: not numeric
        a = self._ival(e.left, parts, rows)
        b = self._ival(e.right, parts, rows)
        if e.op == "/":
            return F64                 # engine divides on the f64 lane
        if a == F64 or b == F64:
            return F64
        if not isinstance(a, IVal) or not isinstance(b, IVal):
            return None
        subject = self._edesc(e)
        if e.op in ("+", "-"):
            s = max(a.scale, b.scale)
            ra, rb = a.at_scale(s), b.at_scale(s)
            ok = max(ra.abs_max, rb.abs_max) <= I64_MAX
            if s > max(a.scale, b.scale) or a.scale != b.scale:
                self._check("scale", subject, ok,
                            f"unify to s={s}: operands "
                            + ("fit int64" if ok else "OVERFLOW int64"))
            # triangle inequality: Σ|a ± b| ≤ Σ|a| + Σ|b|
            mass = ra.mass + rb.mass \
                if ra.mass is not None and rb.mass is not None else None
            if e.op == "+":
                out = IVal(ra.lo + rb.lo, ra.hi + rb.hi, s, mass)
            else:
                out = IVal(ra.lo - rb.hi, ra.hi - rb.lo, s, mass)
            ok2 = out.abs_max <= I64_MAX
            self._check("arith", subject, ok2,
                        f"|result| ≤ {out.abs_max:,} "
                        + ("fits int64" if ok2 else "OVERFLOWS int64"))
            return out
        if e.op == "*":
            s = a.scale + b.scale
            if s > MAX_DEC_SCALE:
                return F64             # engine falls to the float lane
            prods = [a.lo * b.lo, a.lo * b.hi, a.hi * b.lo, a.hi * b.hi]
            # Σ|a·b| ≤ max|a| × Σ|b| (and symmetrically)
            mcands = [x for x in
                      (a.abs_max * b.mass if b.mass is not None else None,
                       b.abs_max * a.mass if a.mass is not None else None)
                      if x is not None]
            out = IVal(min(prods), max(prods), s,
                       min(mcands) if mcands else None)
            ok = out.abs_max <= I64_MAX
            self._check("arith", subject, ok,
                        f"|product| ≤ {out.abs_max:,} (s={s}) "
                        + ("fits int64" if ok else "OVERFLOWS int64"))
            return out
        # %: bounded by the divisor magnitude (dividend sign)
        m = b.abs_max
        return IVal(-m, m, 0) if a.scale == b.scale == 0 else None

    # -- subqueries ---------------------------------------------------------

    def _walk_subqueries(self, e, env: dict) -> None:
        def walk(node):
            if isinstance(node, (A.InSubquery, A.ScalarSubquery, A.Exists,
                                 A.QuantifiedCompare)):
                self._walk_query(node.query, env)
                return
            if hasattr(node, "__dataclass_fields__"):
                for v in vars(node).values():
                    if isinstance(v, A.Expr):
                        walk(v)
                    elif isinstance(v, (list, tuple)):
                        for x in v:
                            if isinstance(x, A.Expr):
                                walk(x)

        walk(e)


# ---------------------------------------------------------------------------
# claim checks: every numeric comment in io/columnar.py + engine/kernels.py
# ---------------------------------------------------------------------------


def kernel_claim_checks() -> list:
    """Executable versions of ``engine/kernels.py``'s numeric-safety
    claims (host arithmetic only — no jax import). Each failed check is a
    ``num-claim`` finding: the comment would be lying."""
    checks = []

    def claim(subject, ok, detail):
        checks.append(NumCheck("claim", subject, bool(ok), "num-claim",
                               detail))

    # K1 — limb kernel: "a per-cell partial is <= 512*255 < 2^17 so the
    # f32 dot is exact" (f32 integers are exact below 2^24)
    claim("limb-partial-exact", 512 * 255 < (1 << 17) < (1 << 24),
          "per-cell limb partial 512×255 stays f32-exact")
    # K2 — "cross-tile accumulation happens in an i32 output ref (exact
    # while n*255 < 2^31 => n < 2^23 rows — the one gate)", and
    # exact_sum_supported gates at n_rows < 2^23
    claim("limb-i32-accumulator", ((1 << 23) - 1) * 255 < (1 << 31) - 1,
          "i32 limb accumulation exact under the n < 2^23 row gate")
    # K3 — two's-complement limb recombination is the identity for ANY
    # int64 (7 unsigned byte limbs + signed arithmetic-shift top limb)
    ok3 = True
    for v in (0, 1, -1, 255, 256, -256, (1 << 62) + 12345,
              -(1 << 62) - 999, (1 << 63) - 1, -(1 << 63)):
        x = np.int64(v)
        limbs = [int((x >> np.int64(8 * k)) & np.int64(255))
                 for k in range(7)]
        limbs.append(int(x >> np.int64(56)))       # signed top limb
        total = sum(l << (8 * k) for k, l in enumerate(limbs))
        ok3 = ok3 and total == v
    claim("limb-recombination", ok3,
          "sum_l limb_l << 8l reproduces every int64 bit-exactly")
    # K4 — "the f32 MXU kernel above cannot carry [exact int64]
    # (24-bit mantissa)": 2^24 + 1 is the first unrepresentable int
    claim("f32-mantissa-limit",
          int(np.float32((1 << 24) + 1)) != (1 << 24) + 1
          and int(np.float32(1 << 24)) == (1 << 24),
          "2^24 + 1 is not f32-representable; 2^24 is")
    # K5 — "counts are exactly representable in f32 below 2^24 rows"
    # (ops.agg_count's kernel gate)
    claim("count-f32-gate",
          int(np.float32((1 << 24) - 1)) == (1 << 24) - 1,
          "every count below the 2^24 row gate is f32-exact")
    # K6 — hash route-bit budget at the max LEGAL (P, S): the shared env
    # readers clamp both knobs to the partition search ceiling, so the
    # disjoint bit windows always fit the mixed 32-bit hash
    os_p = os.environ.get("NDS_TPU_STREAM_PARTITIONS")
    os_s = os.environ.get("NDS_TPU_STREAM_SHARDS")
    try:
        os.environ["NDS_TPU_STREAM_PARTITIONS"] = str(1 << 40)
        os.environ["NDS_TPU_STREAM_SHARDS"] = str(1 << 40)
        p_max = stream_partitions_env()
        s_max = stream_shards_env()
    finally:
        for k, v in (("NDS_TPU_STREAM_PARTITIONS", os_p),
                     ("NDS_TPU_STREAM_SHARDS", os_s)):
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    bits = (p_max.bit_length() - 1) + (s_max.bit_length() - 1)
    claim("hash-route-bits",
          bits <= HASH_BITS and p_max.bit_length() - 1 < HASH_BITS,
          f"clamped max P={p_max}, S={s_max}: {bits} route bits ≤ "
          f"{HASH_BITS}, pshift < {HASH_BITS}")
    return checks


def codec_claim_checks() -> list:
    """Executable versions of ``io/columnar.py``'s codec claims, driven
    through the REAL ``plan_column_codec`` on boundary-value arrays."""
    import pyarrow as pa

    from nds_tpu.io.columnar import DICT_MAX_VALUES, plan_column_codec
    checks = []

    def claim(subject, ok, detail):
        checks.append(NumCheck("claim", subject, bool(ok), "num-claim",
                               detail))

    def plan(values, t="int64", arrow_type=None):
        arr = pa.array(values, type=arrow_type or pa.int64())
        return plan_column_codec(arr, t)

    # C1 — "decimal(7,2) always fits int32 by type": scaled span
    # 2×(10^7 - 1) < 2^31 - 1; p=9 is the widest int32-provable precision
    claim("decimal-int32-by-type",
          2 * (10 ** 7 - 1) < FOR32_SPAN and 2 * (10 ** 9 - 1) < FOR32_SPAN
          and 2 * (10 ** 10 - 1) >= FOR32_SPAN,
          "p ≤ 9 scaled decimals always FOR-encode int32; p = 10 does not")
    from decimal import Decimal
    ext = Decimal(10 ** 7 - 1) / 100
    got = plan([-ext, ext], "decimal(7,2)", pa.decimal128(7, 2))
    claim("decimal-extremes-int32",
          got is not None and got[0].dtype == np.int32
          and got[2].mode == "for"
          and int(got[0][1]) + got[2].base == 10 ** 7 - 1,
          "full-range decimal(7,2) extremes FOR-encode as int32 and "
          "round-trip the scaled value bit-exactly")
    # C2 — FOR int16 edge: span 2^15 - 1 fits, span 2^15 does not
    lo = 5_000_000
    got = plan([lo, lo + FOR16_SPAN - 1])
    ok = got is not None and got[0].dtype == np.int16 \
        and int(got[0][1]) + got[2].base == lo + FOR16_SPAN - 1
    claim("for-int16-edge-fits", ok,
          "span 2^15 - 1 FOR-encodes int16 and round-trips bit-exactly")
    got = plan([lo, lo + FOR16_SPAN])
    claim("for-int16-edge-refuses",
          got is not None and got[0].dtype == np.int32,
          "span 2^15 widens to int32 (int16 refused)")
    # int32 edge on an 8-byte logical: span 2^31 - 2 fits, 2^31 - 1 spills
    got = plan([0, FOR32_SPAN - 1])
    claim("for-int32-edge-fits",
          got is not None and got[0].dtype == np.int32,
          "span 2^31 - 2 FOR-encodes int32")
    got = plan([0, FOR32_SPAN])
    claim("for-int32-edge-refuses",
          got is None or got[2].mode == "dict",
          "span 2^31 - 1 refuses FOR (narrow-width overflow guard)")
    # C3 — dict edge: 4096 distinct wide-span values encode int16 codes
    # clipped into [0, 4096); 4097 distinct refuse (overflow guard)
    vals = [v * (1 << 40) for v in range(DICT_MAX_VALUES)]
    got = plan(vals)
    ok = got is not None and got[2].mode == "dict" \
        and got[0].dtype == np.int16 \
        and int(got[0].max()) == DICT_MAX_VALUES - 1 \
        and int(got[0].min()) == 0
    claim("dict-4096-fits", ok,
          "4096 distinct values dict-encode; top code 4095 is a valid "
          "value-table index (take mode='clip' cannot read past it)")
    got = plan(vals + [(DICT_MAX_VALUES + 7) * (1 << 40)])
    claim("dict-4097-refuses", got is None,
          "4097 distinct values exceed DICT_MAX_VALUES (overflow guard)")
    # C4 — all-null / empty: trivial FOR int16 zeros (never under-priced)
    got = plan([None, None, None])
    claim("all-null-trivial-for",
          got is not None and got[2].mode == "for" and got[2].base == 0
          and got[0].dtype == np.int16 and int(got[0].max()) == 0,
          "all-null column FOR-encodes as int16 zeros")
    # C5 — order preservation: FOR and dict codes sort like their values
    got = plan([40, 10, 30, 20])
    ok = got is not None and got[2].mode == "for" \
        and list(np.argsort(got[0])) == [1, 3, 2, 0]
    vals = [-3, 5, 99, 10 ** 12]
    got2 = plan([vals[i] for i in (3, 0, 2, 1)])
    ok2 = got2 is not None and got2[2].mode == "dict" \
        and list(np.argsort(got2[0])) == [1, 3, 2, 0]
    claim("order-preserving", ok and ok2,
          "FOR and dict codes preserve value order (encoded-space "
          "compares and min/max stay exact)")
    return checks


# ---------------------------------------------------------------------------
# corpus driver + lint-gate findings
# ---------------------------------------------------------------------------


def audit_num_template_text(text: str, file: str,
                            auditor: NumAuditor | None = None) -> list:
    """Instantiate one template (pinned seed, shared with the other
    auditors) and prove each statement; returns NumReports."""
    auditor = auditor or NumAuditor()
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


def audit_num_corpus(template_dir: str | None = None,
                     streamed=None, model: MemModel | None = None) -> list:
    """NumReports for every template in templates.lst order."""
    template_dir = template_dir or TEMPLATE_DIR
    auditor = NumAuditor(streamed=streamed, model=model)
    reports: list = []
    for name in list_templates(template_dir):
        reports.extend(audit_num_template_text(
            load_template(name, template_dir), name, auditor))
    return reports


def reports_to_findings(reports) -> list:
    """Lint-gate findings: every unproven check is a ``num-overflow`` /
    ``num-precision`` finding (rule per check); proven checks are a
    report (``--num-report``), not findings."""
    findings = []
    for r in reports:
        for c in r.checks:
            if c.proven:
                continue
            findings.append(Finding(
                r.file, r.query, c.rule, "error",
                f"{c.kind} {c.subject}: {c.detail}"))
    return findings


def claim_findings() -> list:
    """``num-claim`` findings from the executable claim checks — empty
    while every numeric comment in io/columnar.py + engine/kernels.py
    tells the truth."""
    findings = []
    for c, file in ([(c, "engine/kernels.py")
                     for c in kernel_claim_checks()]
                    + [(c, "io/columnar.py")
                       for c in codec_claim_checks()]):
        if not c.proven:
            findings.append(Finding(
                file, "<claims>", c.rule, "error",
                f"{c.subject}: {c.detail}"))
    return findings


def num_audit_findings(template_dir: str | None = None) -> list:
    """The lint pass entry point (tools/lint.py eighth pass): corpus
    interval proofs plus the codec/kernel claim checks."""
    return reports_to_findings(audit_num_corpus(template_dir)) \
        + claim_findings()


def check_counts(reports) -> dict:
    """``check kind -> (proven, total)`` histogram over the corpus."""
    counts: dict = {}
    for r in reports:
        for c in r.checks:
            p, t = counts.get(c.kind, (0, 0))
            counts[c.kind] = (p + (1 if c.proven else 0), t + 1)
    return counts


def format_num_report(reports) -> str:
    """The per-template proof table (``tools/lint.py --num-report``)."""
    lines = ["# num-audit: per-statement value-range/precision proofs",
             "# checks: codec fit, literal rebase, accumulator range, "
             "arith/scale sites, hash route bits",
             f"{'template':<18} {'class':<16} {'checks':>7} "
             f"{'proven':>7}  worst unproven"]
    for r in reports:
        bad = [c for c in r.checks if not c.proven]
        worst = f"{bad[0].kind} {bad[0].subject}" if bad else "-"
        lines.append(f"{r.query:<18} {r.classification:<16} "
                     f"{len(r.checks):>7} "
                     f"{sum(1 for c in r.checks if c.proven):>7}  {worst}")
    counts = check_counts(reports)
    summary = ", ".join(f"{k}: {p}/{t}"
                        for k, (p, t) in sorted(counts.items()))
    n_safe = sum(1 for r in reports if r.proven_safe)
    lines.append(f"# {len(reports)} statements — {summary}; "
                 f"{n_safe} proven-safe compiled-stream")
    return "\n".join(lines)
