# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Static HBM-footprint auditor: prove per-statement memory bounds on host.

The streaming executor used to guard device memory with a *guess*: a global
survivor-accumulator ceiling (``NDS_TPU_STREAM_ACC_ROWS``, default 2^23)
plus a device-side overflow flag that throws away a whole streamed run and
re-executes eagerly. This module is the third abstract interpreter over the
planner's decomposition — sibling to :mod:`plan_audit` (name/type
resolution) and :mod:`exec_audit` (control path + sync bounds) — and
answers, host-only and with no device in the loop, for every statement of
every template:

1. **How many bytes can it ever hold on device?** A conservative
   *peak-HBM byte bound* composed from:

   * **dtype widths** from :mod:`nds_tpu.schema` through the planner's
     column pruning (only columns the statement references anywhere are
     ever uploaded; a ``SELECT *`` disables pruning, conservatively, for
     the whole statement). Widths mirror the device representation of
     :mod:`nds_tpu.engine.column`: int32/date = 4 B, int64/double and
     scaled-decimal = 8 B, strings = 4 B dictionary codes (value tables
     live on host), plus 1 B validity per row — exactly the shapes
     ``ChunkedTable.padded_chunks`` materializes. Under ENCODED
     execution (``NDS_TPU_ENCODED``, default on) streamed chunks are
     priced at the statically-provable narrow widths instead
     (:func:`encoded_type_width`: ``decimal(p<=9)`` -> 4+1 B,
     spec-bounded quantities -> 2+1 B, ticket numbers -> 4+1 B at the
     audited scale), mirroring the runtime codecs of
     ``io/columnar.plan_column_codec`` — conservatively: a column the
     model cannot prove narrow is priced plain even when the runtime
     (which sees real stats) encodes it.
   * **cardinality bounds propagated through joins**: a join batch whose
     keys cover the non-streamed side's declared (composite) primary key
     on a pristine base-table scan is unique on that side — output rows
     stay bounded by the fact side. Every other batch is bounded by the
     stream-bounds pair bucket the runtime enforces
     (probe-bucket × ``NDS_TPU_STREAM_FANOUT``; inside the compiled
     pipeline exceeding it raises the device overflow flag, so the bound
     is *enforced*, not estimated). Unconnected components multiply
     (cartesian layout — exact product).
   * **filters**: no reduction assumed (a filter may keep every row).
   * **group-bys**: output bounded by the product of the group keys'
     value domains (a base-table column's domain is at most its table's
     row bound) clamped at input rows.

2. **How large can a streamed scan's survivor accumulator grow?** The
   per-scan *accumulator row bound*: ``min(n_chunks × per-chunk output
   bucket, bucket_len(table rows) × fanout^k)`` where ``k`` counts the
   join batches that may fan out survivor rows
   (:func:`stream_graph_fanout`). This is the number the runtime now
   **sizes the accumulator from** (``engine/stream.py``): a statement
   whose proven bound fits the HBM capacity model can never trip the
   overflow rerun, and `exec_audit` reclassifies its former
   ``accumulator-overflow`` fallback to ``compiled-stream`` in lockstep.

3. **When the whole-statement bound exceeds capacity, can a grace-style
   partition decomposition admit it?** A streamed graph whose survivor
   bound is past ``NDS_TPU_HBM_BYTES`` but which joins on plain equi
   keys is hash-partitioned by the executor: every chunk row lands in
   exactly one of ``P`` partitions (join-key hash), each partition
   drives the same compiled per-chunk program into its OWN accumulator,
   and the *per-partition bound* is
   ``min(n_chunks × per-chunk bucket × fanout^k,
   bucket_len(ceil(rows / P) × skew) × fanout^k)``
   (:func:`partition_row_bound`; ``skew`` = ``NDS_TPU_STREAM_SKEW``,
   default 2 — hash partitions are only probabilistically even, so the
   proof is skew-conditional and the runtime ENFORCES it with a
   per-partition overflow flag: a hotter-than-assumed partition reruns
   eagerly, correctness never rides the proof). The partition count is
   chosen STATICALLY from the proof (:func:`choose_partitions` —
   smallest power of two whose per-partition bound fits capacity;
   ``NDS_TPU_STREAM_PARTITIONS`` pins it), so it joins the pipeline
   cache key. The ``hbm-capacity`` gate then tests the per-partition
   bound — which is what retired the 7 fan-out findings
   (q17/q24×2/q25/q29/q64/q72) from the baseline.

The capacity model is ``NDS_TPU_HBM_BYTES`` (default 16 GiB, one v5-lite
chip); the cardinality model is a conservative SF10 row-bound table
(:data:`DEFAULT_ROW_BOUNDS`), both parameterizable per :class:`MemModel`.

**The model is a checked contract.** ``tools/mem_audit_diff.py`` replays
the ``test_synccount`` A/B templates through the real engine and fails
when a measured survivor count or materialized byte volume ever exceeds
the static bound (soundness), and proves the gate can fail via
``--inject-drift`` — the same lockstep rule that ties ``exec_audit`` to
the executor's routing. **When you change the planner's join bounds,
``ChunkedTable`` chunk shapes, or the schema widths, update this model in
the same PR**; ``tests/test_analysis.py`` runs both in tier-1.

The lint gate (``hbm-capacity``, ``tools/lint.py``) fails any
device-resident statement whose peak bound exceeds the configured
capacity, and any streamed statement whose accumulator bound exceeds it;
``--mem-report`` prints the per-statement table.
"""

from __future__ import annotations

import math
import os
from dataclasses import dataclass, replace

import numpy as np

from nds_tpu.analysis import Finding
from nds_tpu.analysis.exec_audit import (_children, _column_refs,
                                         _conjuncts_of, _has_subquery)
from nds_tpu.analysis.plan_audit import _single_row_query
from nds_tpu.queries import (TEMPLATE_DIR, instantiate_template,
                             list_templates, load_template)
from nds_tpu.schema import (COMPOSITE_PRIMARY_KEYS, PRIMARY_KEYS,
                            decimal_precision_scale, get_schemas,
                            is_decimal, is_string)
from nds_tpu.sql import ast as A
from nds_tpu.sql.parser import ParseError, parse

# HBM capacity model: the proof budget every per-statement bound is gated
# against (and the admission test for proof-sized stream accumulators).
# Default: one v5-lite chip's 16 GiB.
DEFAULT_HBM_BYTES = 16 << 30


def hbm_capacity_bytes() -> int:
    """The configured device-memory capacity (``NDS_TPU_HBM_BYTES``)."""
    return int(os.environ.get("NDS_TPU_HBM_BYTES", str(DEFAULT_HBM_BYTES)))


# Conservative SF10 row-count upper bounds (TPC-DS spec scaling, rounded
# UP — the audit must never under-bound a cardinality). The static
# stand-in for the arrow row counts a live session would know exactly;
# parameterizable per MemModel (tools/mem_audit_diff.py passes the toy
# session's real counts).
DEFAULT_ROW_BOUNDS = {
    "call_center": 30,
    "catalog_page": 12_100,
    "catalog_returns": 1_500_000,
    "catalog_sales": 14_500_000,
    "customer": 500_000,
    "customer_address": 250_000,
    "customer_demographics": 1_920_800,
    "date_dim": 73_049,
    "household_demographics": 7_200,
    "income_band": 20,
    "inventory": 133_200_000,
    "item": 102_000,
    "promotion": 500,
    "reason": 45,
    "ship_mode": 20,
    "store": 102,
    "store_returns": 2_900_000,
    "store_sales": 28_900_000,
    "time_dim": 86_400,
    "warehouse": 10,
    "web_page": 200,
    "web_returns": 800_000,
    "web_sales": 7_300_000,
    "web_site": 42,
}


def _pow2_ceil(n: int) -> int:
    return 1 << max(int(n) - 1, 1).bit_length() if n > 2 else 2


def _bucket(n: int) -> int:
    """Mirror of ``ops.bucket_len``: smallest power-of-two capacity >= n
    with the same ``NDS_TPU_MIN_BUCKET`` floor — the audit's row bounds
    must round exactly like the engine's physical buckets."""
    floor = _pow2_ceil(int(os.environ.get("NDS_TPU_MIN_BUCKET", "16")))
    if n <= floor:
        return floor
    return 1 << (int(n) - 1).bit_length()


def type_width(t: str) -> int:
    """Device bytes per row of one column of canonical type ``t``,
    validity byte included — mirrors ``engine/column.py``'s lowering
    (int32/date -> int32, decimals -> scaled int64, strings -> int32
    dictionary codes with a host-side value table)."""
    if is_string(t):
        return 4 + 1
    if is_decimal(t):
        return 8 + 1
    if t in ("int32", "date"):
        return 4 + 1
    return 8 + 1                       # int64 / double / unknown


# ---------------------------------------------------------------------------
# encoded columnar execution: the static width model of the streamed path
# ---------------------------------------------------------------------------
#
# The streamed scan path uploads int-path columns in a NARROW encoded
# representation (io/columnar.plan_column_codec: frame-of-reference /
# sorted-dict), and survivors stay encoded through the accumulator, so
# the widths the proof prices shrink with the data. The RUNTIME chooses
# widths from whole-table stats; this model mirrors that choice from
# static knowledge only — schema types plus spec-fixed value domains at
# the audited scale — and is deliberately conservative: a column the
# model cannot prove narrow statically is priced at its plain width even
# though the runtime may encode it narrower (sound for the capacity
# gate; the runtime sizes its own accumulators from the ACTUAL encoded
# dtypes, so the executor is never constrained by the model's caution).


# the ONE NDS_TPU_ENCODED gate (read at model build time like every
# other executor knob) — shared with the runtime so the model and the
# executor can never read the flag differently
from nds_tpu.io.columnar import encoded_enabled  # noqa: E402

# the ONE NDS_TPU_PREFETCH_DEPTH reader (engine/prefetch.py — stdlib
# only), shared with the runtime so the ring's live-set pricing below
# and the executor's admission arithmetic can never read the knob
# differently
from nds_tpu.engine.prefetch import prefetch_depth  # noqa: E402


# spec-fixed value-domain upper bounds (TPC-DS: quantities are 1..100,
# inventory levels 0..1000) — int64 columns a FOR encoding provably
# narrows to int16 offsets at ANY scale factor
SPEC_INT_DOMAINS = {
    "ss_quantity": 100, "cs_quantity": 100, "ws_quantity": 100,
    "sr_return_quantity": 100, "cr_return_quantity": 100,
    "wr_return_quantity": 100, "inv_quantity_on_hand": 1000,
}

# int64 sequence columns whose value domain is bounded by their table's
# row bound at the audited scale (ticket numbers are assigned per sale)
ROW_BOUND_DOMAINS = {
    "ss_ticket_number": "store_sales",
    "sr_ticket_number": "store_returns",
}


def encoded_type_width(col: str, t: str, row_bounds: dict) -> int:
    """Static streamed-chunk bytes per row of one column under encoded
    execution (validity byte included). Mirrors the runtime codec rules
    on what is provable WITHOUT data: a decimal's precision bounds its
    scaled int64 (p <= 9 always fits an int32 FOR code), and the spec /
    row-bound domains above prove int16/int32 for the quantity and
    ticket-number columns. Everything else keeps its plain width."""
    if is_decimal(t):
        p, _s = decimal_precision_scale(t)
        if p <= 9:
            return 4 + 1
        return 8 + 1
    w = type_width(t)
    dom = SPEC_INT_DOMAINS.get(col)
    if dom is None and col in ROW_BOUND_DOMAINS:
        dom = row_bounds.get(ROW_BOUND_DOMAINS[col])
    if dom is not None:
        if dom < (1 << 15):
            return min(w, 2 + 1)
        if dom < (1 << 31):
            return min(w, 4 + 1)
    return w


# ---------------------------------------------------------------------------
# shared survivor-bound core (used by engine/stream.py at pipeline build)
# ---------------------------------------------------------------------------


def _owns_key(colset, ref: A.ColumnRef) -> str | None:
    """Bare column name when a part whose lowercase ``alias.col`` key set
    is ``colset`` provides ``ref`` — mirroring the planner's qualified /
    suffix-match resolution over its internal column names."""
    name = ref.name.lower()
    if ref.table:
        return name if f"{ref.table.lower()}.{name}" in colset else None
    for c in colset:
        if c == name or c.endswith("." + name):
            return name
    return None


def _equi_sides(c, part_cols):
    """``(li, ri, lkey, rkey)`` when the conjunct is an equi edge between
    two distinct parts: a plain ``col = col`` (bare key names returned),
    or an expression-equi conjunct whose sides each live wholly in one
    part (keys None — an expression can never cover a primary key)."""
    if not (isinstance(c, A.BinaryOp) and c.op == "="):
        return None
    if isinstance(c.left, A.ColumnRef) and isinstance(c.right, A.ColumnRef):
        li = ri = None
        lk = rk = None
        for i, cols in enumerate(part_cols):
            if li is None:
                got = _owns_key(cols, c.left)
                if got:
                    li, lk = i, got
            if ri is None:
                got = _owns_key(cols, c.right)
                if got:
                    ri, rk = i, got
        if li is not None and ri is not None and li != ri:
            return li, ri, lk, rk
        return None

    def side_owner(e):
        refs = _column_refs(e)
        if not refs:
            return None
        owner = None
        for r in refs:
            cands = [i for i, cols in enumerate(part_cols)
                     if _owns_key(cols, r)]
            if len(cands) != 1:
                return None
            if owner is None:
                owner = cands[0]
            elif owner != cands[0]:
                return None
        return owner

    li, ri = side_owner(c.left), side_owner(c.right)
    if li is not None and ri is not None and li != ri:
        return li, ri, None, None
    return None


def _table_pk(src: str | None):
    if not src:
        return None
    pk = COMPOSITE_PRIMARY_KEYS.get(src)
    if pk is None and src in PRIMARY_KEYS:
        pk = (PRIMARY_KEYS[src],)
    return pk


def _batch_unique_side(part_cols, sources, keep, a, b, batch) -> bool:
    """True when one side of the (a, b) edge batch is unique on its join
    keys: the side is a pristine base-table scan whose bare key-name set
    covers its declared (composite) primary key. When the batch touches
    the streamed slot (``keep``), only the OTHER side counts — per-chunk
    multiplicity is bounded by the non-chunk side's uniqueness, and the
    executor masks chunk-side PK plans anyway (their host key ranges
    would bake chunk data into the chunk-invariant program)."""
    cands = [s for s in (a, b) if s != keep] if keep in (a, b) else [a, b]
    for side in cands:
        pk = _table_pk(sources[side])
        if pk is None:
            continue
        keys = set()
        for (li, ri, lk, rk) in batch:
            k = lk if li == side else (rk if ri == side else None)
            if k is not None:
                keys.add(k)
        if keys >= set(pk):
            return True
    return False


def stream_graph_fanout(part_cols, sources, keep, conjuncts):
    """Conservative survivor-multiplicity exponent ``k`` of a streamed
    join graph, or None when the multiplicity is unprovable.

    ``part_cols`` is the per-part set of lowercase ``alias.col`` column
    keys, ``sources`` the per-part pristine catalog table name (None for
    derived relations), ``keep`` the streamed part's index, ``conjuncts``
    the join predicates + WHERE conjuncts (AST expressions).

    The survivor rows of the whole streamed graph are then bounded by
    ``bucket_len(streamed table rows) × fanout^k``: each of the ``k``
    join batches with no unique (PK-covered) side is clamped at runtime
    by the stream-bounds pair bucket (probe bucket × fanout, device
    overflow flag past it), and every unique batch keeps per-row
    multiplicity at <= 1. Subquery conjuncts are FILTERS: multi-pass
    streaming pre-plans their inner tables into device residuals and the
    conjunct reduces to a membership/compare mask over joined rows —
    never growing them — so they do not affect the bound. Returns None
    when some part is not connected to the streamed slot by equi edges
    (cartesian layout: a chunk-data-dependent host read, eager
    fallback)."""
    n = len(part_cols)
    batches: dict = {}
    for c in conjuncts:
        if _has_subquery(c):
            continue
        e = _equi_sides(c, part_cols)
        if e is None:
            # single-part filter, correlation, or a cross-part non-equi
            # residual: applied to joined rows, never grows them
            continue
        li, ri, lk, rk = e
        batches.setdefault(tuple(sorted((li, ri))), []).append(e)

    parent = list(range(n))

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for (a, b) in batches:
        parent[find(a)] = find(b)
    if n and any(find(i) != find(keep) for i in range(n)):
        return None
    k = 0
    for (a, b), batch in batches.items():
        if not _batch_unique_side(part_cols, sources, keep, a, b, batch):
            k += 1
    return k


def _deep_children(e):
    """Every AST expression nested in ``e``, reached through arbitrary
    dataclass / list / tuple containers (unlike ``exec_audit._children``
    this descends into non-Expr dataclasses such as WindowSpec, whose
    partition/order expressions the pruning model must see — a missed
    reference would UNDER-bound a width)."""

    def rec(v):
        if isinstance(v, A.Expr):
            yield v
        elif isinstance(v, (list, tuple)):
            for x in v:
                yield from rec(x)
        elif hasattr(v, "__dataclass_fields__"):
            for f in vars(v).values():
                yield from rec(f)

    if hasattr(e, "__dataclass_fields__"):
        for f in vars(e).values():
            yield from rec(f)


def structural_row_bound(rows: int, k: int, fanout: int) -> int:
    """``bucket_len(rows) × fanout^k`` — the structural term of the
    survivor proof. ONE definition shared by :meth:`MemModel.acc_row_bound`
    (the audit) and ``engine/stream.py._proved_row_bound`` (the runtime
    accumulator sizing), so the two can never drift apart."""
    return _bucket(max(int(rows), 1)) * (int(fanout) ** int(k))


# ---------------------------------------------------------------------------
# partitioned (grace-style) fan-out accumulation: the per-partition proof
# ---------------------------------------------------------------------------

# partition-count search ceiling: past 256 partitions the per-chunk
# dispatch fan-out dominates any accumulator saving
_MAX_PARTITIONS = 256


def _pow2_at_least(n: int) -> int:
    n = int(n)
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def stream_partitions_env() -> int | None:
    """``NDS_TPU_STREAM_PARTITIONS``: pins the partition count of every
    partitionable streamed graph (rounded up to a power of two; <= 1
    disables partitioning). Unset = the proof chooses statically
    (:func:`choose_partitions`). Read at model/pipeline BUILD time.
    Clamped to :data:`_MAX_PARTITIONS` so the partition-bit window of the
    routing hash stays inside the mixed 32-bit width at any legal setting
    (num_audit hash-bit rule: ``log2(P) + log2(S) <= 32``)."""
    env = os.environ.get("NDS_TPU_STREAM_PARTITIONS")
    return min(_pow2_at_least(int(env)), _MAX_PARTITIONS) if env else None


def stream_skew_factor() -> int:
    """``NDS_TPU_STREAM_SKEW``: the hash-skew safety factor of the
    per-partition bound (default 2 — one partition may hold up to
    ``skew ×`` its even share before the enforced overflow flag fires)."""
    return max(int(os.environ.get("NDS_TPU_STREAM_SKEW", "2")), 1)


def stream_shards_env() -> int:
    """``NDS_TPU_STREAM_SHARDS``: shard count of the streamed pipeline's
    device mesh (rounded up to a power of two; <= 1 disables sharding).
    Read at model/pipeline BUILD time like the partition knob. The audit
    models the requested count; the runtime additionally requires that
    many local devices (``parallel.exchange.stream_mesh``) and falls back
    to 1 otherwise — the differential harness closes that gap by checking
    ``StreamEvent.shards`` against the model. Clamped to
    :data:`_MAX_PARTITIONS` like the partition knob: together the two
    route windows consume at most 8 + 8 of the 32 mixed hash bits."""
    env = os.environ.get("NDS_TPU_STREAM_SHARDS")
    return min(_pow2_at_least(int(env)), _MAX_PARTITIONS) if env else 1


def shard_row_bound(rows: int, n_shards: int, n_partitions: int, k: int,
                    fanout: int, skew: int | None = None) -> int:
    """Per-shard survivor-row bound of a mesh-sharded streamed graph:
    the structural bound of one shard's skew-factored row share —
    ``rows/shards × skew`` through the fan-out exponent. Composes with
    grace-style partitioning (``n_partitions`` > 1): the partition share
    re-shares over the mesh, each level keeping its own skew allowance.
    Sound under the skew assumption; the runtime enforces it with
    per-shard overflow flags (overflow ⇒ eager rerun), exactly like
    :func:`partition_row_bound`. Shared by the audit and
    ``engine/stream.py`` — one definition, no drift."""
    if skew is None:
        skew = stream_skew_factor()
    rows = max(int(rows), 1)
    share = rows
    if n_partitions > 1:
        share = min(share, -(-share // int(n_partitions)) * int(skew))
    if n_shards > 1:
        share = min(share, -(-share // int(n_shards)) * int(skew))
    return structural_row_bound(share, k, fanout)


def partition_row_bound(rows: int, n_partitions: int, k: int, fanout: int,
                        skew: int | None = None) -> int:
    """Per-partition survivor-row bound of a hash-partitioned streamed
    graph: the structural bound of one partition's skew-factored row
    share. Sound under the skew assumption; the runtime enforces it with
    a per-partition overflow flag (overflow ⇒ eager rerun). Shared by
    the audit and ``engine/stream.py`` — one definition, no drift."""
    if skew is None:
        skew = stream_skew_factor()
    rows = max(int(rows), 1)
    share = min(rows, -(-rows // max(int(n_partitions), 1)) * int(skew))
    return structural_row_bound(share, k, fanout)


def choose_partitions(rows: int, k: int, fanout: int, row_bytes: int,
                      capacity_bytes: int, forced: int | None = None,
                      skew: int | None = None):
    """``(n_partitions, per_partition_row_bound)`` for one streamed graph.

    ``forced`` (``NDS_TPU_STREAM_PARTITIONS``) pins the count; auto picks
    the smallest power of two whose skew-factored per-partition
    accumulator bound fits ``capacity_bytes`` — statically, so the count
    can join the pipeline-cache key. ``(1, None)`` means unpartitioned:
    either the whole bound already fits, or no count up to
    ``_MAX_PARTITIONS`` admits it (the caller keeps today's legacy-clamp
    behavior)."""
    row_bytes = max(int(row_bytes), 1)
    if forced is not None:
        p = _pow2_at_least(forced)
        if p <= 1:
            return 1, None
        return p, partition_row_bound(rows, p, k, fanout, skew)
    if structural_row_bound(rows, k, fanout) * row_bytes <= capacity_bytes:
        return 1, None
    p = 2
    while p <= _MAX_PARTITIONS:
        bound = partition_row_bound(rows, p, k, fanout, skew)
        if bound * row_bytes <= capacity_bytes:
            return p, bound
        p <<= 1
    return 1, None


def stream_partition_keys(part_cols, sources, keep, conjuncts):
    """Bare chunk-side column names the partition hash keys on, or None
    when the streamed graph is not partitionable (no plain-column equi
    edge incident to the streamed slot — bare scans, expression-only
    edges; subquery conjuncts are skipped like in
    :func:`stream_graph_fanout`, they are residual-planned filters).

    Prefers a fan-out batch (no PK-unique side — the batch whose
    multiplicity forced partitioning in the first place) so rows that
    co-fan-out land in one partition; falls back to any incident equi
    batch (any chunk-row partitioning keeps the per-partition bound
    valid, since multiplicity is per-row). Deterministic: batches walk
    in sorted part order, keys return sorted."""
    batches: dict = {}
    for c in conjuncts:
        if _has_subquery(c):
            continue
        e = _equi_sides(c, part_cols)
        if e is None:
            continue
        li, ri, _lk, _rk = e
        batches.setdefault(tuple(sorted((li, ri))), []).append(e)
    best = None
    for (a, b) in sorted(batches):
        if keep not in (a, b):
            continue
        batch = batches[(a, b)]
        keys = sorted({(lk if li == keep else rk)
                       for (li, ri, lk, rk) in batch
                       if (lk if li == keep else rk) is not None})
        if not keys:
            continue
        fan_out = not _batch_unique_side(part_cols, sources, keep,
                                         a, b, batch)
        if best is None or (fan_out and not best[0]):
            best = (fan_out, tuple(keys))
    return best[1] if best else None


def statement_needed_names(stmt, catalog_cols: dict | None = None) \
        -> set | None:
    """Bare lowercase column names the statement references anywhere —
    the audit's mirror of the planner's projection pushdown
    (``Planner._collect_needed_names``) — or None when pruning is unsafe.

    ``SELECT *`` is resolved SCOPED, like the planner: a star over a
    derived table (CTE or FROM-subquery) needs nothing new (its inner
    projection is explicit and walked); a star over a catalog table adds
    that table's full column set; only a star over an unresolvable name
    disables pruning. The select list of an EXISTS / NOT EXISTS subquery
    is unobservable, so a star directly under one names nothing (its
    explicit items still do). ``catalog_cols`` maps table -> column names
    (default: the TPC-DS schema)."""
    if catalog_cols is None:
        catalog_cols = {t: [f.name for f in fields]
                        for t, fields in get_schemas(True).items()}
    names: set = set()
    disabled = [False]

    def add_table(name):
        cols = catalog_cols.get(name)
        if cols is None:
            disabled[0] = True
        else:
            names.update(c.lower() for c in cols)

    def rel_entries(f, out):
        """(alias, catalog name | None-for-derived) per FROM leaf."""
        if isinstance(f, A.TableRef):
            out.append(((f.alias or f.name).lower(), f.name.lower()))
        elif isinstance(f, A.SubqueryRef):
            out.append((f.alias.lower(), None))
        elif isinstance(f, A.Join):
            rel_entries(f.left, out)
            rel_entries(f.right, out)
        elif isinstance(f, A.Query):
            rel_entries(getattr(f.body, "from_", None), out)

    def walk_expr(e, ctes, rels):
        if isinstance(e, A.Star):
            qual = e.table and e.table.lower()
            if qual is None:
                for _alias, src in rels:
                    if src is not None and src not in ctes:
                        add_table(src)
                if not rels:
                    disabled[0] = True
            else:
                hit = [src for alias, src in rels
                       if alias == qual or src == qual]
                if hit and hit[0] is not None and hit[0] not in ctes:
                    add_table(hit[0])
                elif (hit and (hit[0] is None or hit[0] in ctes)) \
                        or qual in ctes:
                    pass               # star over a derived relation
                    #                    (subquery alias, CTE name, or an
                    #                    ALIAS over a CTE reference)
                elif qual in catalog_cols:
                    add_table(qual)
                else:
                    disabled[0] = True
            return
        if isinstance(e, A.ColumnRef):
            names.add(e.name.lower())
            return
        if isinstance(e, (A.ScalarSubquery, A.InSubquery, A.Exists,
                          A.QuantifiedCompare)):
            q = e.query
            if isinstance(e, A.Exists) and isinstance(q.body, A.Select):
                q = replace(q, body=replace(q.body, items=[
                    it for it in q.body.items
                    if not isinstance(it.expr, A.Star)]))
            walk_query(q, ctes)
            if isinstance(e, (A.InSubquery, A.QuantifiedCompare)):
                walk_expr(e.expr, ctes, rels)
            return
        for c in _deep_children(e):
            walk_expr(c, ctes, rels)

    def walk_from(f, ctes, rels):
        if isinstance(f, A.SubqueryRef):
            walk_query(f.query, ctes)
        elif isinstance(f, A.Join):
            walk_from(f.left, ctes, rels)
            walk_from(f.right, ctes, rels)
            if f.condition is not None:
                walk_expr(f.condition, ctes, rels)
        elif isinstance(f, A.Query):
            walk_from(getattr(f.body, "from_", None), ctes, rels)

    def walk_sel(sel, ctes):
        rels = []
        rel_entries(sel.from_, rels)
        walk_from(sel.from_, ctes, rels)
        for item in sel.items:
            walk_expr(item.expr, ctes, rels)
        if sel.where is not None:
            walk_expr(sel.where, ctes, rels)
        if sel.group_by is not None:
            for e in sel.group_by.exprs:
                walk_expr(e, ctes, rels)
        if sel.having is not None:
            walk_expr(sel.having, ctes, rels)

    def walk_body(b, ctes):
        if isinstance(b, A.SetOp):
            walk_body(b.left, ctes)
            walk_body(b.right, ctes)
        elif isinstance(b, A.Query):
            walk_query(b, ctes)
        else:
            walk_sel(b, ctes)

    def walk_query(q, ctes):
        ctes = set(ctes)
        for cname, cq in q.ctes:
            walk_query(cq, ctes)
            ctes.add(cname.lower())
        walk_body(q.body, ctes)
        for ent in q.order_by:
            walk_expr(ent[0], ctes, [])

    if isinstance(stmt, A.Query):
        walk_query(stmt, set())
    elif isinstance(stmt, (A.InsertInto, A.CreateTempView)):
        walk_query(stmt.query, set())
    elif isinstance(stmt, A.DeleteFrom) and stmt.where is not None:
        walk_expr(stmt.where, set(), [])
    return None if disabled[0] else names


# ---------------------------------------------------------------------------
# the capacity / cardinality model
# ---------------------------------------------------------------------------


class MemModel:
    """Capacity + cardinality model every bound is computed against.

    ``row_bounds`` maps catalog table -> row upper bound (default: the
    conservative SF10 table); ``capacity_bytes`` is the HBM budget
    (``NDS_TPU_HBM_BYTES``); ``fanout``/``chunk_rows``/``acc_ceiling``
    mirror the executor's env knobs, read at construction time so a model
    built after the environment changed sees the change (the same
    build-time discipline ``engine/stream.py`` follows)."""

    def __init__(self, row_bounds=None, capacity_bytes=None, fanout=None,
                 chunk_rows=None, acc_ceiling="env", catalog=None):
        self.row_bounds = dict(DEFAULT_ROW_BOUNDS if row_bounds is None
                               else row_bounds)
        self.capacity_bytes = (hbm_capacity_bytes() if capacity_bytes is None
                               else int(capacity_bytes))
        self.fanout = _pow2_ceil(int(
            os.environ.get("NDS_TPU_STREAM_FANOUT", "4"))
            if fanout is None else int(fanout))
        self.chunk_rows = int(
            os.environ.get("NDS_TPU_STREAM_CHUNK_ROWS", str(1 << 22))
            if chunk_rows is None else chunk_rows)
        if acc_ceiling == "env":
            env = os.environ.get("NDS_TPU_STREAM_ACC_ROWS")
            acc_ceiling = int(env) if env else None
        self.acc_ceiling = acc_ceiling
        # partitioned accumulation knobs (same build-time env discipline)
        self.partitions = stream_partitions_env()  # None = proof-chosen
        self.skew = stream_skew_factor()
        # mesh-sharded execution knob (NDS_TPU_STREAM_SHARDS): the per-
        # shard bound divides the survivor share over the mesh exactly
        # like the partition share rule (shard_row_bound)
        self.shards = stream_shards_env()
        # async-ingest knob (NDS_TPU_PREFETCH_DEPTH, engine/prefetch.py):
        # up to ``depth`` prepared chunks wait in the bounded prefetch
        # ring beyond the two the drive loop already holds — priced into
        # every streamed peak and subtracted from the capacity admission
        # decisions see (the executor mirrors this at pipeline build:
        # the lockstep rule). Depth <= 0 = ring off, priced zero.
        self.prefetch_depth = max(prefetch_depth(), 0)
        if catalog is None:
            catalog = {
                t: {f.name.lower(): type_width(f.type) for f in fields}
                for t, fields in get_schemas(use_decimal=True).items()}
        self.widths = catalog              # table -> {col -> bytes/row}
        # encoded execution (NDS_TPU_ENCODED, default on): streamed chunk
        # scans are priced at the statically-provable encoded widths —
        # the bounds (and therefore choose_partitions) shrink with the
        # data. Same build-time env discipline as the other knobs.
        self.encoded = encoded_enabled()
        if self.encoded:
            self.enc_widths = {
                t: {c: encoded_type_width(c, f.type, self.row_bounds)
                    for c, f in ((f.name.lower(), f) for f in fields)}
                for t, fields in get_schemas(use_decimal=True).items()}
        else:
            self.enc_widths = {}

    def table_rows(self, name: str) -> int | None:
        return self.row_bounds.get(name)

    def pruned_width(self, table: str, needed: set | None,
                     encoded: bool = False) -> int:
        """Bytes per row of ``table`` after the planner's column pruning
        (``needed`` = names the statement references; None disables
        pruning). An empty intersection keeps every column, exactly like
        the planner (it never prunes to zero columns). ``encoded`` prices
        the streamed-chunk representation (narrow codecs)."""
        cols = (self.enc_widths if encoded and self.encoded
                else self.widths).get(table, {})
        if not cols:
            return 9                       # unknown table: one wide column
        if needed is not None:
            kept = {c: w for c, w in cols.items() if c in needed}
            if kept and len(kept) < len(cols):
                cols = kept
        return sum(cols.values())

    def chunk_cap(self) -> int:
        return _bucket(self.chunk_rows)

    def acc_row_bound(self, stream_rows: int, k: int) -> int:
        """Proven survivor-row bound of one streamed graph: the tighter
        of the per-chunk-bucket sum and the structural
        ``bucket_len(rows) × fanout^k`` bound (both sound; the runtime
        sizes its accumulator from the same minimum)."""
        mult = self.fanout ** k
        n_chunks = max(1, math.ceil(stream_rows / self.chunk_rows))
        base = n_chunks * self.chunk_cap() * mult
        return min(base, structural_row_bound(stream_rows, k, self.fanout))

    def partition_bound(self, stream_rows: int, k: int,
                        n_partitions: int) -> int:
        """Per-partition accumulator row bound: the tighter of the
        per-chunk-bucket sum (each of a partition's dispatches still
        contributes at most one chunk output bucket) and the
        skew-factored structural share (:func:`partition_row_bound`)."""
        mult = self.fanout ** k
        n_chunks = max(1, math.ceil(stream_rows / self.chunk_rows))
        base = n_chunks * self.chunk_cap() * mult
        return min(base, partition_row_bound(stream_rows, n_partitions, k,
                                             self.fanout, self.skew))

    def ring_bytes(self, chunk_row_width: int) -> int:
        """Extra live bytes of the bounded prefetch ring: ``depth`` more
        padded chunks resident beyond the in-flight pair the chunk-bytes
        term already prices. Comes off the admitting capacity and joins
        the streamed peak — the static twin of ``stream._ring_bytes``
        (which prices the ACTUAL first-chunk upload bytes; this model
        prices the conservative ``chunk_cap × pruned width``)."""
        return self.prefetch_depth * self.chunk_cap() \
            * max(int(chunk_row_width), 0)

    def admit_capacity(self, chunk_row_width: int) -> int:
        """Capacity the streamed admission decisions compare against:
        ``NDS_TPU_HBM_BYTES`` minus the prefetch ring's live set."""
        return max(self.capacity_bytes - self.ring_bytes(chunk_row_width),
                   1)

    def bare_scan_fits(self, table: str | None, needed: set | None) -> bool:
        """Can a bare streamed scan of ``table`` (no filter, no join: the
        survivor accumulator keeps every row) be proven to fit? True when
        the proven accumulator bound fits the capacity model (net of the
        prefetch ring's live set) AND the env ceiling (if one is set)
        admits the table's rows — exactly the condition under which the
        runtime's proof-sized accumulator can never trip the overflow
        rerun. This is the predicate that retires
        ``accumulator-overflow`` fallbacks (`exec_audit` lockstep)."""
        rows = self.row_bounds.get(table or "")
        if rows is None:
            return False
        if self.acc_ceiling is not None and rows > self.acc_ceiling:
            return False                   # hard ceiling: overflow certain
        bound = self.acc_row_bound(rows, 0)
        w = self.pruned_width(table, needed, encoded=True)
        return bound * w <= self.admit_capacity(w)


# ---------------------------------------------------------------------------
# report types
# ---------------------------------------------------------------------------


@dataclass
class ScanBound:
    """The proven memory fate of one >HBM streamed scan."""

    alias: str
    table: str
    rows: int                  # streamed table row bound
    fanout_k: int | None       # survivor-multiplicity exponent; None =
    #                            unprovable (subquery / cartesian: the
    #                            executor falls back eager there)
    acc_rows: int | None       # proven accumulator row bound (provable)
    acc_bytes: int | None      # acc_rows x streamed-graph row width
    chunk_bytes: int = 0       # one padded chunk's bytes (x2 in flight)
    partitions: int = 1        # grace-style partition count (1 = whole)
    part_rows: int | None = None   # per-partition accumulator row bound
    part_bytes: int | None = None  # part_rows x streamed-graph row width
    shards: int = 1            # mesh shard count (NDS_TPU_STREAM_SHARDS)
    shard_rows: int | None = None  # per-shard survivor-row bound across
    #                                partitions (rows/shards x skew through
    #                                the fan-out — what StreamEvent's
    #                                shard_rows evidence is checked against)
    shard_bytes: int | None = None  # per-(partition, shard) accumulator
    #                                 unit bound x row width — the
    #                                 allocation unit a sharded pipeline's
    #                                 per-shard overflow flags enforce
    ring_bytes: int = 0        # prefetch-ring live set (depth x one
    #                            padded chunk) priced into the streamed
    #                            peak and off the admitting capacity
    #                            (NDS_TPU_PREFETCH_DEPTH; 0 = ring off)

    @property
    def provable(self) -> bool:
        return self.fanout_k is not None


@dataclass
class MemReport:
    """Peak-HBM byte bound of one template statement."""

    file: str
    query: str
    mode: str                  # "streamed" | "device" | "unknown"
    peak_bytes: int = 0
    out_rows: int = 0          # statement output row bound (soundness-
    #                            checked by tools/mem_audit_diff.py)
    scans: tuple = ()          # ScanBounds, FROM order
    detail: str = ""

    def to_dict(self) -> dict:
        return {
            "file": self.file, "query": self.query, "mode": self.mode,
            "peak_bytes": int(self.peak_bytes),
            "out_rows": int(self.out_rows),
            "scans": [{"alias": s.alias, "table": s.table,
                       "rows": int(s.rows), "fanout_k": s.fanout_k,
                       "acc_rows": None if s.acc_rows is None
                       else int(s.acc_rows),
                       "acc_bytes": None if s.acc_bytes is None
                       else int(s.acc_bytes),
                       "chunk_bytes": int(s.chunk_bytes),
                       "partitions": int(s.partitions),
                       "part_rows": None if s.part_rows is None
                       else int(s.part_rows),
                       "part_bytes": None if s.part_bytes is None
                       else int(s.part_bytes),
                       "shards": int(s.shards),
                       "shard_rows": None if s.shard_rows is None
                       else int(s.shard_rows),
                       "shard_bytes": None if s.shard_bytes is None
                       else int(s.shard_bytes),
                       "ring_bytes": int(s.ring_bytes),
                       "provable": s.provable} for s in self.scans],
            "detail": self.detail,
        }


class _MRel:
    """One relation in the walk: row bound + per-column widths and value
    domains, addressable by every alias the relation answers for (a
    materialized outer join keeps both sides' aliases, exactly like the
    planner's merged alias-qualified columns)."""

    __slots__ = ("cols", "widths", "dom", "rows", "source", "chunked",
                 "single_row", "plain_widths")

    def __init__(self, alias, widths: dict, rows: int, dom: dict | None =
                 None, source=None, chunked=False, single_row=False):
        a = alias.lower()
        self.widths = {a: dict(widths)}
        self.cols = {a: set(widths)}
        self.dom = {a: dict(dom or {c: rows for c in widths})}
        self.rows = int(rows)
        self.source = source
        self.chunked = chunked
        self.single_row = single_row
        # encoded execution: a chunked rel's ``widths`` price the narrow
        # streamed representation; ``plain_widths`` keeps the unencoded
        # widths for the paths that materialize the table whole (a
        # non-kept chunked part binds device-resident, unencoded)
        self.plain_widths = None

    @property
    def alias(self) -> str:
        return next(iter(self.cols))

    @property
    def width(self) -> int:
        return sum(w for cols in self.widths.values()
                   for w in cols.values())

    @property
    def plain_width(self) -> int:
        """Unencoded width (equals ``width`` for unencoded rels) — the
        byte size the runtime's whole-table materialization pays, and the
        keep-choice tiebreak (the executor picks by arrow nbytes)."""
        if self.plain_widths is None:
            return self.width
        return sum(self.plain_widths.values())

    def use_plain_widths(self) -> None:
        """Re-price this rel at its unencoded widths (non-kept chunked
        parts materialize whole through the plain device path)."""
        if self.plain_widths is not None:
            self.widths = {self.alias: dict(self.plain_widths)}
            self.plain_widths = None

    def colset(self) -> set:
        return {f"{a}.{c}" for a, cols in self.cols.items() for c in cols}

    def owns(self, ref: A.ColumnRef) -> str | None:
        name = ref.name.lower()
        if ref.table:
            t = ref.table.lower()
            cols = self.cols.get(t)
            return name if cols is not None and name in cols else None
        for cols in self.cols.values():
            if name in cols:
                return name
        return None

    def col_width(self, ref) -> int:
        name = ref.name.lower()
        aliases = [ref.table.lower()] if ref.table else list(self.cols)
        for a in aliases:
            w = self.widths.get(a, {}).get(name)
            if w is not None:
                return w
        return 9

    def col_domain(self, ref) -> int:
        name = ref.name.lower()
        aliases = [ref.table.lower()] if ref.table else list(self.cols)
        for a in aliases:
            d = self.dom.get(a, {}).get(name)
            if d is not None:
                return d
        return self.rows

    def merged_with(self, other: "_MRel", rows: int) -> "_MRel":
        out = _MRel(self.alias, {}, rows)
        out.cols = {**self.cols, **other.cols}
        out.widths = {**self.widths, **other.widths}
        out.dom = {**self.dom, **other.dom}
        out.rows = int(rows)
        return out


class _MemCost:
    """Accumulator for one statement walk: running peak-byte sum (a
    conservative everything-live-at-once over-approximation) plus the
    streamed-scan bounds discovered along the way."""

    def __init__(self):
        self.peak = 0
        self.scans: list = []


# ---------------------------------------------------------------------------
# the interpreter
# ---------------------------------------------------------------------------


class MemAuditor:
    """Host-only abstract interpreter computing peak-HBM byte bounds.

    ``streamed`` names the tables bound as >HBM ChunkedTables (the same
    binding model `exec_audit` uses); ``model`` carries capacities and
    cardinalities. The walk mirrors ``Planner._flatten_from`` →
    ``_join_parts`` → downstream aggregation, tracking (row bound,
    per-column width, per-column domain) per relation."""

    DEFAULT_STREAMED = ("catalog_sales", "inventory", "store_sales",
                        "web_sales")

    def __init__(self, streamed=None, model: MemModel | None = None,
                 base_tables=None):
        self.model = model or MemModel()
        self.streamed = set(self.DEFAULT_STREAMED if streamed is None
                            else streamed)
        self.base_tables = set(self.model.widths if base_tables is None
                               else base_tables)
        self.needed: set | None = None

    # -- entry point --------------------------------------------------------

    def audit_sql(self, sql: str, file: str = "<sql>",
                  query: str = "<sql>") -> MemReport:
        try:
            stmt = parse(sql)
        except ParseError as e:
            return MemReport(file, query, "unknown", detail=str(e))
        self.needed = statement_needed_names(stmt)
        cost = _MemCost()
        env = self._base_env()
        out_rows = 0
        try:
            if isinstance(stmt, A.Query):
                out_rows = self._audit_query(stmt, env, cost).rows
            elif isinstance(stmt, (A.InsertInto, A.CreateTempView)):
                out_rows = self._audit_query(stmt.query, env, cost).rows
            elif isinstance(stmt, A.DeleteFrom):
                name = stmt.table.lower()
                rows = self.model.table_rows(name) or 1
                cost.peak += rows * self.model.pruned_width(name, None)
            else:
                return MemReport(file, query, "unknown",
                                 detail=f"unmodeled statement "
                                        f"{type(stmt).__name__}")
        except RecursionError:
            return MemReport(file, query, "unknown",
                             detail="recursion limit")
        mode = "streamed" if cost.scans else "device"
        return MemReport(file, query, mode, peak_bytes=cost.peak,
                         out_rows=out_rows, scans=tuple(cost.scans))

    def _base_env(self) -> dict:
        env = {}
        for name, widths in self.model.widths.items():
            rows = self.model.table_rows(name) or 1
            env[name] = (widths, rows, name in self.base_tables)
        return env

    # -- query / set-expression walk ---------------------------------------

    def _audit_query(self, q: A.Query, env: dict, cost: _MemCost) -> _MRel:
        env = dict(env)
        for cname, cq in q.ctes:
            out = self._audit_query(cq, env, cost)
            widths = {c: w for cols in out.widths.values()
                      for c, w in cols.items()}
            # a CTE result is a device table whatever it scanned; it may
            # shadow a chunked catalog name (the planner resolves CTEs
            # first, so the statement does not stream the shadowed table)
            env[cname.lower()] = (widths, out.rows, False)
        out = self._audit_body(q.body, env, cost)
        # ORDER BY: the device lexsort holds one index vector alongside
        # the input — 8 B per row, already dominated by the conservative
        # sum; LIMIT clamps the output rows exactly
        if q.limit is not None:
            out.rows = min(out.rows, max(int(q.limit), 0))
        return out

    def _audit_body(self, body, env: dict, cost: _MemCost) -> _MRel:
        if isinstance(body, A.SetOp):
            left = self._audit_body(body.left, env, cost)
            right = self._audit_body(body.right, env, cost)
            rows = left.rows + right.rows
            # the concatenated buffer is a fresh allocation alongside the
            # branches (UNION's distinct grouping reuses it in place)
            cost.peak += _bucket(max(rows, 1)) * max(left.width,
                                                     right.width, 1)
            if body.op in ("intersect", "except"):
                rows = left.rows         # both are subsets of the left
            elif body.op == "union":
                # distinct union: also bounded by the output columns'
                # value-domain product (same rule as SELECT DISTINCT)
                doms = [d for cols in left.dom.values()
                        for d in cols.values()]
                if doms:
                    dom = 1
                    for v in doms:
                        dom = min(dom * max(v, 1), max(rows, 1))
                    rows = min(rows, max(dom, 1))
            out = _MRel(left.alias, {}, rows)
            out.cols, out.widths, out.dom = left.cols, left.widths, left.dom
            out.rows = rows
            return out
        if isinstance(body, A.Query):
            return self._audit_query(body, env, cost)
        return self._audit_select(body, env, cost)

    # -- SELECT -------------------------------------------------------------

    def _audit_select(self, sel: A.Select, env: dict,
                      cost: _MemCost) -> _MRel:
        where = _conjuncts_of(sel.where)
        parts, preds = self._flatten_from(sel.from_, env, cost, where)
        if parts:
            joined = self._audit_graph(parts, list(preds) + list(where),
                                       env, cost)
        else:
            joined = _MRel("_dual", {}, 1, single_row=True)
        for item in sel.items:
            self._walk_subqueries(item.expr, env, cost)
        if sel.having is not None:
            self._walk_subqueries(sel.having, env, cost)
        if not parts:
            # with a FROM graph the WHERE conjuncts were handed to
            # _audit_graph, which walks their subqueries exactly once
            for c in where:
                self._walk_subqueries(c, env, cost)

        rows = joined.rows
        if sel.group_by is not None:
            gb = sel.group_by
            # group output <= product of the key value domains (a base
            # column's domain is at most its table's rows), clamped at
            # input rows; grouping sets replay the aggregation per set
            dom = 1
            for e in gb.exprs:
                d = joined.col_domain(e) if isinstance(e, A.ColumnRef) \
                    else rows
                dom = min(dom * max(d, 1), max(rows, 1))
            n_sets = max(len(gb.sets), 1) if gb.kind != "plain" else 1
            rows = min(rows, max(dom, 1)) * n_sets
        elif self._has_aggregate_items(sel):
            rows = 1                       # keyless aggregate: one row

    # -- projection: output widths/domains ----------------------------------

        widths, dom = {}, {}
        for i, item in enumerate(sel.items):
            e = item.expr
            if isinstance(e, A.Star):
                qual = e.table and e.table.lower()
                for a, cols in joined.widths.items():
                    if qual is None or a == qual:
                        widths.update(cols)
                        dom.update(joined.dom.get(a, {}))
                continue
            if item.alias:
                name = item.alias.lower()
            elif isinstance(e, A.ColumnRef):
                name = e.name.lower()
            else:
                name = f"_c{i}"
            if isinstance(e, A.ColumnRef):
                widths[name] = joined.col_width(e)
                dom[name] = joined.col_domain(e)
            else:
                widths[name] = 9
                dom[name] = rows
        out = _MRel("_out", widths, rows, dom=dom)
        if sel.distinct and dom:
            d = 1
            for v in dom.values():
                d = min(d * max(v, 1), max(rows, 1))
            out.rows = rows = min(rows, max(d, 1))
        # the projected output is a fresh materialization
        cost.peak += _bucket(max(rows, 1)) * max(out.width, 1)
        return out

    def _has_aggregate_items(self, sel: A.Select) -> bool:
        from nds_tpu.sql.parser import AGG_FUNCS

        def has_agg(e) -> bool:
            if isinstance(e, A.FuncCall) and e.name.lower() in AGG_FUNCS:
                return True
            return any(has_agg(c) for c in _children(e))

        return any(has_agg(i.expr) for i in sel.items
                   if not isinstance(i.expr, A.Star))

    # -- FROM flattening (mirror of Planner._flatten_from) ------------------

    def _flatten_from(self, node, env: dict, cost: _MemCost, where=None,
                      top: bool = True):
        if node is None:
            return [], []
        if isinstance(node, A.TableRef):
            name = node.name.lower()
            alias = (node.alias or node.name).lower()
            widths, rows, is_base = env.get(name, ({}, 1, False))
            widths = self._prune(widths)
            chunked = is_base and name in self.streamed
            enc_widths = None
            if chunked and self.model.encoded:
                # streamed scans upload (and accumulate) the narrow
                # encoded representation — the width the proof prices
                enc_cols = self.model.enc_widths.get(name, {})
                enc_widths = {c: enc_cols.get(c, w)
                              for c, w in widths.items()}
            rel = _MRel(alias, enc_widths if enc_widths is not None
                        else widths, rows,
                        source=name if is_base else None, chunked=chunked)
            if enc_widths is not None:
                rel.plain_widths = dict(widths)
            if is_base and not chunked:
                # a device-resident base scan uploads its pruned columns
                cost.peak += _bucket(rows) * rel.width
            return [rel], []
        if isinstance(node, A.SubqueryRef):
            out = self._audit_query(node.query, env, cost)
            rel = _MRel(node.alias,
                        {c: w for cols in out.widths.values()
                         for c, w in cols.items()}, out.rows,
                        single_row=_single_row_query(node.query))
            return [rel], []
        if isinstance(node, A.Join):
            if node.kind in ("cross", "inner"):
                lp, lj = self._flatten_from(node.left, env, cost, where,
                                            top=False)
                rp, rj = self._flatten_from(node.right, env, cost, where,
                                            top=False)
                return lp + rp, lj + rj + _conjuncts_of(node.condition)
            lp, lj = self._flatten_from(node.left, env, cost, top=False)
            got = self._deferred_left(node, lp, lj, env, cost, where, top)
            if got is not None:
                return got
            # outer/semi/anti join: each side materializes whole first
            left = self._audit_graph(lp, lj, env, cost)
            rp, rj = self._flatten_from(node.right, env, cost)
            return self._finish_outer(node, left, rp, rj, env, cost)
        if isinstance(node, A.Query):        # parenthesized join tree
            return self._flatten_from(getattr(node.body, "from_", None),
                                      env, cost, where)
        return [], []

    def _finish_outer(self, node, left, rp, rj, env, cost):
        right = self._audit_graph(rp, rj, env, cost)
        rows = self._binary_join_rows(node, left, right)
        merged = left.merged_with(right, rows)
        cost.peak += _bucket(max(rows, 1)) * merged.width
        return [merged], []

    def _deferred_left(self, node, lp, lj, env, cost, where, top=True):
        """Mirror of the planner's multi-pass LEFT-join deferral (and of
        ``exec_audit._deferred_left``): an eligible join's sides flow
        into the enclosing streamed graph with the ON conjuncts as plain
        edges — the bound rules (PK-unique side => multiplicity 1) then
        price the join exactly like an inner PK batch, and the outer
        extras stay bounded by the preserved side's rows (every preserved
        row appears exactly once, matched or null-extended)."""
        if node.kind != "left" or node.condition is None:
            return None
        conjs = _conjuncts_of(node.condition)
        if not conjs or any(_has_subquery(c) for c in conjs):
            return None

        def plain_pairs(rel):
            out = []
            for c in conjs:
                if not (isinstance(c, A.BinaryOp) and c.op == "=" and
                        isinstance(c.left, A.ColumnRef) and
                        isinstance(c.right, A.ColumnRef)):
                    return None
                rk = rel.owns(c.left)
                lref = c.right
                if rk is None:
                    rk = rel.owns(c.right)
                    lref = c.left
                if rk is None or not any(p.owns(lref) for p in lp):
                    return None
                out.append((lref, rk))
            return out

        l_chunk = any(p.chunked for p in lp)
        if l_chunk:
            if os.environ.get("NDS_TPU_NO_PK_GATHER"):
                return None              # the b1 gather arm is disabled
            # (b1): preserved chunk side — one pristine right scan whose
            # ON keys are exactly its declared (composite) primary key
            rp, rj = self._flatten_from(node.right, env, cost, top=False)
            eligible = len(rp) == 1 and not rj and rp[0].source and \
                not rp[0].chunked
            if eligible:
                pairs = plain_pairs(rp[0])
                pk = _table_pk(rp[0].source)
                eligible = pairs is not None and pk is not None and \
                    {rk for (_l, rk) in pairs} == set(pk)
            if eligible:
                return lp + rp, lj + conjs
            left = self._audit_graph(lp, lj, env, cost)
            return self._finish_outer(node, left, rp, rj, env, cost)
        # (b2): null-introducing chunk side — single build part on the
        # left, single chunked scan on the right, the join being the
        # SELECT's whole FROM, and no remaining WHERE conjunct beyond
        # those the planner consumes below the join (build-side only)
        if len(lp) != 1 or lp[0].chunked:
            return None
        rp, rj = self._flatten_from(node.right, env, cost, top=False)
        eligible = top and len(rp) == 1 and not rj and rp[0].chunked and \
            plain_pairs(rp[0]) is not None
        if eligible:
            for c in (where or []):
                if _has_subquery(c):
                    eligible = False
                    break
                refs = _column_refs(c)
                # conjuncts fully on the build side are consumed below
                # the join by the planner (lw) and do not block
                if refs and all(lp[0].owns(r) for r in refs):
                    continue
                eligible = False
                break
        if eligible:
            lp[0].single_row = False
            return rp + lp, lj + conjs
        left = self._audit_graph(lp, lj, env, cost)
        return self._finish_outer(node, left, rp, rj, env, cost)

    def _prune(self, widths: dict) -> dict:
        if self.needed is None:
            return dict(widths)
        kept = {c: w for c, w in widths.items() if c in self.needed}
        return kept if kept and len(kept) < len(widths) else dict(widths)

    def _binary_join_rows(self, node: A.Join, left: _MRel,
                          right: _MRel) -> int:
        """Row bound of one materialized (outer/semi/anti) binary join.
        Semi/anti never grow the left side; a LEFT join against a side
        whose ON keys cover its declared primary key is 1:1 (matches +
        extras <= left rows); everything else is bounded by the pair
        bucket plus the null-extended extras."""
        if node.kind in ("semi", "anti"):
            return left.rows
        conjuncts = _conjuncts_of(node.condition)
        part_cols = [left.colset(), right.colset()]
        sources = [left.source, right.source]
        unique = {}
        for side, other in ((1, 0), (0, 1)):
            pk = _table_pk(sources[side])
            keys = set()
            for c in conjuncts:
                e = _equi_sides(c, part_cols)
                if e is None:
                    continue
                li, ri, lk, rk = e
                k = lk if li == side else (rk if ri == side else None)
                if k is not None:
                    keys.add(k)
            unique[side] = pk is not None and keys >= set(pk)
        pairs = left.rows if unique.get(1) else (
            right.rows if unique.get(0) and node.kind != "left"
            else _bucket(max(left.rows, 1)) * self.model.fanout)
        if node.kind == "left":
            return pairs + left.rows
        if node.kind == "right":
            return pairs + right.rows
        if node.kind == "full":
            return pairs + left.rows + right.rows
        return pairs

    # -- join-graph bounds (mirror of Planner._join_parts) ------------------

    def _audit_graph(self, parts, conjuncts, env, cost: _MemCost) -> _MRel:
        if not parts:
            return _MRel("_dual", {}, 1, single_row=True)
        if len(parts) == 1 and not any(p.chunked for p in parts):
            for c in conjuncts:
                self._walk_subqueries(c, env, cost)
            return parts[0]
        part_cols = [p.colset() for p in parts]
        sources = [p.source for p in parts]
        batches: dict = {}
        for c in conjuncts:
            if _has_subquery(c):
                # multi-pass streaming: the subquery pre-plans into a
                # device residual and the conjunct filters joined rows —
                # it neither grows rows nor breaks the proof
                self._walk_subqueries(c, env, cost)
                continue
            e = _equi_sides(c, part_cols)
            if e is not None:
                li, ri, _lk, _rk = e
                batches.setdefault(tuple(sorted((li, ri))), []).append(e)

        parent = list(range(len(parts)))

        def find(x):
            while parent[x] != x:
                parent[x] = parent[parent[x]]
                x = parent[x]
            return x

        for (a, b) in batches:
            parent[find(a)] = find(b)

        # per-component row bound: the largest member, times the enforced
        # fanout bucket for every batch with no unique side; components
        # multiply (cartesian layout is an exact product)
        comp_rows: dict = {}
        for i, p in enumerate(parts):
            r = find(i)
            base = 1 if p.single_row else max(p.rows, 1)
            comp_rows[r] = max(comp_rows.get(r, 1), base)
        chunked_idx = [i for i, p in enumerate(parts) if p.chunked]
        # keep-choice mirrors the executor (largest by UNENCODED bytes:
        # the runtime picks by arrow nbytes); non-kept chunked parts bind
        # whole through the plain device path, so they re-price plain
        keep = max(chunked_idx, key=lambda i: parts[i].rows *
                   max(parts[i].plain_width, 1)) if chunked_idx else None
        for i in chunked_idx:
            if i != keep:
                parts[i].use_plain_widths()
        for (a, b), batch in batches.items():
            if not _batch_unique_side(part_cols, sources,
                                      keep if keep is not None else -1,
                                      a, b, batch):
                r = find(a)
                comp_rows[r] = _bucket(comp_rows[r]) * self.model.fanout
        joined_rows = 1
        for r in comp_rows.values():
            joined_rows *= r

        merged = parts[0]
        for p in parts[1:]:
            merged = merged.merged_with(p, joined_rows)
        merged.rows = joined_rows

        if keep is None:
            # device-resident graph: the joined result materializes whole
            cost.peak += _bucket(max(joined_rows, 1)) * merged.width
            return merged

        # streamed graph: non-kept chunked parts bind whole (one
        # streaming axis per graph) — charge their resident bytes
        for i in chunked_idx:
            if i != keep:
                cost.peak += _bucket(parts[i].rows) * parts[i].width
        kept = parts[keep]
        k = stream_graph_fanout(part_cols, sources, keep, conjuncts)
        chunk_bytes = self.model.chunk_cap() * kept.width
        # async ingest: the bounded prefetch ring holds up to ``depth``
        # MORE prepared chunks beyond the in-flight pair — priced into
        # the peak below and off the capacity every admission decision
        # here compares against (lockstep with engine/stream.py)
        ring_bytes = self.model.ring_bytes(kept.width)
        admit_cap = self.model.admit_capacity(kept.width)
        n_parts, part_rows, part_bytes = 1, None, None
        if k is not None:
            acc_rows = self.model.acc_row_bound(kept.rows, k)
            if self.model.acc_ceiling is not None:
                acc_rows = min(acc_rows, self.model.acc_ceiling)
            acc_bytes = acc_rows * merged.width
            survivors = min(joined_rows, acc_rows)
            # grace-style partition decomposition: when the whole-graph
            # bound is past capacity (or NDS_TPU_STREAM_PARTITIONS pins a
            # count), a graph with plain equi keys on the streamed slot
            # is proven per partition instead — the rule the executor
            # mirrors at pipeline build (engine/stream.py)
            forced = self.model.partitions
            if (acc_bytes > admit_cap
                    or (forced is not None and forced > 1)):
                keys = stream_partition_keys(part_cols, sources, keep,
                                             conjuncts)
                if keys:
                    p, _ = choose_partitions(
                        kept.rows, k, self.model.fanout,
                        max(merged.width, 1), admit_cap,
                        forced=forced, skew=self.model.skew)
                    if p > 1:
                        n_parts = p
                        part_rows = self.model.partition_bound(
                            kept.rows, k, p)
                        if self.model.acc_ceiling is not None:
                            part_rows = min(part_rows,
                                            self.model.acc_ceiling)
                        part_bytes = part_rows * merged.width
        else:
            # eager loop: survivors concatenate up to the graph bound
            acc_rows = acc_bytes = None
            survivors = joined_rows
        # mesh-sharded execution (NDS_TPU_STREAM_SHARDS): the per-shard
        # survivor bound is the share rule applied over the mesh —
        # rows/shards x skew through the fan-out — and the allocation
        # unit is the (partition, shard) composition. The eager loop
        # never shards, so unprovable scans keep shards=1.
        n_shards, srows, sbytes = 1, None, None
        if k is not None and self.model.shards > 1:
            n_shards = self.model.shards
            srows = min(acc_rows,
                        shard_row_bound(kept.rows, n_shards, 1, k,
                                        self.model.fanout, self.model.skew))
            unit = min(part_rows if part_rows is not None else acc_rows,
                       shard_row_bound(kept.rows, n_shards, n_parts, k,
                                       self.model.fanout, self.model.skew))
            if self.model.acc_ceiling is not None:
                srows = min(srows, self.model.acc_ceiling)
                unit = min(unit, self.model.acc_ceiling)
            sbytes = unit * merged.width
        sb = ScanBound(kept.alias, kept.source or "?", kept.rows, k,
                       acc_rows, acc_bytes, chunk_bytes,
                       partitions=n_parts, part_rows=part_rows,
                       part_bytes=part_bytes, shards=n_shards,
                       shard_rows=srows, shard_bytes=sbytes,
                       ring_bytes=ring_bytes)
        cost.scans.append(sb)
        # working set: two chunks in flight + the prefetch ring's live
        # set (depth more prepared chunks) + the survivor accumulator(s)
        # (partitioned: every partition's proof-sized accumulator is live
        # until the single materializing sync; eager: the concatenated
        # survivor union)
        if part_bytes is not None:
            held = n_parts * part_bytes
        elif acc_bytes is not None:
            held = acc_bytes
        else:
            held = _bucket(max(survivors, 1)) * merged.width
        cost.peak += 2 * chunk_bytes + ring_bytes + held
        merged.rows = survivors
        return merged

    # -- subqueries inside expressions --------------------------------------

    def _walk_subqueries(self, e, env: dict, cost: _MemCost) -> None:
        def walk(node):
            if isinstance(node, (A.InSubquery, A.ScalarSubquery, A.Exists,
                                 A.QuantifiedCompare)):
                self._audit_query(node.query, env, cost)
                return
            for c in _children(node):
                walk(c)

        walk(e)


# ---------------------------------------------------------------------------
# corpus driver + lint-gate findings
# ---------------------------------------------------------------------------

# pinned instantiation seed shared with plan_audit/exec_audit: bounds must
# not depend on sampled parameter values
_AUDIT_SEED = 20260803


def audit_mem_template_text(text: str, file: str,
                            auditor: MemAuditor | None = None) -> list:
    auditor = auditor or MemAuditor()
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


def audit_mem_corpus(template_dir: str | None = None, streamed=None,
                     model: MemModel | None = None) -> list:
    """MemReports for every template in templates.lst order."""
    template_dir = template_dir or TEMPLATE_DIR
    auditor = MemAuditor(streamed=streamed, model=model)
    reports: list = []
    for name in list_templates(template_dir):
        reports.extend(audit_mem_template_text(
            load_template(name, template_dir), name, auditor))
    return reports


def reports_to_findings(reports, capacity_bytes: int | None = None) -> list:
    """``hbm-capacity`` findings: a device-resident statement whose peak
    bound exceeds the configured capacity cannot be admitted at the
    audited scale, and a streamed statement whose proven accumulator
    bound exceeds it would be sized past HBM (the runtime would fall back
    to the legacy ceiling and risk the overflow rerun the proof exists to
    retire). A PARTITIONED scan is gated on its per-partition bound
    instead — the unit the executor allocates and the per-partition
    overflow flag enforces; that rule is what cleared the 7 fan-out
    accumulators from the baseline. Eager-fallback scans (unprovable
    multiplicity) are reported in ``--mem-report`` but not gated — the
    eager loop's working set is per-chunk."""
    cap = hbm_capacity_bytes() if capacity_bytes is None else capacity_bytes
    findings = []
    for r in reports:
        if r.mode == "device" and r.peak_bytes > cap:
            findings.append(Finding(
                r.file, r.query, "hbm-capacity", "error",
                f"device-resident peak bound {r.peak_bytes:,} B exceeds "
                f"the configured HBM capacity {cap:,} B "
                "(NDS_TPU_HBM_BYTES)"))
        for s in r.scans:
            if not s.provable:
                continue
            if s.shards > 1 and s.shard_bytes is not None:
                # sharded pipeline: the allocation unit is one
                # (partition, shard) accumulator — the bound the per-shard
                # overflow flags enforce
                if s.shard_bytes > cap:
                    findings.append(Finding(
                        r.file, r.query, "hbm-capacity", "error",
                        f"streamed scan {s.table!r} per-shard accumulator "
                        f"bound {s.shard_bytes:,} B ({s.shards} shards x "
                        f"{s.partitions} partitions) exceeds the "
                        f"configured HBM capacity {cap:,} B"))
                continue
            if s.partitions > 1 and s.part_bytes is not None:
                if s.part_bytes > cap:
                    findings.append(Finding(
                        r.file, r.query, "hbm-capacity", "error",
                        f"streamed scan {s.table!r} per-partition "
                        f"accumulator bound {s.part_bytes:,} B "
                        f"({s.part_rows:,} rows x {s.partitions} "
                        f"partitions) exceeds the configured HBM "
                        f"capacity {cap:,} B"))
            elif s.acc_bytes is not None and s.acc_bytes > cap:
                findings.append(Finding(
                    r.file, r.query, "hbm-capacity", "error",
                    f"streamed scan {s.table!r} accumulator bound "
                    f"{s.acc_bytes:,} B ({s.acc_rows:,} rows) exceeds the "
                    f"configured HBM capacity {cap:,} B"))
    return findings


def mem_audit_findings(template_dir: str | None = None) -> list:
    """The lint pass entry point (tools/lint.py fifth pass)."""
    return reports_to_findings(audit_mem_corpus(template_dir))


def _human(n) -> str:
    if n is None:
        return "-"
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if n < 1024 or unit == "TiB":
            return f"{n:.0f}{unit}" if unit == "B" else f"{n:.1f}{unit}"
        n /= 1024
    return str(n)


def format_mem_report(reports) -> str:
    """The per-statement bound table (``tools/lint.py --mem-report``)."""
    cap = hbm_capacity_bytes()
    depth = max(prefetch_depth(), 0)
    lines = ["# mem-audit: per-statement peak-HBM byte bounds",
             f"# capacity model: {_human(cap)} (NDS_TPU_HBM_BYTES); "
             f"prefetch ring depth {depth} (NDS_TPU_PREFETCH_DEPTH) — "
             "ring live set (depth x chunk bytes) priced into every "
             "streamed peak and off the admitting capacity",
             f"{'template':<18} {'mode':<9} {'peak':>9}  accumulators"]
    worst = 0
    for r in reports:
        worst = max(worst, r.peak_bytes)
        bits = []
        for s in r.scans:
            ring = f" + ring {_human(s.ring_bytes)}" if s.ring_bytes \
                else ""
            if s.provable and s.shards > 1:
                bits.append(f"{s.table}: S={s.shards}"
                            + (f" x P={s.partitions}"
                               if s.partitions > 1 else "")
                            + f" x {_human(s.shard_bytes)}/shard "
                            f"({s.shard_rows:,} rows/shard, "
                            f"k={s.fanout_k}){ring}")
            elif s.provable and s.partitions > 1:
                bits.append(f"{s.table}: P={s.partitions} x "
                            f"{_human(s.part_bytes)}/part "
                            f"({s.part_rows:,} rows/part, "
                            f"k={s.fanout_k}){ring}")
            elif s.provable:
                bits.append(f"{s.table}: {_human(s.acc_bytes)} "
                            f"({s.acc_rows:,} rows, k={s.fanout_k})"
                            f"{ring}")
            else:
                bits.append(f"{s.table}: unprovable (eager loop){ring}")
        lines.append(f"{r.query:<18} {r.mode:<9} "
                     f"{_human(r.peak_bytes):>9}  " + "; ".join(bits))
    lines.append(f"# {len(reports)} statements — worst peak bound "
                 f"{_human(worst)} vs capacity {_human(cap)}")
    return "\n".join(lines)
