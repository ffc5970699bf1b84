# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Whole-query trace-replay compilation: ONE XLA program per query.

The engine executes eagerly, table-at-a-time: every one of the ~100-400
small dispatches a query makes pays launch latency, and every blocking
read a host round trip, even after the lazy-count work cut the BLOCKING
reads to 1-3 per query (what share of the wall that is on a local chip is
not measured). The reference never has this problem: Spark compiles
each stage to one JVM loop and the driver makes one round trip
(ref: nds/nds_power.py:125-135).

The TPU-native answer is the same one jit gives training loops: TRACE the
whole query into one program and REPLAY it. Mechanics:

1. RECORD: run the query eagerly once under ``ops.recording()`` — every
   host read (bucket-sizing syncs, batched count resolutions, host-built
   dimension maps, chunk span plans) logs its value in order.
2. COMPILE: re-run the SAME planner code under ``jax.jit`` with the
   session's catalog columns as arguments and ``ops.replaying(log)``
   serving every host read from the recording — no device contact during
   tracing. The result is one fused XLA program for the entire pipeline:
   scans, joins, aggregation, sort, limit.
3. REPLAY: subsequent executions of the same query text on the same data
   version call the compiled program: one dispatch, one result fetch —
   the reference's one-round-trip execution contract, plus XLA now
   fuses/optimizes ACROSS operator boundaries the eager path could not.

Safety: the replay cache is keyed on (query text, session data version);
any catalog mutation bumps the version. A divergence between trace and
recording raises ``ops.ReplayMismatch`` and the query permanently falls
back to the eager path. A query that binds a streaming (>HBM
ChunkedTable) scan is blacklisted to the eager chunk loop at compile
time; other queries in the same session replay normally.
"""

from __future__ import annotations

from dataclasses import replace as _replace

import jax

from nds_tpu.engine import ops as E
from nds_tpu.engine.table import DeviceTable
from nds_tpu.obs import trace as _obs


class _NotReplayable(Exception):
    pass


try:
    from jax.core import DropVar as _DropVar
except ImportError:  # pragma: no cover - future jax relocations
    from jax.extend.core import DropVar as _DropVar  # type: ignore

import os as _os

# segmentation budget, read at USE time (not import): a post-import
# change to the knob must shape the next replay build, not be silently
# frozen (the conc-audit env-freeze rule).
def _max_eqns() -> int:
    return int(_os.environ.get("NDS_TPU_REPLAY_MAX_EQNS", "4500"))


def _count_eqns(jaxpr) -> int:
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)     # unwrap ClosedJaxpr
    n = 0
    for eq in jaxpr.eqns:
        n += _eqn_weight(eq)
    return n


def _eqn_weight(eq) -> int:
    """1 + every equation nested in the eqn's sub-jaxprs (pjit bodies,
    scan/cond branches) — the unit XLA optimization time scales with."""
    n = 1
    for v in eq.params.values():
        if hasattr(v, "jaxpr"):
            n += _count_eqns(v.jaxpr)
        elif isinstance(v, (list, tuple)):
            for x in v:
                if hasattr(x, "jaxpr"):
                    n += _count_eqns(x.jaxpr)
    return n


# read at USE time like _max_eqns() above
def _max_segments() -> int:
    return int(_os.environ.get("NDS_TPU_REPLAY_MAX_SEGMENTS", "6"))


def _split_jaxpr(closed, max_eqns):
    """Partition a whole-query ClosedJaxpr into sequential segments of
    bounded optimization weight, each compiled as its OWN XLA program.

    XLA's optimization passes go superlinear on the handful of
    megaprograms the biggest queries trace to (q14/q67-class); chaining
    K bounded programs keeps compile time ~linear while still replacing
    the few-hundred-dispatch eager stream with K dispatches. Returns
    ``(segments, out_src)`` where each segment is ``(jaxpr, const_vals,
    invars, outvars)`` and ``out_src`` maps every program output var to
    its position, or None when the program does not split cleanly
    (effects, or a single oversized equation)."""
    from jax.extend import core as jex_core
    jaxpr = closed.jaxpr
    if jaxpr.effects:
        return None
    weights = [_eqn_weight(eq) for eq in jaxpr.eqns]
    if not jaxpr.eqns or max(weights) > max_eqns:
        return None                       # one indivisible giant equation
    groups, cur, cur_w = [], [], 0
    for eq, w in zip(jaxpr.eqns, weights):
        if cur and cur_w + w > max_eqns:
            groups.append(cur)
            cur, cur_w = [], 0
        cur.append(eq)
        cur_w += w
    if cur:
        groups.append(cur)
    if len(groups) > _max_segments():
        return None
    const_of = dict(zip(jaxpr.constvars, closed.consts))
    # var -> defining group index (inputs/consts = -1)
    def_in = {v: -1 for v in list(jaxpr.invars) + list(jaxpr.constvars)}
    for gi, eqns in enumerate(groups):
        for eq in eqns:
            for ov in eq.outvars:
                def_in[ov] = gi
    is_var = lambda a: not isinstance(a, jex_core.Literal)  # noqa: E731
    # vars each group consumes from OUTSIDE itself
    needs = [[] for _ in groups]
    for gi, eqns in enumerate(groups):
        seen = set()
        for eq in eqns:
            for iv in eq.invars:
                if is_var(iv) and def_in[iv] != gi and iv not in seen:
                    seen.add(iv)
                    needs[gi].append(iv)
    # vars that must cross a segment boundary (consumed later or output)
    final_out = [v for v in jaxpr.outvars if is_var(v)]
    crossers = set(final_out)
    for gi in range(len(groups)):
        crossers.update(v for v in needs[gi] if def_in[v] >= 0)
    segments = []
    for gi, eqns in enumerate(groups):
        invars = needs[gi]
        outvars = []
        for eq in eqns:
            for ov in eq.outvars:
                if ov in crossers and not isinstance(ov, _DropVar):
                    outvars.append(ov)
        seg_consts = [const_of[v] for v in invars if v in const_of]
        cvars = [v for v in invars if v in const_of]
        rvars = [v for v in invars if v not in const_of]
        # NO debug_info on segments: a segment's invars/outvars are a
        # re-partition of the whole program's, so inheriting its
        # arg_names/result_paths trips the constructor's length
        # assertion on jax >= 0.4.30 and the whole split silently
        # blacklisted the query (the seed's one red tier-1 test). The
        # segments are synthetic — there are no user-meaningful names
        # to preserve.
        seg = jex_core.Jaxpr(constvars=cvars, invars=rvars,
                             outvars=outvars, eqns=eqns)
        segments.append((seg, seg_consts, rvars, outvars))
    return segments, list(jaxpr.outvars), const_of


# log entries whose array payloads are DEVICE OPERANDS (consumed via
# jnp.asarray and elementwise math only): these lift into jit arguments
# instead of baking fact-sized constants into the program. Entries whose
# values drive HOST decisions (sync counts, chunk spans, key ranges) must
# stay literal. Maps tag -> indices of liftable tuple elements (None =
# the whole value is one array).
_LIFTABLE = {
    "cast_str": (0,),          # (inv codes, dictionary)
    "concat": (0,),
    "date_part": None,
    "month_arith": None,
    "dense_dim": (1,),         # (base, position map) — may be None
}
_LIFT_MIN_ELEMS = 1024


def _lift_log(log):
    """Split a recorded log into (log-with-ArgRefs, operand arrays)."""
    import numpy as np
    out_log, operands = [], []

    def lift(arr):
        operands.append(arr)
        return E.ArgRef(len(operands) - 1)

    for tag, val in log:
        idxs = _LIFTABLE.get(tag, ())
        if idxs is None and isinstance(val, np.ndarray) and \
                val.size >= _LIFT_MIN_ELEMS:
            val = lift(val)
        elif idxs and isinstance(val, tuple):
            val = tuple(
                lift(x) if (i in idxs and isinstance(x, np.ndarray)
                            and x.size >= _LIFT_MIN_ELEMS) else x
                for i, x in enumerate(val))
        out_log.append((tag, val))
    return out_log, operands


class CompiledQuery:
    """One compiled whole-query program + the metadata to call it."""

    def __init__(self, session, stmt, log, out_template):
        self.session = session
        self.stmt = stmt
        # big array payloads become jit ARGUMENTS (program stays small and
        # the executable is not re-specialized to them)
        self.log, self.operands = _lift_log(list(log))
        # (names, kinds, dict_values, valids-present, plen, nrows_bound)
        self.out_template = out_template
        self.arg_spec = None       # [(table, col, has_valid)]
        self.jitted = None
        self.segments = None       # chained programs when too big for one
        self.seg_invars = None
        self.seg_outsrc = None
        self.seg_constenv = None

    # ---------------------------------------------------------------- build

    def _flat_args(self):
        """The session catalog's column buffers, in a deterministic order
        (re-collected at every call so maintenance-refreshed tables feed
        the current buffers — the data version guards semantic change)."""
        args = []
        for tname, cname, has_valid in self.arg_spec:
            col = self.session.catalog[tname][cname]
            args.append(col.data)
            if has_valid:
                args.append(col.valid)
        return args

    def compile(self):
        from nds_tpu.sql.planner import Planner
        catalog = self.session.catalog
        # lazy view counts resolve up front: a DeviceCount closed over the
        # trace would leak a stale device scalar into the program
        for t in catalog.values():
            if isinstance(t, DeviceTable) and \
                    isinstance(t.nrows, E.DeviceCount):
                t.nrows = t.nrows.to_int()
        # argument universe: every DEVICE table in the catalog. Host-
        # resident ChunkedTables are left out: a query that binds one is
        # filtered upstream by record_eligible() and routed to the
        # compiled streaming executor (engine/stream.py) instead, while
        # every other query in the same >HBM session stays
        # replay-eligible.
        self.arg_spec = []
        for tname in sorted(catalog):
            t = catalog[tname]
            if not isinstance(t, DeviceTable):
                continue
            for cname, col in t.columns.items():
                self.arg_spec.append((tname, cname, col.valid is not None))
        spec = self.arg_spec
        base_tables = set(self.session.base_tables)
        stmt, log = self.stmt, self.log
        names, kinds, dicts, valided, plen, bound = self.out_template

        @_obs.scoped("replay")
        def traced(flat, operands):
            # rebuild the catalog around the traced buffers
            cat = {}
            i = 0
            for tname, cname, has_valid in spec:
                data = flat[i]
                i += 1
                valid = None
                if has_valid:
                    valid = flat[i]
                    i += 1
                src = catalog[tname][cname]
                cat.setdefault(tname, {})[cname] = _replace(
                    src, data=data, valid=valid)
            cat2 = {t: DeviceTable(cols, catalog[t].nrows)
                    for t, cols in cat.items()}
            planner = Planner(cat2, base_tables=base_tables)
            with E.replaying(log, operands):
                out = planner.query(stmt)
            outs = []
            for n in names:
                c = out[n]
                outs.append(c.data)
                outs.append(c.valid)
            outs.append(E.count_arr(out.nrows))
            return tuple(outs)

        # validate the replay log end-to-end with the SAME trace the jit
        # cache will reuse, and gate on program size: a handful of
        # rollup+window giants (q14/q67-class) trip superlinear XLA
        # optimization time as ONE program — those split into a chain of
        # bounded segment programs instead (compile ~linear, K dispatches)
        E.resolve_counts()   # the trace must start with a clean batch
        self.jitted = jax.jit(traced)
        # span covers the whole-query re-trace (the host-side cost of
        # turning the recording into one program); XLA backend compile
        # lands on the first run() and is metered there via compile_ns
        with _obs.span("replay.compile", statement="whole-query"):
            closed = self.jitted.trace(
                self._flat_args(), self.operands).jaxpr
        n_eqns = _count_eqns(closed.jaxpr)
        if n_eqns > _max_eqns():
            self.jitted = None
            split = _split_jaxpr(closed, _max_eqns())
            if split is None:
                raise _NotReplayable(
                    f"program too large to fuse profitably ({n_eqns} eqns) "
                    "and not cleanly splittable")
            segs, out_src, const_env = split
            import functools
            from jax import core as jcore
            self.segments = [
                (jax.jit(functools.partial(jcore.eval_jaxpr, seg)),
                 consts, invars, outvars)
                for seg, consts, invars, outvars in segs]
            self.seg_invars = closed.jaxpr.invars
            self.seg_outsrc = out_src
            # a program output may BE a jaxpr constvar (a recorded value
            # reaching the output untransformed): those never cross a
            # segment boundary, so the run env must be seeded with them
            self.seg_constenv = const_env
        return self

    # ----------------------------------------------------------------- run

    def _run_segments(self):
        """Execute the chained segment programs, feeding each segment from
        an environment of prior outputs (K dispatches instead of 1)."""
        from jax.extend import core as jex_core
        import jax.tree_util as jtu
        leaves = jtu.tree_leaves((self._flat_args(), self.operands))
        env = dict(self.seg_constenv)
        env.update(zip(self.seg_invars, leaves))
        for seg_fn, consts, invars, outvars in self.segments:
            outs = seg_fn(consts, *[env[v] for v in invars])
            env.update(zip(outvars, outs))
        import jax.numpy as jnp
        # literal outputs carry raw trace-time scalars (TypedInt); jit
        # would have returned arrays, so the chained path must too
        return tuple(
            jnp.asarray(v.val, dtype=v.aval.dtype)
            if isinstance(v, jex_core.Literal)
            else env[v] for v in self.seg_outsrc)

    def run(self, block: bool = False) -> DeviceTable:
        with _obs.span("replay.drive",
                       segments=len(self.segments or ()) or 1):
            return self._run(block)

    def _run(self, block: bool) -> DeviceTable:
        from nds_tpu.engine.column import Column
        names, kinds, dicts, valided, plen, bound = self.out_template
        # the first call traces: stray real counts must not sit in the
        # pending list where the traced resolve would batch them
        E.resolve_counts()
        if self.segments is not None:
            # the jaxpr's outvars are the FLAT leaves (None valids are
            # dropped by tracing); re-expand to the (data, valid)*N +
            # count layout run() consumes using the template's flags
            flat = list(self._run_segments())
            outs = []
            for has_valid in valided:
                outs.append(flat.pop(0))
                outs.append(flat.pop(0) if has_valid else None)
            outs.append(flat.pop(0))
        else:
            outs = self.jitted(self._flat_args(), self.operands)
        if block:
            import jax as _jax
            _jax.block_until_ready(outs[-1])
        cols = {}
        for j, n in enumerate(names):
            data, valid = outs[2 * j], outs[2 * j + 1]
            cols[n] = Column(kinds[j], data, valid, dicts[j])
        nrows = E.DeviceCount(outs[-1], bound)
        return DeviceTable(cols, nrows, plen=plen)


def out_template_of(table: DeviceTable):
    names = list(table.column_names)
    kinds = [table[n].kind for n in names]
    dicts = [table[n].dict_values for n in names]
    valided = [table[n].valid is not None for n in names]
    return (names, kinds, dicts, valided, table.plen,
            E.count_bound(table.nrows))


def _binds_chunked(session, stmt) -> bool:
    """True when any table reference in the statement resolves to a
    host-resident ChunkedTable in the session catalog. Conservative on
    shadowing: a CTE reusing a chunked table's name still counts (the
    statement simply stays on the planner path, which handles it)."""
    from nds_tpu.engine.table import ChunkedTable
    from nds_tpu.sql import ast as A
    chunked = {name for name, t in session.catalog.items()
               if isinstance(t, ChunkedTable)}
    if not chunked:
        return False
    found = False

    def walk(x):
        nonlocal found
        if found:
            return
        if isinstance(x, A.TableRef) and x.name.lower() in chunked:
            found = True
            return
        if hasattr(x, "__dataclass_fields__"):
            for f in vars(x).values():
                walk_any(f)

    def walk_any(f):
        if isinstance(f, (list, tuple)):
            for y in f:
                walk_any(y)
        elif hasattr(f, "__dataclass_fields__"):
            walk(f)
    walk(stmt)
    return found


def record_eligible(session, stmt=None) -> bool:
    """Recording is attempted per QUERY, not per catalog: a session with
    >HBM ChunkedTables still replays every query that binds only device
    tables. A query that DOES bind a chunked scan is routed away from
    whole-query record/replay up front — recording it would log one host
    decision per chunk and the compile trace cannot rebuild a
    host-resident table from jit arguments. Its streaming is compiled
    one layer down instead: the planner's ``_stream_join_parts`` hands the
    join graph to the chunk pipeline executor (engine/stream.py), which
    applies the same record/replay machinery to ONE chunk-invariant
    per-chunk program."""
    if stmt is not None and _binds_chunked(session, stmt):
        return False
    return True
