# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Tracer-hazard lint: JAX-specific static checks over ``nds_tpu/``.

Python-``ast`` based; no JAX import, no tracing. Rules (each suppressible
with ``# nds-lint: ignore[rule]`` on the flagged line or the line above):

* ``host-sync-in-loop`` — a device->host synchronization primitive
  (``.item()``, ``np.asarray``/``np.array`` over device values,
  ``jax.device_get``, ``float()``/``int()`` of arrays is not detectable
  statically so it is out of scope) lexically inside a ``for``/``while``
  loop of the hot-path modules (``engine/ops.py``, ``sql/planner.py``).
  One sync per query is accounting; one per loop iteration is a dispatch
  stall. Warning severity: the existing accounted reads are baselined.
* ``tracer-if`` — a Python ``if``/``while`` whose test references a
  non-static parameter of a ``jax.jit``-decorated function. Under tracing
  this raises ``TracerBoolConversionError`` at best and silently bakes a
  branch at worst.
* ``cache-key-list`` — a raw ``list``/``set``/``dict`` display or
  comprehension inside the key expression of a ``*_CACHE`` dict: lists are
  unhashable, and even via tuple() the unbounded contents make the jit
  cache key explode. A cache threaded through a helper as a plain
  parameter (the planner's ``_fused_run(self, cache, ...)``) is covered
  too: call sites passing a ``*_CACHE`` alias it to the callee's
  parameter, and the callee's writes/evictions/keys count against the
  module cache.
* ``unbounded-cache`` — a module-level ``*_CACHE`` dict written by
  subscript somewhere in its module with no eviction evidence (no
  ``len()`` guard, ``pop``/``popitem``/``clear``) anywhere: every new key
  pins a jitted executable for process lifetime.
* ``time-in-jit`` — ``time.time()``/``time.perf_counter()`` inside a
  ``jax.jit``-decorated function: it runs once at trace time and becomes
  a constant in the compiled program.
* ``span-in-jit`` — an ``obs.span(...)`` trace context entered inside a
  ``jax.jit``-decorated function. Spans read the host clock and the
  thread's sync counters at enter/exit; under tracing those run ONCE at
  trace time (measuring compile, not execution) and the span would be
  recorded on every retrace instead of every run. The runtime half of
  this guard is ``obs.trace.span()`` returning a null span under
  ``replay_mode() == "replay"``; this rule catches the static case the
  runtime guard cannot see — a span lexically inside a jitted body.
  ``obs.op(...)`` (the engine-primitive span) counts as a span in every
  rule here. ``obs.scoped`` (``jax.named_scope`` around a body: names
  operations at trace time, reads no clock) and ``obs.annotation`` (a
  profiler ``TraceAnnotation``: no ring record, no counter read — what
  the prefetch ring's worker uses in place of a span) do not.
  Only obs-owned calls trip it: conventional module names
  (``obs``/``_obs``/``obs_trace``), any ``nds_tpu.obs`` import alias,
  and bare names from-imported from the obs package — an unrelated
  ``.span()`` (``re.Match.span()``) or a local helper does not.
* ``host-sync-in-shard-map`` — a host-sync primitive, an
  ``ops.host_read``-charging call (``host_read``, ``timed_read``,
  ``guarded_scalar_read``, ``host_sync``, ``count_int``,
  ``resolve_counts``, ``.to_int()``, ``.item()``, ``device_get``,
  ``np.asarray``), or an ``obs.span(...)`` trace context inside a
  function passed to ``shard_map``/``pjit``. A shard_map body is traced
  once and runs as one SPMD program on every mesh device: a host read
  there is at best a tracer error and at worst a per-dispatch full-mesh
  barrier, and a span would clock the trace, not the execution (the
  ``span-in-jit`` hazard, but the runtime null-span guard cannot see a
  shard_map body that is traced outside replay mode). The rule resolves
  the body by name — any function whose name is passed as the first
  argument to a ``shard_map``/``pjit`` call in the module — and also
  sees ONE level down into module-local helpers, like
  ``chunk-loop-host-sync``. Error severity: the sharded streamed
  pipeline's collective budget proves these bodies sync-free, so a
  violation is a correctness bug, not a perf note.
* ``host-read-in-pallas`` — a host-sync primitive, an
  ``ops.host_read``-charging call, or an ``obs.span(...)`` trace context
  inside a function passed to ``pl.pallas_call``. A Pallas kernel body
  is compiled to Mosaic and runs per grid cell ON the device: a host
  read there is not merely slow, it cannot exist (tracer error at best),
  and a span would clock the kernel trace. Resolution mirrors
  ``host-sync-in-shard-map``: any function whose name is passed as the
  first argument to a ``pallas_call`` in the module, one level down into
  module-local helpers. Error severity — the fused chunk-scan/probe
  kernels (``engine/kernels.py``) are priced at ZERO host syncs by the
  exec-audit sync model, so a violation is a correctness bug.
* ``host-sync-in-prefetch-worker`` — a host-sync primitive, an
  ``ops.host_read``-charging call, or an ``obs.span(...)`` trace
  context inside a callable handed to the bounded prefetch ring
  (``engine/prefetch.py``: the ``prepare`` step of
  ``chunk_ring``/``ChunkRing``, any named function passed to those
  constructors, or the callee of a call expression passed as the
  source iterator — the generator's per-item body runs on the worker
  too). The ring runs these on its WORKER thread, whose sync counters
  and span ring are thread-local: a host read there would charge syncs
  the driver's accounting (and the exec-audit sync model's "prefetch
  worker = 0" row) never sees, and a span would land in the
  ``unattributed`` diagnostics ring instead of the query's trace.
  Resolution mirrors ``host-sync-in-shard-map``: name-based (module-
  local), one level down into module-local helpers. Error severity —
  the worker's zero-sync contract is what lets ingest leave the driver
  thread at all.
* ``swallowed-fault`` — an ``except`` handler that catches one of the
  fault layer's classified errors (``FaultError`` / ``FaultInjected`` /
  ``StatementTimeout``, bare or attribute-qualified) whose body neither
  records a :class:`nds_tpu.engine.faults.FaultEvent`
  (``record_fault_event(...)``) nor re-raises. A recovery path that
  absorbs a classified fault silently breaks the fault-tolerance
  contract's evidence rule (DESIGN.md "Fault-tolerance contract"):
  ``tools/fault_diff.py`` proves FaultEvent counts match injections
  exactly, so a swallowed fault is an un-auditable fallback — exactly
  the failure-as-log-noise pattern the registry exists to end. Error
  severity.
* ``chunk-loop-host-sync`` — a host-sync primitive (``.item()``,
  ``np.asarray``/``np.array``, ``device_get``, ``.to_int()``, or the
  engine's ``host_sync``/``count_int``/``resolve_counts``) lexically
  inside a ``for`` loop over ``device_chunks()``/``padded_chunks()``,
  in ANY module. A >HBM table streams hundreds of chunks: one sync per
  chunk is the O(chunks) control-plane cost the compiled streaming
  executor (``engine/stream.py``) exists to remove — new chunk loops
  must stay device-resident or route through it. The surviving eager
  fallback loop is baselined. The rule also sees ONE level down: a call
  from the loop body to a module-local helper (bare name or
  ``self.method``) whose body syncs directly is flagged at the call
  site — the gap that let a sync hide behind a one-line refactor.
"""

from __future__ import annotations

import ast
import os

from nds_tpu.analysis import Finding, suppressed

# modules whose loops are hot paths (per-query, per-chunk dispatch loops)
HOT_PATH_FILES = ("engine/ops.py", "sql/planner.py")

_SYNC_NP_FUNCS = {"asarray", "array"}
_TIME_FUNCS = {"time", "perf_counter", "perf_counter_ns", "monotonic"}
# iterator methods that yield device chunks of a >HBM streamed table
_CHUNK_ITER_FUNCS = {"device_chunks", "padded_chunks"}
# engine entry points that resolve a device scalar on host
_ENGINE_SYNC_FUNCS = {"host_sync", "count_int", "resolve_counts"}
# obs.trace entry points that open a span (clock + counter reads)
_SPAN_ATTRS = ("span", "op")
# ops.host_read-charging entry points (every counted device->host read
# funnels through host_read; these are the call forms code reaches it by)
_HOST_READ_FUNCS = {"host_read", "timed_read", "guarded_scalar_read"}
# the fault layer's classified error types (engine/faults.py): a handler
# catching one must record a FaultEvent or re-raise (swallowed-fault)
_FAULT_ERROR_NAMES = {"FaultError", "FaultInjected", "StatementTimeout"}
# the recorder call forms a compliant handler may use
_FAULT_RECORD_FUNCS = {"record_fault_event"}


def _sync_primitive(node) -> str | None:
    """The host-sync primitive a Call node invokes, or None. One shared
    matcher for the direct chunk-loop check and the helper pre-pass."""
    f = node.func
    if isinstance(f, ast.Attribute):
        owner = f.value.id if isinstance(f.value, ast.Name) else None
        if f.attr == "item" and not node.args:
            return ".item()"
        if owner in ("np", "numpy") and f.attr in _SYNC_NP_FUNCS:
            return f"np.{f.attr}()"
        if f.attr == "device_get":
            return "device_get()"
        if f.attr == "to_int" and not node.args:
            return ".to_int()"
        if f.attr in _ENGINE_SYNC_FUNCS:
            return f"{f.attr}()"
    elif isinstance(f, ast.Name) and f.id in _ENGINE_SYNC_FUNCS:
        return f"{f.id}()"
    return None


def _collect_sync_helpers(tree) -> dict:
    """Map each module-local function/method to (lineno, primitive) of
    the first host-sync primitive its body calls directly — the
    one-level-down index the chunk-loop rule resolves call sites
    against. Methods are keyed ``(ClassName, name)`` and module-level or
    nested functions ``(None, name)``, so a ``self.helper()`` call only
    resolves against its own class — a same-named method on an unrelated
    class in the module is not evidence. Nested function definitions
    attribute to the innermost def (matching how a call would reach
    them)."""
    helpers: dict = {}

    class _Scan(ast.NodeVisitor):
        def __init__(self):
            self.stack: list = []       # (class-or-None, name) per def
            self.classes: list = []

        def visit_ClassDef(self, node):
            self.classes.append(node.name)
            self.generic_visit(node)
            self.classes.pop()

        def visit_FunctionDef(self, node):
            # a def at class-body level is that class's method; any other
            # def (module-level, or nested in a function) is reachable as
            # a bare name
            cls = self.classes[-1] if self.classes and not self.stack \
                else None
            self.stack.append((cls, node.name))
            self.generic_visit(node)
            self.stack.pop()

        visit_AsyncFunctionDef = visit_FunctionDef

        def visit_Call(self, node):
            what = _sync_primitive(node)
            if what and self.stack:
                helpers.setdefault(self.stack[-1],
                                   (node.lineno, what))
            self.generic_visit(node)

    _Scan().visit(tree)
    return helpers


def _collect_shard_bodies(tree) -> set:
    """Names of functions passed as the first argument to a
    ``shard_map``/``pjit`` call anywhere in the module (including the
    engine's ``shard_map_compat`` shim) — the bodies the
    ``host-sync-in-shard-map`` rule polices. Name-based resolution: the
    conventional pattern defines the body and wraps it in the same
    scope, so a name collision only widens coverage."""
    bodies = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        name = f.id if isinstance(f, ast.Name) else (
            f.attr if isinstance(f, ast.Attribute) else None)
        if name in ("shard_map", "shard_map_compat", "pjit") and \
                node.args and isinstance(node.args[0], ast.Name):
            bodies.add(node.args[0].id)
    return bodies


def _collect_prefetch_bodies(tree) -> set:
    """Names of callables the prefetch ring runs on its worker thread:
    arguments of a ring constructor (``chunk_ring``/``ChunkRing``) —
    positional or keyword, bare name or ``self.method`` — PLUS the
    callee of a call expression passed as the source iterator
    (``chunk_ring(scan.device_chunks(self), ...)``: the generator's
    per-item body runs on the worker too). Name-based like the
    shard/pallas collectors: a collision only widens coverage."""
    bodies = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        name = f.id if isinstance(f, ast.Name) else (
            f.attr if isinstance(f, ast.Attribute) else None)
        if name not in ("chunk_ring", "ChunkRing"):
            continue
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            if isinstance(arg, ast.Name):
                bodies.add(arg.id)
            elif isinstance(arg, ast.Attribute):
                bodies.add(arg.attr)
            elif isinstance(arg, ast.Call):
                cf = arg.func
                if isinstance(cf, ast.Name):
                    bodies.add(cf.id)
                elif isinstance(cf, ast.Attribute):
                    bodies.add(cf.attr)
    return bodies


def _collect_pallas_bodies(tree) -> set:
    """Names of functions passed as the first argument to a
    ``pallas_call`` anywhere in the module (``pl.pallas_call(kernel,
    ...)`` / bare ``pallas_call``) — the kernel bodies the
    ``host-read-in-pallas`` rule polices. Name-based resolution like
    ``_collect_shard_bodies``: the conventional pattern defines the
    body and wraps it in the same scope."""
    bodies = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        name = f.id if isinstance(f, ast.Name) else (
            f.attr if isinstance(f, ast.Attribute) else None)
        if name == "pallas_call" and node.args and \
                isinstance(node.args[0], ast.Name):
            bodies.add(node.args[0].id)
    return bodies


def _is_jit_decorator(dec) -> tuple[bool, set]:
    """(is jax.jit, static arg positions/names) for one decorator node."""
    static: set = set()
    # @jax.jit / @jit
    if isinstance(dec, ast.Attribute) and dec.attr == "jit":
        return True, static
    if isinstance(dec, ast.Name) and dec.id == "jit":
        return True, static
    # @functools.partial(jax.jit, static_argnums=(..)) / static_argnames
    # and the decorator-factory spelling @jax.jit(static_argnums=(..))
    if isinstance(dec, ast.Call):
        f = dec.func
        is_partial = (isinstance(f, ast.Attribute) and f.attr == "partial") \
            or (isinstance(f, ast.Name) and f.id == "partial")
        is_jit_factory = (isinstance(f, ast.Attribute) and f.attr == "jit") \
            or (isinstance(f, ast.Name) and f.id == "jit")
        if (is_partial and dec.args and _is_jit_decorator(dec.args[0])[0]) \
                or is_jit_factory:
            for kw in dec.keywords:
                if kw.arg in ("static_argnums", "static_argnames"):
                    for elt in ast.walk(kw.value):
                        if isinstance(elt, ast.Constant):
                            static.add(elt.value)
            return True, static
    return False, static


class _Lint(ast.NodeVisitor):
    def __init__(self, path: str, rel: str, source: str,
                 sync_helpers: dict | None = None,
                 shard_bodies: set | None = None,
                 pallas_bodies: set | None = None,
                 prefetch_bodies: set | None = None):
        self.rel = rel
        self.sync_helpers = sync_helpers or {}
        self.shard_bodies = shard_bodies or set()
        self.shard_depth = 0         # inside a shard_map/pjit body
        self.pallas_bodies = pallas_bodies or set()
        self.pallas_depth = 0        # inside a pallas_call kernel body
        self.prefetch_bodies = prefetch_bodies or set()
        self.prefetch_depth = 0      # inside a prefetch-worker callable
        self.lines = source.splitlines()
        self.findings: list = []
        self.scope_stack = ["<module>"]
        self.class_stack: list = []  # enclosing class names (self.X calls)
        self.loop_depth = 0
        self.chunk_loop_depth = 0    # for-loops over device/padded chunks
        self.jit_params: list = []   # stack of traced-param name sets
        self.jit_depth = 0           # count of enclosing jax.jit functions
        self.is_hot = any(rel.endswith(h) for h in HOT_PATH_FILES)
        # *_CACHE dicts assigned at module level in this file
        self.module_caches: set = set()
        self.cache_writes: dict = {}     # name -> [lineno]
        self.cache_evictions: set = set()
        # a module cache is often threaded through a helper as a plain
        # parameter (planner's `_fused_run(self, cache, ...)`): record how
        # each function USES its parameters cache-wise, plus every call
        # site that passes a *_CACHE in, and join the two at finish()
        self.fn_param_use: dict = {}     # func name -> (params, records)
        self.param_use_stack: list = []  # (param names, {param: record})
        self.cache_arg_calls: list = []  # (callee, pos|kwarg, cache name)
        # span-in-jit: names that refer to the obs trace module (by
        # convention or import alias) and to its span() function (by
        # from-import). An unrelated .span() — re.Match.span(), a local
        # helper — must NOT trip the rule.
        self.obs_aliases: set = {"obs", "_obs", "obs_trace"}
        self.span_funcs: set = set()

    def visit_Import(self, node):
        for a in node.names:
            if a.asname and a.name.startswith("nds_tpu.obs"):
                self.obs_aliases.add(a.asname)
        self.generic_visit(node)

    def visit_ImportFrom(self, node):
        mod = node.module or ""
        if mod.startswith("nds_tpu.obs") or mod == "nds_tpu":
            for a in node.names:
                bound = a.asname or a.name
                if a.name == "span":
                    self.span_funcs.add(bound)
                elif a.name in ("trace", "export", "obs"):
                    # only actual submodule names become module aliases —
                    # a from-imported function/class (SpanRecord, rollup)
                    # is not an owner whose .span() is a trace context
                    self.obs_aliases.add(bound)
        self.generic_visit(node)

    def _emit(self, rule: str, severity: str, message: str,
              lineno: int) -> None:
        if suppressed(self.lines, lineno, rule):
            return
        self.findings.append(Finding(self.rel, self.scope_stack[-1], rule,
                                     severity, message, lineno))

    # -- scope / jit tracking ----------------------------------------------

    def visit_FunctionDef(self, node):
        jit_static: set | None = None
        for dec in node.decorator_list:
            is_jit, static = _is_jit_decorator(dec)
            if is_jit:
                jit_static = static
        self.scope_stack.append(node.name)
        args = node.args
        names = [a.arg for a in
                 args.posonlyargs + args.args + args.kwonlyargs]
        if jit_static is not None:
            traced = {n for i, n in enumerate(names)
                      if i not in jit_static and n not in jit_static}
            self.jit_depth += 1
        elif self.jit_depth:
            # a nested helper defined inside a jit function still runs
            # under the trace: closures over the enclosing traced params
            # stay traced (its own params shadow them — their tracedness
            # is not knowable statically, so they are not flagged)
            traced = (self.jit_params[-1] if self.jit_params
                      else set()) - set(names)
        else:
            traced = set()
        self.jit_params.append(traced)
        self.param_use_stack.append((names, {}))
        is_shard = node.name in self.shard_bodies
        self.shard_depth += is_shard
        is_pallas = node.name in self.pallas_bodies
        self.pallas_depth += is_pallas
        is_prefetch = node.name in self.prefetch_bodies
        self.prefetch_depth += is_prefetch
        saved_loop = self.loop_depth
        saved_chunk = self.chunk_loop_depth
        self.loop_depth = 0
        self.chunk_loop_depth = 0
        self.generic_visit(node)
        self.loop_depth = saved_loop
        self.chunk_loop_depth = saved_chunk
        self.shard_depth -= is_shard
        self.pallas_depth -= is_pallas
        self.prefetch_depth -= is_prefetch
        self.jit_params.pop()
        if jit_static is not None:
            self.jit_depth -= 1
        self.fn_param_use[node.name] = self.param_use_stack.pop()
        self.scope_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_ClassDef(self, node):
        self.class_stack.append(node.name)
        self.generic_visit(node)
        self.class_stack.pop()

    def _in_jit(self) -> bool:
        return self.jit_depth > 0

    # -- loops --------------------------------------------------------------

    def visit_For(self, node):
        is_chunk = any(
            isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)
            and n.func.attr in _CHUNK_ITER_FUNCS
            for n in ast.walk(node.iter))
        self.loop_depth += 1
        self.chunk_loop_depth += is_chunk
        self.generic_visit(node)
        self.chunk_loop_depth -= is_chunk
        self.loop_depth -= 1

    def visit_While(self, node):
        self._check_tracer_test(node.test, node.lineno, "while")
        self.loop_depth += 1
        self.generic_visit(node)
        self.loop_depth -= 1

    # -- fault-layer recovery paths -----------------------------------------

    def visit_Try(self, node):
        for h in node.handlers:
            if h.type is not None and self._catches_fault_error(h.type):
                if not self._handler_records_or_raises(h):
                    self._emit(
                        "swallowed-fault", "error",
                        "except clause catches a classified fault "
                        "(FaultError family) but neither records a "
                        "FaultEvent (record_fault_event) nor re-raises "
                        "— recovery paths must stay auditable "
                        "(DESIGN.md 'Fault-tolerance contract')",
                        h.lineno)
        self.generic_visit(node)

    visit_TryStar = visit_Try

    @staticmethod
    def _catches_fault_error(type_expr) -> bool:
        """Does the handler's type expression name one of the fault
        layer's classified errors (bare, attribute-qualified, or inside
        a tuple)?"""
        for n in ast.walk(type_expr):
            if isinstance(n, ast.Name) and n.id in _FAULT_ERROR_NAMES:
                return True
            if isinstance(n, ast.Attribute) and \
                    n.attr in _FAULT_ERROR_NAMES:
                return True
        return False

    @staticmethod
    def _handler_records_or_raises(handler) -> bool:
        for stmt in handler.body:
            for n in ast.walk(stmt):
                if isinstance(n, ast.Raise):
                    return True
                if isinstance(n, ast.Call):
                    f = n.func
                    name = f.id if isinstance(f, ast.Name) else (
                        f.attr if isinstance(f, ast.Attribute) else None)
                    if name in _FAULT_RECORD_FUNCS:
                        return True
        return False

    def visit_If(self, node):
        self._check_tracer_test(node.test, node.lineno, "if")
        self.generic_visit(node)

    def _check_tracer_test(self, test, lineno: int, kind: str) -> None:
        if not self._in_jit():
            return
        traced = self.jit_params[-1]

        def hazardous(node) -> bool:
            # identity tests (x is None) are pytree-structure checks and
            # .dtype/.shape/.ndim/.size are static metadata — both are
            # legal on tracers
            if isinstance(node, ast.Compare) and \
                    all(isinstance(op, (ast.Is, ast.IsNot))
                        for op in node.ops):
                return False
            if isinstance(node, ast.Attribute) and \
                    node.attr in ("dtype", "shape", "ndim", "size"):
                return False
            if isinstance(node, ast.Name):
                return node.id in traced
            return any(hazardous(c) for c in ast.iter_child_nodes(node))

        if hazardous(test):
            names = sorted({n.id for n in ast.walk(test)
                            if isinstance(n, ast.Name) and n.id in traced})
            self._emit("tracer-if", "error",
                       f"Python {kind} on traced parameter "
                       f"{', '.join(repr(n) for n in names)} inside a "
                       "jax.jit function", lineno)

    # -- calls / attributes -------------------------------------------------

    def _check_chunk_loop_sync(self, node) -> None:
        """Flag host syncs inside a ``device_chunks()``/``padded_chunks()``
        loop: per-chunk host decisions are the O(chunks) dispatch cost the
        compiled streaming executor removes (engine/stream.py)."""
        if not self.chunk_loop_depth:
            return
        what = _sync_primitive(node)
        if what:
            self._emit("chunk-loop-host-sync", "warning",
                       f"{what} inside a device_chunks() loop syncs once "
                       "per chunk (O(chunks) round trips); keep the chunk "
                       "pipeline device-resident or route it through the "
                       "compiled streaming executor", node.lineno)
            return
        # one level down: a call to a module-local helper whose body syncs
        # directly — the refactor that used to hide a per-chunk sync.
        # ``self.helper()`` resolves only against the enclosing class's
        # methods; a bare name only against module-level/nested functions.
        f = node.func
        key = None
        if isinstance(f, ast.Name):
            key = (None, f.id)
        elif isinstance(f, ast.Attribute) and \
                isinstance(f.value, ast.Name) and f.value.id == "self" \
                and self.class_stack:
            key = (self.class_stack[-1], f.attr)
        hit = key is not None and self.sync_helpers.get(key)
        if hit and key[1] not in _CHUNK_ITER_FUNCS:
            lineno, prim = hit
            self._emit("chunk-loop-host-sync", "warning",
                       f"{key[1]}() (defined in this module, syncs via "
                       f"{prim} at line {lineno}) called inside a "
                       "device_chunks() loop: one host sync per chunk "
                       "hidden one level down", node.lineno)

    def _check_shard_map_sync(self, node) -> None:
        """Flag host reads / spans inside a shard_map or pjit body: the
        body is one traced SPMD program — a host read there is a tracer
        hazard and a full-mesh barrier, a span clocks the trace."""
        if not self.shard_depth:
            return
        f = node.func
        what = _sync_primitive(node)
        if what is None:
            if isinstance(f, ast.Attribute) and \
                    f.attr in _HOST_READ_FUNCS:
                what = f"{f.attr}()"
            elif isinstance(f, ast.Name) and f.id in _HOST_READ_FUNCS:
                what = f"{f.id}()"
        is_span = (isinstance(f, ast.Attribute) and f.attr in _SPAN_ATTRS
                   and isinstance(f.value, ast.Name)
                   and f.value.id in self.obs_aliases) or \
            (isinstance(f, ast.Name) and f.id in self.span_funcs)
        if what or is_span:
            self._emit("host-sync-in-shard-map", "error",
                       f"{what or 'obs.span(...)'} inside a shard_map/"
                       "pjit body: the body is one traced SPMD program — "
                       "host reads are tracer hazards and full-mesh "
                       "barriers; resolve on host before the dispatch or "
                       "ride the overflow/collective channels",
                       node.lineno)
            return
        # one level down: a module-local helper whose body syncs directly
        key = None
        if isinstance(f, ast.Name):
            key = (None, f.id)
        elif isinstance(f, ast.Attribute) and \
                isinstance(f.value, ast.Name) and f.value.id == "self" \
                and self.class_stack:
            key = (self.class_stack[-1], f.attr)
        hit = key is not None and self.sync_helpers.get(key)
        if hit:
            lineno, prim = hit
            self._emit("host-sync-in-shard-map", "error",
                       f"{key[1]}() (defined in this module, syncs via "
                       f"{prim} at line {lineno}) called inside a "
                       "shard_map/pjit body: one host sync per dispatch "
                       "hidden one level down", node.lineno)

    def _check_pallas_sync(self, node) -> None:
        """Flag host reads / spans inside a pallas_call kernel body: the
        body compiles to a Mosaic program running per grid cell on the
        device — host reads cannot exist there, spans would clock the
        kernel trace."""
        if not self.pallas_depth:
            return
        f = node.func
        what = _sync_primitive(node)
        if what is None:
            if isinstance(f, ast.Attribute) and \
                    f.attr in _HOST_READ_FUNCS:
                what = f"{f.attr}()"
            elif isinstance(f, ast.Name) and f.id in _HOST_READ_FUNCS:
                what = f"{f.id}()"
        is_span = (isinstance(f, ast.Attribute) and f.attr in _SPAN_ATTRS
                   and isinstance(f.value, ast.Name)
                   and f.value.id in self.obs_aliases) or \
            (isinstance(f, ast.Name) and f.id in self.span_funcs)
        if what or is_span:
            self._emit("host-read-in-pallas", "error",
                       f"{what or 'obs.span(...)'} inside a pallas_call "
                       "kernel body: the kernel is one Mosaic device "
                       "program per grid cell — host reads cannot exist "
                       "there and spans clock the kernel trace; compute "
                       "on refs only and resolve on host outside the "
                       "launch", node.lineno)
            return
        # one level down: a module-local helper whose body syncs directly
        key = None
        if isinstance(f, ast.Name):
            key = (None, f.id)
        elif isinstance(f, ast.Attribute) and \
                isinstance(f.value, ast.Name) and f.value.id == "self" \
                and self.class_stack:
            key = (self.class_stack[-1], f.attr)
        hit = key is not None and self.sync_helpers.get(key)
        if hit:
            lineno, prim = hit
            self._emit("host-read-in-pallas", "error",
                       f"{key[1]}() (defined in this module, syncs via "
                       f"{prim} at line {lineno}) called inside a "
                       "pallas_call kernel body: a host sync hidden one "
                       "level down", node.lineno)

    def _check_prefetch_sync(self, node) -> None:
        """Flag host reads / spans inside a callable the prefetch ring
        runs on its worker thread: the worker's sync counters and span
        ring are thread-local, so a sync there escapes the driver's
        accounting (the exec-audit "prefetch worker = 0 host syncs"
        row) and a span lands unattributed."""
        if not self.prefetch_depth:
            return
        f = node.func
        what = _sync_primitive(node)
        if what is None:
            if isinstance(f, ast.Attribute) and \
                    f.attr in _HOST_READ_FUNCS:
                what = f"{f.attr}()"
            elif isinstance(f, ast.Name) and f.id in _HOST_READ_FUNCS:
                what = f"{f.id}()"
        is_span = (isinstance(f, ast.Attribute) and f.attr in _SPAN_ATTRS
                   and isinstance(f.value, ast.Name)
                   and f.value.id in self.obs_aliases) or \
            (isinstance(f, ast.Name) and f.id in self.span_funcs)
        if what or is_span:
            self._emit("host-sync-in-prefetch-worker", "error",
                       f"{what or 'obs.span(...)'} inside a prefetch-"
                       "ring worker callable: the worker's sync "
                       "counters and span ring are thread-local — a "
                       "host read there escapes the driver's sync "
                       "accounting and a span lands unattributed; "
                       "resolve on the driver before handing work to "
                       "the ring", node.lineno)
            return
        # one level down: a module-local helper whose body syncs directly
        key = None
        if isinstance(f, ast.Name):
            key = (None, f.id)
        elif isinstance(f, ast.Attribute) and \
                isinstance(f.value, ast.Name) and f.value.id == "self" \
                and self.class_stack:
            key = (self.class_stack[-1], f.attr)
        hit = key is not None and self.sync_helpers.get(key)
        if hit:
            lineno, prim = hit
            self._emit("host-sync-in-prefetch-worker", "error",
                       f"{key[1]}() (defined in this module, syncs via "
                       f"{prim} at line {lineno}) called inside a "
                       "prefetch-ring worker callable: a host sync "
                       "hidden one level down", node.lineno)

    def visit_Call(self, node):
        self._check_chunk_loop_sync(node)
        self._check_shard_map_sync(node)
        self._check_pallas_sync(node)
        self._check_prefetch_sync(node)
        f = node.func
        if isinstance(f, ast.Attribute):
            owner = f.value.id if isinstance(f.value, ast.Name) else None
            if self.is_hot and self.loop_depth > 0:
                if f.attr == "item" and not node.args:
                    self._emit("host-sync-in-loop", "warning",
                               ".item() inside a hot-path loop blocks on "
                               "device->host transfer per iteration",
                               node.lineno)
                elif owner in ("np", "numpy") and \
                        f.attr in _SYNC_NP_FUNCS:
                    self._emit("host-sync-in-loop", "warning",
                               f"np.{f.attr}() inside a hot-path loop "
                               "forces a device->host copy per iteration",
                               node.lineno)
                elif f.attr == "device_get":
                    self._emit("host-sync-in-loop", "warning",
                               "device_get() inside a hot-path loop",
                               node.lineno)
            if owner in ("time", "_time") and f.attr in _TIME_FUNCS and \
                    self._in_jit():
                self._emit("time-in-jit", "error",
                           f"time.{f.attr}() inside a jax.jit function is "
                           "evaluated once at trace time", node.lineno)
            if f.attr in _SPAN_ATTRS and owner in self.obs_aliases and \
                    self._in_jit():
                self._emit("span-in-jit", "error",
                           "obs.span(...) inside a jax.jit function reads "
                           "the host clock at trace time (tracer hazard); "
                           "open the span around the jitted call instead",
                           node.lineno)
        elif isinstance(f, ast.Name) and f.id in self.span_funcs and \
                self._in_jit():
            self._emit("span-in-jit", "error",
                       "span(...) inside a jax.jit function reads the "
                       "host clock at trace time (tracer hazard); open "
                       "the span around the jitted call instead",
                       node.lineno)
        self._note_cache_method_write(node)
        # a *_CACHE passed as an argument aliases it to the callee's
        # parameter — resolved against the callee's use at finish()
        callee, self_off = None, 0
        if isinstance(f, ast.Name):
            callee = f.id
        elif isinstance(f, ast.Attribute) and \
                isinstance(f.value, ast.Name) and f.value.id == "self":
            callee, self_off = f.attr, 1
        if callee is not None:
            for i, a in enumerate(node.args):
                cname = self._is_cache_name(a)
                if cname:
                    self.cache_arg_calls.append(
                        (callee, i + self_off, cname))
            for kw in node.keywords:
                cname = self._is_cache_name(kw.value)
                if cname and kw.arg is not None:
                    self.cache_arg_calls.append((callee, kw.arg, cname))
        self.generic_visit(node)

    # -- cache hygiene ------------------------------------------------------

    def _is_cache_name(self, node) -> str | None:
        if isinstance(node, ast.Name) and node.id.endswith("_CACHE"):
            return node.id
        return None

    def _param_record(self, node) -> dict | None:
        """The cache-use record for ``node`` when it names a parameter of
        the innermost function, else None."""
        if not (isinstance(node, ast.Name) and self.param_use_stack):
            return None
        params, records = self.param_use_stack[-1]
        if node.id not in params:
            return None
        return records.setdefault(node.id, {
            "write": None, "evict": False, "keyhaz": [],
            "scope": self.scope_stack[-1]})

    def visit_Assign(self, node):
        # module-level NAME_CACHE = {} / dict()
        if self.scope_stack == ["<module>"]:
            for tgt in node.targets:
                name = self._is_cache_name(tgt)
                if name and isinstance(node.value, (ast.Dict, ast.Call)):
                    self.module_caches.add(name)
        # NAME_CACHE[key] = value
        for tgt in node.targets:
            if isinstance(tgt, ast.Subscript):
                name = self._is_cache_name(tgt.value)
                if name:
                    self.cache_writes.setdefault(name, []).append(
                        tgt.lineno)
                    self._check_cache_key(name, tgt.slice, tgt.lineno)
                else:
                    rec = self._param_record(tgt.value)
                    if rec is not None:
                        if rec["write"] is None:
                            rec["write"] = tgt.lineno
                        rec["keyhaz"].extend(
                            self._key_hazards(tgt.slice, tgt.lineno))
        self.generic_visit(node)

    def visit_AnnAssign(self, node):
        if self.scope_stack == ["<module>"]:
            name = self._is_cache_name(node.target)
            if name and node.value is not None:
                self.module_caches.add(name)
        self.generic_visit(node)

    def visit_Subscript(self, node):
        if isinstance(node.ctx, ast.Load):
            name = self._is_cache_name(node.value)
            if name:
                self._check_cache_key(name, node.slice, node.lineno)
            else:
                rec = self._param_record(node.value)
                if rec is not None:
                    rec["keyhaz"].extend(
                        self._key_hazards(node.slice, node.lineno))
        self.generic_visit(node)

    def visit_Compare(self, node):
        # len(NAME_CACHE) >= ... counts as eviction evidence
        for sub in [node.left] + list(node.comparators):
            if isinstance(sub, ast.Call) and \
                    isinstance(sub.func, ast.Name) and \
                    sub.func.id == "len" and sub.args:
                name = self._is_cache_name(sub.args[0])
                if name:
                    self.cache_evictions.add(name)
                else:
                    rec = self._param_record(sub.args[0])
                    if rec is not None:
                        rec["evict"] = True
        self.generic_visit(node)

    def visit_Attribute(self, node):
        if node.attr in ("pop", "popitem", "clear"):
            name = self._is_cache_name(node.value)
            if name:
                self.cache_evictions.add(name)
            else:
                rec = self._param_record(node.value)
                if rec is not None:
                    rec["evict"] = True
        self.generic_visit(node)

    def _note_cache_method_write(self, node) -> None:
        """CACHE.setdefault(k, v) / CACHE.update(...) grow the cache like a
        subscript store does (setdefault's first argument is the key)."""
        f = node.func
        if not (isinstance(f, ast.Attribute)
                and f.attr in ("setdefault", "update")):
            return
        name = self._is_cache_name(f.value)
        if name:
            self.cache_writes.setdefault(name, []).append(node.lineno)
            if f.attr == "setdefault" and node.args:
                self._check_cache_key(name, node.args[0], node.lineno)
            return
        rec = self._param_record(f.value)
        if rec is not None:
            if rec["write"] is None:
                rec["write"] = node.lineno
            if f.attr == "setdefault" and node.args:
                rec["keyhaz"].extend(
                    self._key_hazards(node.args[0], node.lineno))

    def _key_hazards(self, key, lineno: int) -> list:
        for n in ast.walk(key):
            if isinstance(n, (ast.List, ast.ListComp, ast.Set, ast.SetComp,
                              ast.Dict, ast.DictComp)):
                return [(lineno, type(n).__name__)]
        return []

    def _check_cache_key(self, name: str, key, lineno: int) -> None:
        for lineno, tname in self._key_hazards(key, lineno):
            self._emit("cache-key-list", "error",
                       f"raw {tname} in {name} key: unhashable and "
                       "unbounded as a jit-cache key", lineno)

    def _resolve_cache_aliases(self) -> None:
        """Join call sites that pass a module *_CACHE with the callee's
        parameter use, so writes/evictions/key hazards through the alias
        count against the module cache."""
        emitted: set = set()
        for callee, pos, cname in self.cache_arg_calls:
            info = self.fn_param_use.get(callee)
            if info is None:
                continue
            params, records = info
            pname = pos if isinstance(pos, str) else (
                params[pos] if pos < len(params) else None)
            rec = records.get(pname)
            if rec is None:
                continue
            if rec["write"] is not None:
                self.cache_writes.setdefault(cname, []).append(rec["write"])
            if rec["evict"]:
                self.cache_evictions.add(cname)
            for lineno, tname in rec["keyhaz"]:
                if (lineno, cname) in emitted:
                    continue
                emitted.add((lineno, cname))
                self.scope_stack = ["<module>", rec["scope"]]
                self._emit("cache-key-list", "error",
                           f"raw {tname} in {cname} key (through parameter "
                           f"{pname!r} of {callee}()): unhashable and "
                           "unbounded as a jit-cache key", lineno)

    def finish(self) -> None:
        self._resolve_cache_aliases()
        for name in sorted(self.module_caches):
            writes = self.cache_writes.get(name)
            if writes and name not in self.cache_evictions:
                self.scope_stack = ["<module>"]
                self._emit("unbounded-cache", "warning",
                           f"{name} grows without eviction (no len() "
                           "guard or pop/popitem/clear in module)",
                           writes[0])


def lint_file(path: str, rel: str | None = None) -> list:
    with open(path) as f:
        source = f.read()
    rel = rel or path
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [Finding(rel, "<module>", "syntax-error", "error",
                        str(e), e.lineno or 0)]
    lint = _Lint(path, rel, source, _collect_sync_helpers(tree),
                 _collect_shard_bodies(tree), _collect_pallas_bodies(tree),
                 _collect_prefetch_bodies(tree))
    lint.visit(tree)
    lint.finish()
    return lint.findings


def lint_tree(root: str | None = None) -> list:
    """Lint every ``.py`` file under ``nds_tpu/`` (or ``root``)."""
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    repo = os.path.dirname(os.path.abspath(root))
    findings: list = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                p = os.path.join(dirpath, fn)
                findings.extend(lint_file(p, os.path.relpath(p, repo)))
    return findings
