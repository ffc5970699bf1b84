# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Concurrency auditor: shared-state inventory + lock-discipline lint.

The serving front (ROADMAP item 5) runs concurrent query streams through
ONE process: the pipeline cache, the expression-fusion caches, the mesh
cache, the listener and the span tracer are all shared mutable state on
the query path. This pass is the static half of the concurrency
contract (the runtime half is ``tools/conc_audit_diff.py``'s threaded
stress differential): it inventories every module-level and class-level
mutable object in ``nds_tpu/`` plus every ``threading.local``/``Lock``,
classifies each mutation site, and enforces the lock discipline the
engine's caches follow. Python-``ast`` based like ``jax_lint``; no JAX
import, no device. Suppressible in-source with
``# nds-lint: ignore[rule]``.

State classification — every mutation site of a module/class-level
object must fall into one of the ACCEPTED classes:

* **lock-guarded** — the mutation is lexically dominated by a
  ``with <lock>`` on a module/class-level ``threading.Lock``/``RLock``,
  and every other guarded mutation of the same state uses the SAME lock
  (a lock dedicated to that state — two locks "guarding" one dict is a
  race with extra steps). Aliasing through plain parameters is resolved
  like ``jax_lint``'s cache rules: ``_identity_cache(cache, ...)`` /
  ``_fused_run(cache, ...)`` mutation sites count against the module
  global each call site passes in, carrying the callee's guard.
* **thread-local** — an attribute store on a module-level
  ``threading.local()``: per-thread by construction (the sync counters,
  span rings, StreamEvent rings).
* **bounded-evidence-ring** — ``append``/``appendleft``/``clear`` on a
  module/class-level ``deque(maxlen=...)`` (the listener's
  ``unattributed`` pattern): GIL-atomic single-op mutations of a bounded
  diagnostics ring; a torn multi-op invariant cannot exist because there
  is no multi-op invariant.
* **atomic-rebind** — a plain ``global NAME; NAME = <expr>`` rebind of a
  module scalar/flag (``_pallas_broken``, ``trace._enabled``): one
  GIL-atomic pointer store, last-writer-wins by design. An AUGMENTED
  rebind (``NAME += 1``) is a read-modify-write and stays a finding, and
  a rebind of a container that elsewhere has a dedicated lock must hold
  that lock.
* module import scope — mutations at module body level run under the
  import lock, exactly once; exempt.

Everything else is **unguarded-mutation** (error when the site is
reachable from the concurrent entry points — Planner statement
execution via ``Session.sql``, pipeline build/drive, the listener/span
drains, the throughput driver threads, the bench heartbeat — warning
otherwise).

Lock-discipline rules:

* ``mixed-guard`` — state mutated under a lock at one site and off-lock
  (or under a different lock) at another: the lock protects nothing.
* ``sync-under-lock`` — an ``ops.host_read``-family call (``host_read``,
  ``timed_read``, ``guarded_scalar_read``, ``host_sync``, ``count_int``,
  ``resolve_counts``, ``.item()``, ``.to_int()``, ``device_get``)
  lexically inside a ``with <lock>`` body, directly or one level down
  into a module-local helper: a device->host sync holds every waiter for
  a full round trip (and under GSPMD a full-mesh barrier).
* ``compile-under-lock`` — a ``jax.jit(...)`` call (or a one-level-down
  helper that makes one) inside a ``with <lock>`` body: a compile under
  ``_PIPELINE_LOCK`` would serialize every Throughput stream behind
  XLA's optimizer. The engine's pattern is claim-under-lock /
  compile-off-lock / land-under-lock (the singleflight registries).
* ``wait-under-lock`` — a blocking ``.wait()``/``.join()``/``.get()``
  inside a ``with <lock>`` body: the classic lost-wakeup/deadlock shape
  (the waiter holds the lock its waker needs).
* ``lock-order-cycle`` — the directed acquired-while-holding graph
  (lexical ``with`` nesting plus one level down through precisely
  resolved calls) contains a cycle: two threads taking the locks in
  opposite orders deadlock. Acyclic order = deadlock-free.

Cache-key completeness (the rule PR 9 established by hand for encodings
and PR 12 for the Pallas mode, now checked statically): every recognized
cache declares its key-building and value-building functions in
:data:`CACHE_REGISTRY`; every env knob (``os.environ`` read) reachable
from the value builder through the package call graph must appear in the
knob set reachable from the key expression, or be exempted by name WITH
a justification (``cache-key-missing-knob`` otherwise). A module-level
``*_CACHE``/``*_cache``-named dict mutated by key anywhere that is NOT
registered raises ``cache-unregistered`` — a new cache must declare its
contract to land, which is the "nothing stops the next PR" hook.

Import-time env freeze (``env-freeze``): a module-level constant
assigned from ``os.environ`` at import bakes the process start
environment into compiled behavior — the ``_ACC_ROWS``/``_STREAM_FANOUT``
bug class PR 6 fixed. Knobs read at build/use time (functions) are the
accepted pattern; a deliberate process-lifetime freeze (``_MIN_BUCKET``)
carries an in-source suppression with its justification.
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field

from nds_tpu.analysis import Finding, suppressed

# ---------------------------------------------------------------------------
# matchers
# ---------------------------------------------------------------------------

# constructors whose module-level assignment is shared mutable state
_CONTAINER_CTORS = {"dict", "list", "set", "defaultdict", "OrderedDict"}
_LOCK_CTORS = {"Lock", "RLock"}
_MUTATING_METHODS = {"append", "appendleft", "extend", "add", "insert",
                     "remove", "discard", "pop", "popitem", "popleft",
                     "clear", "update", "setdefault", "sort", "reverse"}
_RING_METHODS = {"append", "appendleft", "clear"}
# ops.host_read-family: every counted device->host read funnels through
# these entry points (shared with jax_lint's shard-map/pallas rules)
_HOST_READ_FUNCS = {"host_read", "timed_read", "guarded_scalar_read",
                    "host_sync", "count_int", "resolve_counts"}

# concurrent entry points: functions the Throughput driver threads, the
# bench heartbeat, and the per-query path enter from multiple threads at
# once. Matched as (path suffix, function-name prefix); reachability is
# the call-graph closure from here.
ENTRY_POINTS = (
    ("engine/session.py", "sql"),            # Planner statement execution
    ("engine/stream.py", "stream_execute"),  # pipeline build/drive
    # the bounded prefetch ring: its worker thread runs concurrently
    # with the driver by construction. All ring state is INSTANCE-scoped
    # (one queue + stop event per ring, never module-level), handed
    # between exactly two threads through the queue's own lock —
    # workers never touch the session caches — so the inventory below
    # stays at zero findings; the runtime half is conc_audit_diff's
    # ring-liveness probe.
    ("engine/prefetch.py", ""),
    # the fault registry + recovery layer: fault_point/with_retry run on
    # every thread (drivers, ring workers, watchdog helpers). Its shared
    # state is exactly three things — the occurrence counters (ONE dict
    # under the dedicated _FAULT_LOCK), the FaultEvent ring
    # (thread-local deque(maxlen)), and the statement clock
    # (thread-local) — so the inventory stays at zero findings; the
    # runtime half is tools/fault_diff.py's injection matrix.
    ("engine/faults.py", ""),
    ("listener.py", "record_stream_event"),
    ("listener.py", "drain_stream_events"),
    ("listener.py", "report_task_failure"),
    ("listener.py", "notify_all"),
    ("obs/trace.py", "span"),
    ("obs/trace.py", "annotate"),
    ("obs/trace.py", "drain_spans"),
    ("obs/trace.py", "note_sync"),
    ("obs/trace.py", "attach"),
    ("obs/ledger.py", "beat"),               # bench heartbeat thread
    ("obs/ledger.py", "_loop"),
    # the live-metrics registry: inc/observe run on every driver thread
    # (query loop, heartbeat, admission waits) while snapshot/export
    # reads from the heartbeat thread. All counter/gauge/histogram
    # state is INSTANCE-scoped on the Registry behind its ONE dedicated
    # _lock; module level holds only import-time constants (EDGES, the
    # metric-name vocabulary) and the _DEFAULT instance binding — so the
    # whole-module inventory stays at zero findings; the runtime half is
    # conc_audit_diff's "metrics" lock probe (threaded-quantile drift).
    ("obs/metrics.py", ""),
    # the campaign driver: single-threaded BY CONTRACT — all run state
    # (manifest dict, in-flight child handle) is local to run_campaign,
    # module level holds only import-time constants (PRESETS, knob
    # tuple), and the only cross-thread surface it touches is the fault
    # registry's thread-local ring — so the whole-module inventory stays
    # at zero findings; auditing it whole pins that contract against a
    # future "parallel arms" edit quietly adding shared state.
    ("obs/campaign.py", ""),
    ("parallel/admission.py", ""),           # admission runs per stream
    ("parallel/exchange.py", "stream_mesh"),
    ("parallel/exchange.py", "exchange_join_pairs"),
)


@dataclass
class CacheSpec:
    """Registered contract of one recognized cache: which functions build
    its key and its value, which modules the value-build closure may span
    (method calls resolve by name inside this set only — the planner
    drives ops/kernels/exprs through instance methods the call graph
    cannot type), and which reachable knobs are deliberately NOT key
    members, each with its justification."""

    key_fns: tuple
    builder_fns: tuple
    modules: tuple                      # path suffixes the closure spans
    exempt: dict = field(default_factory=dict)   # knob -> justification
    identity_keyed: bool = False        # value derives from keyed arrays
    #                                     alone: env-exempt by design


# the engine's module-set for caches whose value is a traced program of
# planner/engine code (the pipeline and the fusion caches)
_ENGINE_MODULES = ("engine/stream.py", "sql/planner.py", "engine/ops.py",
                   "engine/kernels.py", "engine/exprs.py",
                   "engine/column.py", "engine/table.py",
                   "engine/window.py", "parallel/exchange.py",
                   "analysis/mem_audit.py", "io/columnar.py")

# knobs that are deliberately not pipeline-key members; every entry is a
# reviewed claim the stress differential can falsify
_PIPELINE_EXEMPT = {
    "NDS_TPU_STREAM_STRICT": "error ROUTING only: strict re-raises "
    "instead of falling back eager; the compiled program is identical",
    "NDS_TPU_STREAM_EXEC": "routing decided BEFORE the cache is "
    "consulted (eager escape hatch never reaches the build)",
    "NDS_TPU_NO_EXPR_FUSE": "inside the pipeline trace both arms inline "
    "into the same recorded program; the fusion caches are bypassed, "
    "not re-keyed",
    "NDS_TPU_NO_PK_GATHER": "plan-shape knob: its effect changes "
    "join_preds/sources, which are key members",
    "NDS_TPU_DEFER_FILTER_MAX_ROWS": "its effect is the part's physical "
    "length, which is a key member via part specs",
    "NDS_TPU_ENCODED": "encodings ride the chunk/part specs, which are "
    "key members (enc_key per column)",
    "NDS_TPU_STREAM_CHUNK_ROWS": "chunk capacity is a key member "
    "(chunk_cap) — the knob only feeds table construction",
    "NDS_TPU_MIN_BUCKET": "deliberately import-frozen process-wide "
    "shape contract (ops._MIN_BUCKET, suppressed env-freeze): "
    "mem_audit's live read equals the frozen value under the contract, "
    "so the key cannot go stale within one process",
    "NDS_TPU_CHUNK_STORE": "source routing only: the persistent chunk "
    "store's wire path produces bit-identical buffers (same codecs, "
    "same lowering math, encodings already key members via enc_key), "
    "so a store on/off flip can never stale a compiled pipeline",
    "NDS_TPU_CHUNK_STORE_VERIFY": "load-time CRC toggle only: it "
    "decides whether wire files are verified before the mmap, never "
    "what the buffers contain — same bit-identical-buffers argument "
    "as NDS_TPU_CHUNK_STORE",
    "NDS_TPU_FAULT": "deterministic fault injection (engine/faults.py): "
    "an injected build fault PREVENTS the cache entry (the build "
    "raises/degrades), and a non-injected build bakes nothing of the "
    "knob into the program — the knob can never stale a compiled "
    "pipeline; tools/fault_diff.py additionally resets the pipeline "
    "cache around every injected run",
    "NDS_TPU_FAULT_HANG_S": "injection timing only (how long a "
    "hang-kind fault blocks before raising): never reaches a compiled "
    "program's values",
    "NDS_TPU_FAULT_DRIFT": "harness-only recovery suppression for the "
    "--inject-drift self-test: changes whether a retry happens, never "
    "what a successful build compiles",
    "NDS_TPU_STATEMENT_DEADLINE_S": "watchdog timing only: decides WHEN "
    "a hung blocking read raises StatementTimeout, never what a "
    "completed read returns — a timed-out statement produces no result "
    "to cache",
    "NDS_TPU_CHUNK_STORE_LOCK_STALE_S": "writer-lock steal age of the "
    "chunk store: write-side contention policy, never the wire bytes "
    "(same bit-identical-buffers argument as NDS_TPU_CHUNK_STORE)",
}

CACHE_REGISTRY = {
    ("engine/stream.py", "_PIPELINE_CACHE"): CacheSpec(
        key_fns=("_cache_key",),
        builder_fns=("_build_pipeline",),
        modules=_ENGINE_MODULES,
        exempt=_PIPELINE_EXEMPT),
    ("sql/planner.py", "_MASK_FUSE_CACHE"): CacheSpec(
        key_fns=("_fused_run",),
        builder_fns=("_fused_run",),
        modules=("sql/planner.py", "engine/exprs.py", "engine/ops.py",
                 "engine/column.py", "engine/kernels.py"),
        exempt={
            "NDS_TPU_NO_EXPR_FUSE": "checked before the cache is "
            "consulted: the knob disables the cache, it cannot stale it",
            "NDS_TPU_PALLAS": "segment kernels never trace inside "
            "scalar-expression fusion (no aggregation in _fused_run)",
            "NDS_TPU_PALLAS_MAX_GROUPS": "same: group-count gate of "
            "segment kernels, unreachable from scalar expressions",
            "NDS_TPU_EXACT_ONEHOT_BUDGET": "same segment-kernel gate",
            "NDS_TPU_PAIR_BUDGET": "join-probe bucket budget: joins "
            "never trace inside scalar-expression fusion",
            "NDS_TPU_GROUP_PACK_MIN": "group-by packing: no grouping "
            "inside scalar-expression fusion",
            "NDS_TPU_LAZY_SHRINK_ROWS": "compaction policy: fusion "
            "programs never compact",
            "NDS_TPU_STREAM_FANOUT": "stream-join bucket allowance: no "
            "joins inside scalar-expression fusion",
            "NDS_TPU_DEFER_FILTER_MAX_ROWS": "plan routing above the "
            "fusion layer; inputs are keyed by column signature",
        }),
    ("parallel/exchange.py", "_STREAM_MESHES"): CacheSpec(
        key_fns=("stream_mesh",),
        builder_fns=("stream_mesh",),
        modules=("parallel/exchange.py",),
        exempt={
            "NDS_TPU_STREAM_MESH_AXIS": "the axis name IS the second "
            "key component (resolved before the lookup)"}),
    ("parallel/exchange.py", "_exchange_step_cache"): CacheSpec(
        key_fns=("exchange_join_pairs",),
        builder_fns=("_exchange_join_step",),
        modules=("parallel/exchange.py",)),
    # identity-keyed memos: the cached value is a pure function of the
    # keyed host arrays (dictionary sorts/merges/uniques) — env-exempt by
    # design, declared so the unregistered-cache gate stays meaningful
    ("engine/ops.py", "_rank_cache"): CacheSpec(
        (), (), ("engine/ops.py",), identity_keyed=True),
    ("engine/ops.py", "_merged_cache"): CacheSpec(
        (), (), ("engine/ops.py",), identity_keyed=True),
    ("engine/ops.py", "_dense_dim_cache"): CacheSpec(
        (), (), ("engine/ops.py",), identity_keyed=True),
    ("engine/ops.py", "_dim_span_cache"): CacheSpec(
        (), (), ("engine/ops.py",), identity_keyed=True),
    ("engine/ops.py", "_union_cache"): CacheSpec(
        (), (), ("engine/ops.py",), identity_keyed=True),
    ("engine/exprs.py", "_str_literal_dicts"): CacheSpec(
        (), (), ("engine/exprs.py",), identity_keyed=True),
    ("engine/exprs.py", "_map_dict_cache"): CacheSpec(
        (), (), ("engine/exprs.py",), identity_keyed=True),
}
# _EXPR_FUSE_CACHE shares _MASK_FUSE_CACHE's whole contract (same
# builder, same key shape, same exemptions)
CACHE_REGISTRY[("sql/planner.py", "_EXPR_FUSE_CACHE")] = \
    CACHE_REGISTRY[("sql/planner.py", "_MASK_FUSE_CACHE")]


# ---------------------------------------------------------------------------
# per-module model
# ---------------------------------------------------------------------------


@dataclass
class Mutation:
    """One mutation site of a shared object (or of a function parameter,
    resolved to a shared object through call-site aliasing)."""

    target: str            # global name or "Class.attr"
    scope: str             # enclosing function qualname
    lineno: int
    kind: str              # "store" | "method:<name>" | "rebind" |
    #                        "aug-rebind" | "del" | "tls-attr"
    guards: tuple          # lock names held lexically at the site
    module_scope: bool     # True when at module body level (import-time)


@dataclass
class FuncInfo:
    qualname: str
    lineno: int
    params: list = field(default_factory=list)    # ordered param names
    calls: list = field(default_factory=list)     # resolved-late refs
    env_reads: set = field(default_factory=set)
    lock_withs: list = field(default_factory=list)  # lock names taken
    param_mutations: dict = field(default_factory=dict)  # param -> [Mutation]
    param_forwards: list = field(default_factory=list)   # (param, callee,
    #                                                       arg idx, via_self)
    jit_calls: list = field(default_factory=list)        # linenos
    first_sync: tuple | None = None               # (lineno, what) | None
    # calls made while holding each lock: lock -> [(callee ref, lineno)]
    calls_under_lock: dict = field(default_factory=dict)
    syncs_under_lock: list = field(default_factory=list)  # (lock, what, line)
    jit_under_lock: list = field(default_factory=list)    # (lock, line)
    waits_under_lock: list = field(default_factory=list)  # (lock, what, line)
    nested_locks: list = field(default_factory=list)      # (outer, inner, ln)


@dataclass
class ModuleInfo:
    rel: str
    lines: list
    globals_kind: dict = field(default_factory=dict)  # name -> kind
    env_freeze: list = field(default_factory=list)    # (name, lineno)
    functions: dict = field(default_factory=dict)     # qualname -> FuncInfo
    mutations: list = field(default_factory=list)     # [Mutation]
    imports: dict = field(default_factory=dict)       # alias -> module rel
    from_imports: dict = field(default_factory=dict)  # name -> (mod, name)
    cache_writes: dict = field(default_factory=dict)  # cache -> [(key ast,
    #                                                   scope, lineno)]
    cache_arg_calls: list = field(default_factory=list)  # (callee, arg idx,
    #                                                       via_self, name)


def _ctor_kind(node) -> str | None:
    """Shared-state kind of a module/class-level assignment RHS."""
    if isinstance(node, ast.Dict):
        return "dict"
    if isinstance(node, (ast.List, ast.ListComp)):
        return "list"
    if isinstance(node, (ast.Set, ast.SetComp)):
        return "set"
    if isinstance(node, ast.Call):
        f = node.func
        name = f.attr if isinstance(f, ast.Attribute) else (
            f.id if isinstance(f, ast.Name) else None)
        if name in _CONTAINER_CTORS:
            return {"dict": "dict", "defaultdict": "dict",
                    "OrderedDict": "dict", "list": "list",
                    "set": "set"}[name]
        if name == "deque":
            has_maxlen = any(kw.arg == "maxlen" for kw in node.keywords)
            return "ring" if has_maxlen else "list"
        if name in _LOCK_CTORS:
            return "lock"
        if name == "local":
            return "tls"
        if name == "Event":
            return "event"
    if isinstance(node, ast.Constant):
        return "scalar"
    if isinstance(node, ast.Name) and node.id in ("None", "True", "False"):
        return "scalar"
    return None


def _reads_environ(node) -> set | None:
    """Env var names a (key/value) expression reads, or None when it
    makes no environment read at all. Unresolvable names read as
    ``<dynamic>``."""
    out: set = set()
    found = False
    for n in ast.walk(node):
        if isinstance(n, ast.Attribute) and n.attr in ("environ",):
            found = True
        if isinstance(n, ast.Call):
            f = n.func
            name = f.attr if isinstance(f, ast.Attribute) else (
                f.id if isinstance(f, ast.Name) else None)
            if name in ("get", "getenv"):
                owner = f.value if isinstance(f, ast.Attribute) else None
                owner_env = owner is not None and any(
                    isinstance(x, ast.Attribute) and x.attr == "environ"
                    or isinstance(x, ast.Name) and x.id == "os"
                    for x in ast.walk(owner))
                if owner_env and n.args:
                    found = True
                    a = n.args[0]
                    out.add(a.value if isinstance(a, ast.Constant)
                            else "<dynamic>")
        if isinstance(n, ast.Subscript):
            v = n.value
            if isinstance(v, ast.Attribute) and v.attr == "environ":
                found = True
                s = n.slice
                out.add(s.value if isinstance(s, ast.Constant)
                        else "<dynamic>")
    return out if found else None


class _ModuleScan(ast.NodeVisitor):
    """One pass over a module AST building its :class:`ModuleInfo`."""

    def __init__(self, rel: str, source: str):
        self.info = ModuleInfo(rel, source.splitlines())
        self.scope: list = []          # FuncInfo stack
        self.class_stack: list = []
        self.lock_stack: list = []     # lock names currently held
        self.param_stack: list = []    # param-name sets per function

    # -- imports ------------------------------------------------------------

    def visit_Import(self, node):
        for a in node.names:
            if a.name.startswith("nds_tpu"):
                alias = a.asname or a.name.split(".")[0]
                self.info.imports[alias] = a.name
        self.generic_visit(node)

    def visit_ImportFrom(self, node):
        mod = node.module or ""
        if mod.startswith("nds_tpu"):
            for a in node.names:
                self.info.from_imports[a.asname or a.name] = (mod, a.name)
        self.generic_visit(node)

    # -- shared-state inventory ----------------------------------------------

    def _note_state(self, name: str, value, lineno: int) -> None:
        kind = _ctor_kind(value)
        if kind:
            self.info.globals_kind.setdefault(name, kind)
        env = _reads_environ(value) if value is not None else None
        if env is not None:
            self.info.env_freeze.append((name, lineno))

    def visit_Assign(self, node):
        if not self.scope:
            owner = ".".join(self.class_stack)
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    full = f"{owner}.{tgt.id}" if owner else tgt.id
                    self._note_state(full, node.value, node.lineno)
        self._note_mutation_targets(node)
        self.generic_visit(node)

    def visit_AnnAssign(self, node):
        if not self.scope and isinstance(node.target, ast.Name) and \
                node.value is not None:
            owner = ".".join(self.class_stack)
            full = f"{owner}.{node.target.id}" if owner \
                else node.target.id
            self._note_state(full, node.value, node.lineno)
        if isinstance(node.target, ast.Subscript):
            self._note_subscript_store(node.target, node.lineno)
        self.generic_visit(node)

    # -- scopes ---------------------------------------------------------------

    def visit_ClassDef(self, node):
        self.class_stack.append(node.name)
        self.generic_visit(node)
        self.class_stack.pop()

    def visit_FunctionDef(self, node):
        qual = ".".join(self.class_stack + [node.name]) if \
            self.class_stack and not self.scope else node.name
        fi = self.info.functions.setdefault(
            qual, FuncInfo(qual, node.lineno))
        args = node.args
        ordered = [a.arg for a in
                   args.posonlyargs + args.args + args.kwonlyargs]
        fi.params = ordered
        params = set(ordered)
        self.scope.append(fi)
        self.param_stack.append(params)
        saved_locks = self.lock_stack
        self.lock_stack = []           # a def body runs at CALL time
        self.generic_visit(node)
        self.lock_stack = saved_locks
        self.param_stack.pop()
        self.scope.pop()
        if self.scope:
            # a nested def's effects fold into the enclosing function
            # too: its body runs (at most) within the caller's dynamic
            # extent for the closures the engine jits
            outer = self.scope[-1]
            outer.calls.extend(fi.calls)
            outer.env_reads |= fi.env_reads

    visit_AsyncFunctionDef = visit_FunctionDef

    # -- with-lock tracking ----------------------------------------------------

    def _lock_name(self, expr) -> str | None:
        """Resolve a with-context expression to a known lock name:
        ``_LOCK_NAME`` (module global), ``Class._lock`` / ``cls._lock`` /
        ``self._lock`` (class attribute)."""
        if isinstance(expr, ast.Name):
            if self.info.globals_kind.get(expr.id) == "lock":
                return expr.id
            return None
        if isinstance(expr, ast.Attribute) and \
                isinstance(expr.value, ast.Name):
            owner = expr.value.id
            if owner in ("cls", "self") and self.class_stack:
                owner = self.class_stack[-1]
            full = f"{owner}.{expr.attr}"
            if self.info.globals_kind.get(full) == "lock":
                return full
        return None

    def visit_With(self, node):
        locks = [self._lock_name(item.context_expr)
                 for item in node.items]
        locks = [l for l in locks if l]
        fi = self.scope[-1] if self.scope else None
        if fi is not None:
            fi.lock_withs.extend(locks)
        for outer in self.lock_stack:
            for inner in locks:
                if outer != inner and fi is not None:
                    fi.nested_locks.append((outer, inner, node.lineno))
        self.lock_stack.extend(locks)
        self.generic_visit(node)
        for _ in locks:
            self.lock_stack.pop()

    # -- mutations -------------------------------------------------------------

    def _target_of(self, expr) -> tuple | None:
        """(kind, name) of a mutation target expression: a module global,
        a class attribute, or an attribute of a threading.local."""
        if isinstance(expr, ast.Name):
            k = self.info.globals_kind.get(expr.id)
            if k and k not in ("lock",):
                return (k, expr.id)
            return None
        if isinstance(expr, ast.Attribute) and \
                isinstance(expr.value, ast.Name):
            owner = expr.value.id
            if self.info.globals_kind.get(owner) == "tls":
                return ("tls", owner)
            if owner in ("cls", "self") and self.class_stack:
                owner = self.class_stack[-1]
            full = f"{owner}.{expr.attr}"
            k = self.info.globals_kind.get(full)
            if k and k not in ("lock",):
                return (k, full)
        return None

    def _emit_mutation(self, target: tuple, kind: str,
                       lineno: int) -> None:
        tkind, name = target
        mut = Mutation(name, self.scope[-1].qualname if self.scope
                       else "<module>", lineno,
                       "tls-attr" if tkind == "tls" else kind,
                       tuple(self.lock_stack), not self.scope)
        self.info.mutations.append(mut)

    def _note_subscript_store(self, tgt, lineno: int) -> None:
        target = self._target_of(tgt.value)
        if target:
            self._emit_mutation(target, "store", lineno)
            if target[0] == "dict":
                self.info.cache_writes.setdefault(
                    target[1], []).append(
                    (tgt.slice, self.scope[-1].qualname if self.scope
                     else "<module>", lineno))
        elif self.scope and isinstance(tgt.value, ast.Name) and \
                tgt.value.id in self.param_stack[-1]:
            self.scope[-1].param_mutations.setdefault(
                tgt.value.id, []).append(Mutation(
                    tgt.value.id, self.scope[-1].qualname, lineno,
                    "store", tuple(self.lock_stack), False))

    def _note_mutation_targets(self, node: ast.Assign) -> None:
        for tgt in node.targets:
            if isinstance(tgt, ast.Subscript):
                self._note_subscript_store(tgt, tgt.lineno)
            elif isinstance(tgt, (ast.Name, ast.Attribute)):
                target = self._target_of(tgt)
                if target and self.scope:
                    # a bare-name rebind inside a function only reaches
                    # the module global through a `global` declaration;
                    # conservatively treat Name stores in functions as
                    # rebinds (a local shadow of a tracked global name
                    # is rare and reads as shadowing anyway)
                    self._emit_mutation(target, "rebind", tgt.lineno)

    def visit_AugAssign(self, node):
        tgt = node.target
        if isinstance(tgt, ast.Subscript):
            self._note_subscript_store(tgt, node.lineno)
        else:
            target = self._target_of(tgt) if isinstance(
                tgt, (ast.Name, ast.Attribute)) else None
            if target and self.scope:
                self._emit_mutation(target, "aug-rebind", node.lineno)
        self.generic_visit(node)

    def visit_Delete(self, node):
        for tgt in node.targets:
            if isinstance(tgt, ast.Subscript):
                target = self._target_of(tgt.value)
                if target:
                    self._emit_mutation(target, "del", node.lineno)
        self.generic_visit(node)

    # -- calls ------------------------------------------------------------------

    def _callee_ref(self, f) -> tuple | None:
        """Late-resolved callee reference: ("name", x) | ("self", m) |
        ("mod", alias, attr)."""
        if isinstance(f, ast.Name):
            return ("name", f.id)
        if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
            if f.value.id in ("self", "cls"):
                return ("self", f.attr)
            return ("mod", f.value.id, f.attr)
        return None

    def _sync_call(self, node) -> str | None:
        f = node.func
        if isinstance(f, ast.Attribute):
            if f.attr == "item" and not node.args:
                return ".item()"
            if f.attr == "to_int" and not node.args:
                return ".to_int()"
            if f.attr == "device_get":
                return "device_get()"
            if f.attr in _HOST_READ_FUNCS:
                return f"{f.attr}()"
        elif isinstance(f, ast.Name) and f.id in _HOST_READ_FUNCS:
            return f"{f.id}()"
        return None

    def visit_Call(self, node):
        fi = self.scope[-1] if self.scope else None
        f = node.func
        # env reads
        env = _reads_environ(node)
        if env is not None and fi is not None:
            fi.env_reads |= env
        # method-style mutations
        if isinstance(f, ast.Attribute) and f.attr in _MUTATING_METHODS:
            target = self._target_of(f.value)
            if target:
                self._emit_mutation(target, f"method:{f.attr}",
                                    node.lineno)
                if target[0] == "dict" and f.attr == "setdefault" \
                        and node.args:
                    self.info.cache_writes.setdefault(
                        target[1], []).append(
                        (node.args[0],
                         fi.qualname if fi else "<module>",
                         node.lineno))
            elif fi is not None and isinstance(f.value, ast.Name) and \
                    self.param_stack and \
                    f.value.id in self.param_stack[-1]:
                fi.param_mutations.setdefault(f.value.id, []).append(
                    Mutation(f.value.id, fi.qualname, node.lineno,
                             f"method:{f.attr}", tuple(self.lock_stack),
                             False))
        if fi is not None:
            ref = self._callee_ref(f)
            if ref:
                fi.calls.append(ref)
                callee = ref[1] if ref[0] in ("name", "self") else None
                if callee:
                    via_self = ref[0] == "self"
                    for i, a in enumerate(node.args):
                        if isinstance(a, ast.Name):
                            # *_cache aliasing through parameters (the
                            # jax_lint pattern): a shared container
                            # passed in, or a parameter forwarded on.
                            # The raw argument index is recorded with
                            # the call KIND — whether a self-call binds
                            # an implicit first parameter depends on the
                            # callee's signature (staticmethods do not),
                            # resolved at join time.
                            if self.info.globals_kind.get(a.id) in \
                                    ("dict", "list", "set", "ring"):
                                self.info.cache_arg_calls.append(
                                    (callee, i, via_self, a.id))
                            elif self.param_stack and \
                                    a.id in self.param_stack[-1]:
                                fi.param_forwards.append(
                                    (a.id, callee, i, via_self))
            # jit compiles
            is_jit = (isinstance(f, ast.Attribute) and f.attr == "jit") \
                or (isinstance(f, ast.Name) and f.id == "jit")
            if is_jit:
                fi.jit_calls.append(node.lineno)
                if self.lock_stack:
                    fi.jit_under_lock.append(
                        (self.lock_stack[-1], node.lineno))
            what = self._sync_call(node)
            if what and fi.first_sync is None:
                fi.first_sync = (node.lineno, what)
            # under-lock discipline
            if self.lock_stack:
                if what:
                    fi.syncs_under_lock.append(
                        (self.lock_stack[-1], what, node.lineno))
                # .wait() (Event/Condition) and argless .join() (Thread;
                # str.join always takes the iterable) are blocking
                is_wait = isinstance(f, ast.Attribute) and (
                    f.attr == "wait" or
                    (f.attr == "join" and not node.args))
                if is_wait:
                    fi.waits_under_lock.append(
                        (self.lock_stack[-1], f".{f.attr}()",
                         node.lineno))
                if ref:
                    fi.calls_under_lock.setdefault(
                        self.lock_stack[-1], []).append(
                        (ref, node.lineno))
        self.generic_visit(node)


def scan_module(path: str, rel: str) -> ModuleInfo | None:
    with open(path) as fh:
        source = fh.read()
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError:
        return None
    # two passes: the inventory must exist before function bodies are
    # classified (a lock defined after its first use still guards it)
    pre = _ModuleScan(rel, source)
    for node in tree.body:
        if isinstance(node, (ast.Assign, ast.AnnAssign)):
            tgts = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for t in tgts:
                if isinstance(t, ast.Name) and node.value is not None:
                    pre._note_state(t.id, node.value, node.lineno)
        elif isinstance(node, ast.ClassDef):
            for sub in node.body:
                if isinstance(sub, (ast.Assign, ast.AnnAssign)):
                    tgts = sub.targets if isinstance(sub, ast.Assign) \
                        else [sub.target]
                    for t in tgts:
                        if isinstance(t, ast.Name) and \
                                sub.value is not None:
                            pre._note_state(f"{node.name}.{t.id}",
                                            sub.value, sub.lineno)
    scan = _ModuleScan(rel, source)
    scan.info.globals_kind = pre.info.globals_kind
    scan.visit(tree)
    return scan.info


# ---------------------------------------------------------------------------
# package-level joins
# ---------------------------------------------------------------------------


class PackageModel:
    """Every module's :class:`ModuleInfo` plus the cross-module joins:
    call-graph closure, env-knob propagation, parameter-aliased mutation
    resolution."""

    def __init__(self, modules: dict):
        self.modules = modules          # rel -> ModuleInfo
        # (rel, qualname) -> FuncInfo
        self.functions = {(rel, q): fi
                          for rel, mi in modules.items()
                          for q, fi in mi.functions.items()}
        # method name -> [(rel, qualname)] for name-based resolution
        self.by_name: dict = {}
        for (rel, q), fi in self.functions.items():
            self.by_name.setdefault(q.split(".")[-1], []).append((rel, q))

    def resolve(self, rel: str, ref, fuzzy_modules=None):
        """Function keys a callee reference may reach. Precise edges:
        bare name in the same module, from-imports, module-alias attrs,
        self/cls methods. ``fuzzy_modules`` additionally matches unknown
        attr calls by bare method name within the given module set (the
        planner's instance-typed engine calls)."""
        mi = self.modules[rel]
        out = []
        kind = ref[0]
        if kind == "name":
            name = ref[1]
            if (rel, name) in self.functions:
                out.append((rel, name))
            elif name in mi.from_imports:
                mod, orig = mi.from_imports[name]
                target = _module_rel(mod)
                for cand_rel in self.modules:
                    if target and cand_rel.endswith(target) and \
                            (cand_rel, orig) in self.functions:
                        out.append((cand_rel, orig))
        elif kind == "self":
            name = ref[1]
            for q in self.modules[rel].functions:
                if q.split(".")[-1] == name and "." in q:
                    out.append((rel, q))
            if not out and (rel, name) in self.functions:
                out.append((rel, name))
        elif kind == "mod":
            alias, attr = ref[1], ref[2]
            mod = mi.imports.get(alias)
            if mod is None and alias in mi.from_imports:
                # `from nds_tpu.engine import ops as E` arrives as a
                # from-import of a SUBMODULE
                m, orig = mi.from_imports[alias]
                mod = f"{m}.{orig}"
            if mod:
                target = _module_rel(mod)
                for cand_rel in self.modules:
                    if target and cand_rel.endswith(target):
                        if (cand_rel, attr) in self.functions:
                            out.append((cand_rel, attr))
                        else:
                            out.extend(
                                (cand_rel, q) for q in
                                self.modules[cand_rel].functions
                                if q.split(".")[-1] == attr and "." in q)
            elif fuzzy_modules is not None:
                out.extend(k for k in self.by_name.get(attr, ())
                           if any(k[0].endswith(s)
                                  for s in fuzzy_modules))
        if not out and fuzzy_modules is not None and kind in ("mod",):
            out.extend(k for k in self.by_name.get(ref[-1], ())
                       if any(k[0].endswith(s) for s in fuzzy_modules))
        return out

    def knob_closure(self, roots, fuzzy_modules=None) -> set:
        """Env vars read by ``roots`` (function keys) or anything they
        transitively call through resolvable edges."""
        seen = set()
        knobs: set = set()
        stack = [r for r in roots if r in self.functions]
        while stack:
            key = stack.pop()
            if key in seen:
                continue
            seen.add(key)
            fi = self.functions[key]
            knobs |= fi.env_reads
            for ref in fi.calls:
                for nxt in self.resolve(key[0], ref, fuzzy_modules):
                    if nxt not in seen:
                        stack.append(nxt)
        return knobs

    def reachable(self, entry_points) -> set:
        """Function keys reachable from the entry-point patterns through
        the widest (name-fuzzy, package-wide) edges — an over-
        approximation, which is the safe direction for deciding what
        runs concurrently."""
        all_suffixes = tuple(self.modules)
        roots = []
        for (suffix, prefix) in entry_points:
            for (rel, q) in self.functions:
                if rel.endswith(suffix) and \
                        q.split(".")[-1].startswith(prefix):
                    roots.append((rel, q))
        seen = set()
        stack = roots
        while stack:
            key = stack.pop()
            if key in seen:
                continue
            seen.add(key)
            fi = self.functions[key]
            for ref in fi.calls:
                cands = self.resolve(key[0], ref, all_suffixes)
                if not cands and ref[0] in ("name", "self"):
                    cands = [k for k in self.by_name.get(ref[1], ())]
                stack.extend(c for c in cands if c not in seen)
        return seen


def _module_rel(dotted: str) -> str | None:
    """``nds_tpu.engine.ops`` -> ``engine/ops.py`` (suffix form)."""
    if not dotted.startswith("nds_tpu"):
        return None
    parts = dotted.split(".")[1:]
    if not parts:
        return None
    return "/".join(parts) + ".py"


# ---------------------------------------------------------------------------
# findings
# ---------------------------------------------------------------------------


def _emit(findings, mi, scope, rule, severity, msg, lineno):
    if suppressed(mi.lines, lineno, rule):
        return
    findings.append(Finding(mi.rel, scope, rule, severity, msg, lineno))


def _resolve_param_aliases(model: PackageModel) -> None:
    """Attribute mutation sites inside callees that received a shared
    container as a parameter back to the module global, carrying the
    callee's guard state — transitively through parameter forwards
    (depth-bounded: ``_fused_run`` forwards its ``cache`` parameter to
    ``_fuse_insert``, whose mutations must count against the module
    caches the original call sites pass in). Name-based callee
    resolution like jax_lint: a collision only widens coverage."""
    for rel, mi in model.modules.items():
        for (callee, idx, via_self, gname) in mi.cache_arg_calls:
            seen = set()
            stack = [(callee, idx, via_self, 0)]
            while stack:
                cname, cidx, cself, depth = stack.pop()
                if depth > 3 or (cname, cidx, cself) in seen:
                    continue
                seen.add((cname, cidx, cself))
                for (frel, fq) in model.by_name.get(cname, ()):
                    fi = model.functions[(frel, fq)]
                    # a self-call binds an implicit first parameter only
                    # when the callee actually declares one — a
                    # staticmethod invoked through self does not
                    cpos = cidx + (1 if cself and fi.params and
                                   fi.params[0] in ("self", "cls")
                                   else 0)
                    if cpos >= len(fi.params):
                        continue
                    pname = fi.params[cpos]
                    for m in fi.param_mutations.get(pname, ()):
                        # the finding lands on the CALLEE's module: the
                        # flagged line is the real mutation site, so the
                        # report points at actionable code and an
                        # in-source suppression THERE is honored
                        model.modules[frel].mutations.append(Mutation(
                            gname, f"{fq}(via {cname})", m.lineno,
                            m.kind, m.guards, False))
                    for (fwd_param, fwd_callee, fwd_idx, fwd_self) in \
                            fi.param_forwards:
                        if fwd_param == pname:
                            stack.append((fwd_callee, fwd_idx,
                                          fwd_self, depth + 1))


def audit_package(root: str, repo: str | None = None,
                  registry: dict | None = None,
                  entry_points=ENTRY_POINTS) -> list:
    """Run the concurrency audit over every ``.py`` under ``root``.
    Returns the findings list (same :class:`Finding` shape as the other
    five passes)."""
    registry = CACHE_REGISTRY if registry is None else registry
    repo = repo or os.path.dirname(os.path.abspath(root))
    modules: dict = {}
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                p = os.path.join(dirpath, fn)
                rel = os.path.relpath(p, repo)
                mi = scan_module(p, rel)
                if mi is not None:
                    modules[rel] = mi
    model = PackageModel(modules)
    _resolve_param_aliases(model)
    reachable = model.reachable(entry_points)
    findings: list = []

    for rel, mi in sorted(modules.items()):
        _audit_mutations(findings, model, mi, reachable)
        _audit_lock_bodies(findings, model, mi)
        _audit_env_freeze(findings, mi)
        _audit_caches(findings, model, mi, registry)
    _audit_lock_order(findings, model)
    return findings


def _state_guard_map(mi: ModuleInfo) -> dict:
    """state name -> set of locks observed guarding its mutations."""
    guards: dict = {}
    for m in mi.mutations:
        if m.module_scope or m.kind == "tls-attr":
            continue
        if m.guards:
            guards.setdefault(m.target, set()).add(m.guards[-1])
    return guards


def _audit_mutations(findings, model, mi, reachable) -> None:
    guard_map = _state_guard_map(mi)
    for m in mi.mutations:
        if m.module_scope:
            continue                    # import-time: serialized
        kind = mi.globals_kind.get(m.target, "")
        if m.kind == "tls-attr":
            continue                    # thread-local by construction
        if kind == "ring" and (
                m.kind.startswith("method:") and
                m.kind.split(":")[1] in _RING_METHODS):
            continue                    # bounded evidence ring
        state_locks = guard_map.get(m.target, set())
        if m.guards:
            if len(state_locks) > 1:
                _emit(findings, mi, m.scope, "mixed-guard", "error",
                      f"{m.target} is guarded by more than one lock "
                      f"({', '.join(sorted(state_locks))}): a lock can "
                      "only protect state it exclusively guards",
                      m.lineno)
            continue                    # lock-guarded (consistency above)
        if m.kind == "rebind" and not state_locks:
            # atomic rebind: one GIL-atomic pointer store, last-writer-
            # wins — accepted for flags/latches and whole-object resets
            continue
        reach = any(k[0] == mi.rel and
                    (k[1] == m.scope or m.scope.startswith(k[1]))
                    for k in reachable) or "(via " in m.scope
        sev = "error" if reach else "warning"
        if state_locks:
            _emit(findings, mi, m.scope, "mixed-guard", "error",
                  f"{m.target} is mutated off-lock here but under "
                  f"{', '.join(sorted(state_locks))} elsewhere: every "
                  "mutation must hold the state's dedicated lock",
                  m.lineno)
        else:
            _emit(findings, mi, m.scope, "unguarded-mutation", sev,
                  f"{m.target} ({kind or 'shared object'}) is mutated "
                  "with no dedicated lock, thread-local scope, or "
                  "bounded-ring pattern: concurrent query streams race "
                  "here — add a module Lock with double-checked "
                  "insert (see _PIPELINE_LOCK) or make it thread-local",
                  m.lineno)


def _audit_lock_bodies(findings, model, mi) -> None:
    """sync/compile/wait inside a with-lock body, one level down."""
    for q, fi in sorted(mi.functions.items()):
        for (lock, what, ln) in fi.syncs_under_lock:
            _emit(findings, mi, q, "sync-under-lock", "error",
                  f"{what} while holding {lock}: a device->host sync "
                  "holds every waiter for a full round trip — resolve "
                  "before acquiring or after releasing", ln)
        for (lock, ln) in fi.jit_under_lock:
            _emit(findings, mi, q, "compile-under-lock", "error",
                  f"jax.jit(...) while holding {lock}: an XLA compile "
                  "under a shared lock serializes every concurrent "
                  "stream — claim under the lock, compile off-lock, "
                  "land under the lock (the singleflight pattern)", ln)
        for (lock, what, ln) in fi.waits_under_lock:
            _emit(findings, mi, q, "wait-under-lock", "error",
                  f"blocking {what} while holding {lock}: the waiter "
                  "holds the lock its waker needs (lost-wakeup/"
                  "deadlock shape) — wait off-lock and re-check", ln)
        # one level down: a called module-local helper that syncs or
        # compiles directly
        for lock, calls in fi.calls_under_lock.items():
            for (ref, ln) in calls:
                for key in model.resolve(mi.rel, ref):
                    if key[0] != mi.rel:
                        continue
                    callee = model.functions[key]
                    if callee.first_sync:
                        sln, what = callee.first_sync
                        _emit(findings, mi, q, "sync-under-lock",
                              "error",
                              f"{key[1]}() (syncs via {what} at line "
                              f"{sln}) called while holding {lock}: "
                              "one host sync per acquisition hidden "
                              "one level down", ln)
                    if callee.jit_calls:
                        _emit(findings, mi, q, "compile-under-lock",
                              "error",
                              f"{key[1]}() (jits at line "
                              f"{callee.jit_calls[0]}) called while "
                              f"holding {lock}: a compile hidden one "
                              "level down", ln)


def _audit_env_freeze(findings, mi) -> None:
    for (name, ln) in mi.env_freeze:
        _emit(findings, mi, "<module>", "env-freeze", "warning",
              f"{name} snapshots os.environ at import: a knob set after "
              "import is silently ignored and a compiled-behavior knob "
              "escapes every cache key — read it at build/use time "
              "(stream_fanout() pattern), or suppress with a "
              "justification if the freeze is a process contract", ln)


def _audit_caches(findings, model, mi, registry) -> None:
    for cname, writes in sorted(mi.cache_writes.items()):
        writes = [w for w in writes if w[1] != "<module>"]
        if not writes:
            continue                    # import-time table construction
        spec = None
        for (suffix, reg_name), s in registry.items():
            if cname == reg_name and mi.rel.endswith(suffix):
                spec = s
                break
        looks_cache = "cache" in cname.lower() or \
            cname in ("_STREAM_MESHES",)
        if spec is None:
            if looks_cache:
                _emit(findings, mi, writes[0][1], "cache-unregistered",
                      "warning",
                      f"{cname} is keyed and written on the query path "
                      "but not declared in conc_audit.CACHE_REGISTRY: "
                      "register its key/builder functions (or mark it "
                      "identity-keyed) so cache-key completeness is "
                      "checked", writes[0][2])
            continue
        if spec.identity_keyed:
            continue
        key_roots = [(rel, q) for (rel, q) in model.functions
                     if q.split(".")[-1] in spec.key_fns and
                     any(rel.endswith(s) for s in spec.modules)]
        builder_roots = [(rel, q) for (rel, q) in model.functions
                         if q.split(".")[-1] in spec.builder_fns and
                         any(rel.endswith(s) for s in spec.modules)]
        key_knobs = model.knob_closure(key_roots,
                                       fuzzy_modules=spec.modules)
        builder_knobs = model.knob_closure(builder_roots,
                                           fuzzy_modules=spec.modules)
        missing = (builder_knobs - key_knobs) - set(spec.exempt) - \
            {"<dynamic>"}
        for knob in sorted(missing):
            _emit(findings, mi, writes[0][1], "cache-key-missing-knob",
                  "error",
                  f"{cname}: env knob {knob} is reachable from the "
                  f"cached computation ({'/'.join(spec.builder_fns)}) "
                  "but absent from the key expression "
                  f"({'/'.join(spec.key_fns)}) — a post-change lookup "
                  "would serve a stale artifact; add it to the key or "
                  "exempt it WITH a justification in CACHE_REGISTRY",
                  writes[0][2])


def _audit_lock_order(findings, model) -> None:
    """Global acquired-while-holding graph; any cycle is a deadlock."""
    edges: dict = {}
    sites: dict = {}
    for (rel, q), fi in model.functions.items():
        for (outer, inner, ln) in fi.nested_locks:
            edges.setdefault((rel, outer), set()).add((rel, inner))
            sites.setdefault(((rel, outer), (rel, inner)), (rel, q, ln))
        # one level down: a call made under `outer` into a function that
        # takes `inner` (precise resolution only)
        for outer, calls in fi.calls_under_lock.items():
            for (ref, ln) in calls:
                for key in model.resolve(rel, ref):
                    callee = model.functions[key]
                    for inner in callee.lock_withs:
                        if (key[0], inner) != (rel, outer):
                            edges.setdefault((rel, outer), set()).add(
                                (key[0], inner))
                            sites.setdefault(
                                ((rel, outer), (key[0], inner)),
                                (rel, q, ln))
    # DFS cycle detection
    WHITE, GRAY, BLACK = 0, 1, 2
    color = {}
    stack_path = []

    def dfs(node):
        color[node] = GRAY
        stack_path.append(node)
        for nxt in sorted(edges.get(node, ())):
            if color.get(nxt, WHITE) == GRAY:
                cyc = stack_path[stack_path.index(nxt):] + [nxt]
                names = " -> ".join(f"{r}:{n}" for (r, n) in cyc)
                rel, q, ln = sites.get((node, nxt), (node[0], "?", 0))
                mi = model.modules[rel]
                _emit(findings, mi, q, "lock-order-cycle", "error",
                      f"lock acquisition cycle {names}: two threads "
                      "taking these locks in opposite orders deadlock — "
                      "impose one global order (or merge the locks)",
                      ln)
            elif color.get(nxt, WHITE) == WHITE:
                dfs(nxt)
        stack_path.pop()
        color[node] = BLACK

    for node in sorted(edges):
        if color.get(node, WHITE) == WHITE:
            dfs(node)


def audit_concurrency(root: str | None = None) -> list:
    """The sixth ``tools/lint.py`` pass: audit the shipped ``nds_tpu/``
    package (or ``root``)."""
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return audit_package(root)
