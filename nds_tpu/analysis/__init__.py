# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Static analysis over the query corpus and the engine/driver code.

The reference harness leans on Spark's analyzer to reject bad plans before
execution; this package is the TPU build's equivalent, run entirely on host
with no device in the loop:

* :mod:`nds_tpu.analysis.plan_audit` — walks the parsed AST of every query
  template against the :mod:`nds_tpu.schema` catalog: column resolution
  (mirroring the planner's ``alias.column`` suffix-match scoping), dtype
  compatibility of comparisons/joins/aggregate arguments, join-graph
  connectivity (true cartesians), unknown functions, window/grouping misuse.
* :mod:`nds_tpu.analysis.jax_lint` — a Python-``ast`` lint for JAX hazards in
  ``nds_tpu/``: host syncs inside hot-path loops, Python ``if`` on
  tracer-valued parameters, unhashable/unbounded jit-cache keys,
  ``time.time()`` inside jitted regions.
* :mod:`nds_tpu.analysis.exec_audit` — abstract interpreter over the
  planner's decomposition: execution-path classification (compiled-stream
  / eager-fallback / device-resident) and static host-sync bounds.
* :mod:`nds_tpu.analysis.mem_audit` — per-statement peak-HBM byte bounds
  and the stream-accumulator proofs ``engine/stream.py`` sizes from.
* :mod:`nds_tpu.analysis.perf_audit` — the static byte/roofline cost
  model over the same decomposition: exact h2d upload bytes (the padded
  encoded-chunk closed form), per-stage HBM traffic, sharded ICI wire
  bytes from the collective-budget shapes, and a roofline lower-bound
  wall with a ranked bottleneck tag per statement. Exactness is differentially checked against runtime
  ``StreamEvent`` byte evidence by ``tools/perf_audit_diff.py``.
* :mod:`nds_tpu.analysis.driver_audit` — driver-level hygiene for the
  top-level CLIs and ``tools/``: swallowed exceptions, shell-injection
  surfaces, file handles opened outside context managers.
* :mod:`nds_tpu.analysis.conc_audit` — shared-state/lock-discipline
  audit over the whole package: inventories every module/class-level
  mutable object, classifies each mutation site (lock-guarded /
  thread-local / bounded-ring / atomic-rebind / unguarded), enforces
  the no-sync-no-compile-under-lock and lock-order rules, and checks
  cache-key completeness (every env knob reachable from a cached
  computation appears in its key). Runtime half:
  ``tools/conc_audit_diff.py``'s threaded stress differential.
* :mod:`nds_tpu.analysis.num_audit` — value-range/precision abstract
  interpreter over the same decomposition: proves per statement that
  every FOR/dict codec fits its priced narrow width, every encoded
  compare's ``lit - base`` rebase threshold stays in int64,
  no SUM/COUNT/AVG accumulator exceeds int64 / f64-exact-integer range
  through join fan-out, decimal scale is preserved exactly, and the
  hash partition+shard route bits fit the mixed 32-bit width — plus
  executable versions of the numeric-safety claims written as comments
  in ``io/columnar.py`` and ``engine/kernels.py``. Runtime half:
  ``tools/num_audit_diff.py``'s boundary-value differential.
* :mod:`nds_tpu.analysis.param_audit` — literal-bindability prover over
  the same decomposition: classifies every literal occurrence BINDABLE
  (safe to ride as a jit operand of the one compiled per-chunk program
  — recorded graph, chunk shapes, codec selection, partition counts,
  residual keys and stream bounds all value-invariant) or FOLD-REQUIRED
  with a machine-readable reason, derives per-template parameter
  signatures with proven safe value domains, and exports the shared
  rule (``conjunct_bind_slots`` / ``skeleton_conjunct_key``) that
  ``engine/stream.py`` uses to canonicalize the pipeline-cache key so
  K parameter vectors share one compile. Runtime half:
  ``tools/param_audit_diff.py``'s one-compile-many-params differential.

``tools/lint.py`` runs all nine and gates on new findings against the
checked-in :data:`BASELINE_PATH` (accepted pre-existing findings); code-lint
findings are suppressible in-source with ``# nds-lint: ignore[rule]``.
"""

from __future__ import annotations

import json
import os
import re
from dataclasses import asdict, dataclass

BASELINE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "baseline.json")

SEVERITIES = ("error", "warning")


@dataclass(frozen=True)
class Finding:
    """One analyzer finding. ``query`` is the query/template name for plan
    findings and the enclosing scope (function or ``<module>``) for code
    findings; ``line`` is advisory (0 for plan findings, which carry no
    source positions) and excluded from baseline identity so unrelated
    edits don't churn the baseline."""

    file: str
    query: str
    rule: str
    severity: str
    message: str
    line: int = 0

    def key(self) -> str:
        return f"{self.file}::{self.query}::{self.rule}::{self.message}"

    def to_dict(self) -> dict:
        return asdict(self)

    def __str__(self) -> str:
        loc = f"{self.file}:{self.line}" if self.line else self.file
        return f"{loc} [{self.query}] {self.severity} {self.rule}: {self.message}"


_SUPPRESS_RE = re.compile(r"#\s*nds-lint:\s*ignore(?:\[([\w\-, ]*)\])?")


def suppressed(source_lines: list, lineno: int, rule: str) -> bool:
    """True when ``# nds-lint: ignore[rule]`` (or a bare ``ignore``) appears
    on the flagged line, or on a comment-ONLY line directly above it (a
    trailing comment on the previous statement suppresses only that
    statement). ``lineno`` is 1-based, as in ``ast`` nodes."""
    for ln in (lineno, lineno - 1):
        if not 1 <= ln <= len(source_lines):
            continue
        text = source_lines[ln - 1]
        if ln != lineno and not text.lstrip().startswith("#"):
            continue
        m = _SUPPRESS_RE.search(text)
        if m:
            rules = m.group(1)
            if rules is None:
                return True
            if rule in {r.strip() for r in rules.split(",")}:
                return True
    return False


def load_baseline(path: str | None = None) -> dict:
    """Baseline as ``{finding key: accepted count}``; {} when absent."""
    path = path or BASELINE_PATH
    if not os.path.exists(path):
        return {}
    with open(path) as f:
        doc = json.load(f)
    return dict(doc.get("keys", {}))

def write_baseline(findings, path: str | None = None) -> None:
    keys: dict = {}
    for f in findings:
        keys[f.key()] = keys.get(f.key(), 0) + 1
    doc = {"version": 1,
           "note": ("Accepted pre-existing findings; tools/lint.py fails "
                    "only on findings NOT covered here. Regenerate with "
                    "tools/lint.py --update-baseline after review."),
           "keys": dict(sorted(keys.items()))}
    with open(path or BASELINE_PATH, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")


def diff_against_baseline(findings, baseline: dict) -> list:
    """Findings not covered by the baseline. A baseline entry absorbs up to
    its accepted COUNT of identical keys, so a second instance of an
    accepted hazard in the same scope still fails the gate."""
    remaining = dict(baseline)
    new = []
    for f in findings:
        k = f.key()
        if remaining.get(k, 0) > 0:
            remaining[k] -= 1
        else:
            new.append(f)
    return new
