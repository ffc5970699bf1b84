# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Static analysis suite (nds_tpu/analysis): the plan auditor must pass the
whole shipped corpus clean (modulo the checked-in baseline), each rule must
trip on a known-bad fixture, in-source suppression must be honored, and the
baseline diff must reject only NEW findings — the CI-gate contract of
tools/lint.py."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TEMPLATES = os.path.join(REPO, "nds_tpu", "queries", "templates")


def audit(sql: str):
    from nds_tpu.analysis.plan_audit import PlanAuditor
    return PlanAuditor().audit_sql(sql)


def rules(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# plan auditor: full corpus
# ---------------------------------------------------------------------------


def test_corpus_passes_plan_audit_clean():
    """All 99 templates (103 statements) audit clean: the only accepted
    error is the TPC-DS spec's own deliberate cartesian in query77
    (``from cs, cr`` — two per-call-center aggregates), which the
    checked-in baseline carries."""
    from nds_tpu.analysis.plan_audit import audit_corpus
    findings = audit_corpus()
    errors = [f for f in findings if f.severity == "error"]
    assert [(f.file, f.rule) for f in errors] == \
        [("query77.tpl", "cartesian-join")], \
        "\n".join(str(f) for f in errors)


def test_corpus_audit_is_deterministic():
    from nds_tpu.analysis.plan_audit import audit_corpus
    a = [f.key() for f in audit_corpus()]
    b = [f.key() for f in audit_corpus()]
    assert a == b


# ---------------------------------------------------------------------------
# plan auditor: known-bad fixtures trip the expected rule
# ---------------------------------------------------------------------------


def test_unresolvable_column():
    fs = audit("select ss_no_such_col from store_sales")
    assert rules(fs) == {"unresolved-column"}
    assert "ss_no_such_col" in fs[0].message


def test_unresolvable_qualified_column():
    fs = audit("select s.ss_item_sk from store_sales ss")
    assert "unresolved-column" in rules(fs)


def test_unknown_table():
    fs = audit("select 1 x from no_such_table")
    assert "unknown-table" in rules(fs)


def test_dtype_mismatched_join():
    # int32 surrogate key joined against a char(2) state column
    fs = audit("select count(*) c from store_sales, store "
               "where ss_store_sk = s_state")
    assert "type-mismatch" in rules(fs)


def test_dtype_mismatched_literal_comparison():
    fs = audit("select count(*) c from store_sales "
               "where ss_quantity = 'many'")
    assert "type-mismatch" in rules(fs)
    # ...while numeric and date/string coercions the corpus relies on pass
    assert not audit("select count(*) c from date_dim "
                     "where d_date between '1999-01-01' and '1999-02-01'")


def test_cartesian_join_detected():
    fs = audit("select count(*) c from store_sales, customer_demographics "
               "where ss_quantity > 5")
    assert "cartesian-join" in rules(fs)
    assert "customer_demographics" in fs[-1].message


def test_connected_join_not_cartesian():
    fs = audit("select count(*) c from store_sales, store "
               "where ss_store_sk = s_store_sk")
    assert "cartesian-join" not in rules(fs)


def test_single_row_subquery_exempt_from_cartesian():
    # broadcasting a 1-row aggregate is a gather, not a pair explosion
    fs = audit("select count(*) c from store_sales, "
               "(select avg(ss_quantity) aq from store_sales) m "
               "where ss_quantity > aq")
    assert "cartesian-join" not in rules(fs)


def test_constant_projection_subquery_not_single_row():
    # select 1 from t is one row PER INPUT ROW: the exemption needs a
    # real aggregate, or the flagship rule misses a true cross join
    fs = audit("select count(*) c from store_sales, "
               "(select 1 x from customer_demographics) m")
    assert "cartesian-join" in rules(fs)


def test_or_predicate_connects_but_and_does_not():
    # an OR spanning two relations is evaluated per pair — a pair filter,
    # not a cartesian...
    assert "cartesian-join" not in rules(
        audit("select count(*) c from store_sales, store "
              "where ss_store_sk = 1 or s_store_sk = 2"))
    # ...but an AND of single-relation filters decomposes into independent
    # conjuncts and must still flag the unconnected pair
    assert "cartesian-join" in rules(
        audit("select count(*) c from store_sales, store "
              "where ss_store_sk = 1 and s_store_sk = 2"))


def test_unknown_function():
    fs = audit("select percentile_disc(ss_quantity) p from store_sales")
    assert "unknown-function" in rules(fs)


def test_window_misuse_and_nested_aggregate():
    assert "window-misuse" in rules(
        audit("select rank() r from store_sales"))
    assert "nested-aggregate" in rules(
        audit("select sum(avg(ss_quantity)) s from store_sales"))
    # q12-class windowed aggregate-over-aggregate is legal
    assert not audit(
        "select sum(sum(ss_ext_sales_price)) over (partition by ss_store_sk)"
        " w from store_sales group by ss_store_sk, ss_ext_sales_price")


def test_agg_in_where_and_agg_arg_type():
    assert "agg-in-where" in rules(
        audit("select ss_item_sk from store_sales "
              "where sum(ss_quantity) > 5"))
    assert "agg-arg-type" in rules(
        audit("select sum(s_state) s from store group by s_store_sk"))


def test_grouping_misuse():
    assert "grouping-misuse" in rules(
        audit("select grouping(ss_store_sk) g from store_sales"))
    assert "grouping-misuse" in rules(
        audit("select grouping(ss_item_sk) g from store_sales "
              "group by rollup(ss_store_sk)"))
    assert not audit("select grouping(ss_store_sk) g from store_sales "
                     "group by rollup(ss_store_sk)")


def test_setop_arity():
    fs = audit("select ss_item_sk, ss_quantity from store_sales "
               "union all select sr_item_sk from store_returns")
    assert "setop-arity" in rules(fs)


def test_duplicate_projected_names_keep_arity():
    # duplicate output names collapse as scope keys but still count as
    # columns: 2 vs 2 is NOT an arity error...
    assert not audit(
        "select ss_item_sk, ss_item_sk from store_sales "
        "union all select sr_item_sk, sr_ticket_number from store_returns")
    # ...and a dup-name 2-column IN subquery IS one
    fs = audit("select ss_item_sk from store_sales where ss_item_sk in "
               "(select sr_item_sk, sr_item_sk from store_returns)")
    assert "subquery-arity" in rules(fs)


def test_join_edge_through_non_comparison_predicates():
    # IN-list / LIKE predicates spanning two relations connect them: the
    # planner turns them into pair filters, not a cartesian
    assert "cartesian-join" not in rules(
        audit("select s.ss_item_sk from store_sales s, item i "
              "where s.ss_item_sk in (i.i_item_sk)"))
    assert "cartesian-join" not in rules(
        audit("select s.ss_item_sk from store_sales s, item i "
              "where i.i_item_id like 'AAA%' and s.ss_item_sk in "
              "(i.i_item_sk, i_manufact_id)"))


def test_cte_and_correlation_resolve():
    # the query1 shape: CTE referenced twice + correlated scalar subquery
    fs = audit(textwrap.dedent("""
        with ctr as (select sr_customer_sk ctr_customer_sk,
                            sr_store_sk ctr_store_sk,
                            sum(sr_return_amt) ctr_total_return
                     from store_returns, date_dim
                     where sr_returned_date_sk = d_date_sk
                     group by sr_customer_sk, sr_store_sk)
        select c_customer_id from ctr ctr1, store, customer
        where ctr1.ctr_total_return >
              (select avg(ctr_total_return) * 1.2 from ctr ctr2
               where ctr1.ctr_store_sk = ctr2.ctr_store_sk)
          and s_store_sk = ctr1.ctr_store_sk
          and ctr1.ctr_customer_sk = c_customer_sk
        order by c_customer_id
        limit 100"""))
    assert not fs, "\n".join(str(f) for f in fs)


# ---------------------------------------------------------------------------
# jax lint
# ---------------------------------------------------------------------------


def lint_snippet(tmp_path, code, rel="nds_tpu/engine/ops.py"):
    from nds_tpu.analysis.jax_lint import lint_file
    p = tmp_path / "snippet.py"
    p.write_text(textwrap.dedent(code))
    return lint_file(str(p), rel)


def test_jax_lint_host_sync_in_loop(tmp_path):
    fs = lint_snippet(tmp_path, """
        import numpy as np
        def drain(cols):
            out = []
            for c in cols:
                out.append(c.total.item())
                out.append(np.asarray(c.data))
            return out
    """)
    assert [f.rule for f in fs] == ["host-sync-in-loop"] * 2
    assert all(f.severity == "warning" for f in fs)


def test_jax_lint_hot_path_scoping(tmp_path):
    # the same sync outside the hot-path modules is not a finding
    fs = lint_snippet(tmp_path, """
        def drain(cols):
            return [c.total.item() for c in cols]
    """, rel="nds_tpu/report.py")
    assert not fs


def test_jax_lint_tracer_if_and_time(tmp_path):
    fs = lint_snippet(tmp_path, """
        import functools, time
        import jax
        @functools.partial(jax.jit, static_argnums=(1,))
        def kern(x, n):
            t0 = time.time()
            if n > 2:          # static arg: fine
                x = x + 1
            if x > 0:          # traced arg: hazard
                return x
            return x - t0
    """)
    assert sorted(f.rule for f in fs) == ["time-in-jit", "tracer-if"]
    assert all(f.severity == "error" for f in fs)


def test_jax_lint_nested_helper_and_argless_jit(tmp_path):
    # a helper defined inside a jit function still runs under the trace:
    # closures over the traced params keep tracer semantics, and an
    # argless jit function still evaluates time.time() once at trace time
    fs = lint_snippet(tmp_path, """
        import time
        import jax
        @jax.jit
        def f(x):
            def inner():
                if x > 0:
                    return x + 1
                return x
            return inner()
        @jax.jit
        def g():
            return time.time()
    """)
    assert sorted(f.rule for f in fs) == ["time-in-jit", "tracer-if"]
    # ...but a nested helper's OWN params shadow the outer tracers and
    # their tracedness is unknowable — not flagged
    fs = lint_snippet(tmp_path, """
        import jax
        @jax.jit
        def f(x):
            def clamp(x):
                if x is None:
                    return 0
                return x
            return clamp(3)
    """)
    assert not fs, "\n".join(str(f) for f in fs)


def test_jax_lint_static_metadata_if_ok(tmp_path):
    fs = lint_snippet(tmp_path, """
        import jax
        import jax.numpy as jnp
        @jax.jit
        def kern(x, valid):
            if valid is None:                # pytree structure: fine
                valid = jnp.ones(x.shape[0], bool)
            if x.dtype == jnp.float64:       # static metadata: fine
                x = x.astype(jnp.float32)
            return x, valid
    """)
    assert not fs


def test_jax_lint_span_in_jit(tmp_path):
    # an obs.span(...) context inside a jitted function reads the host
    # clock at trace time — flagged whether spelled obs.span, trace.span
    # or a bare imported span; nested defs inside the jit body count too
    fs = lint_snippet(tmp_path, """
        import jax
        from nds_tpu.obs import trace as obs
        from nds_tpu.obs.trace import span
        @jax.jit
        def kern(x):
            with obs.span("drive"):
                y = x + 1
            with span("bare"):
                y = y * 2
            return y
    """)
    assert [f.rule for f in fs] == ["span-in-jit"] * 2
    assert all(f.severity == "error" for f in fs)
    fs = lint_snippet(tmp_path, """
        import jax
        @jax.jit
        def kern(x):
            def helper():
                with obs.span("nested"):
                    return x
            return helper()
    """)
    assert [f.rule for f in fs] == ["span-in-jit"]


def test_jax_lint_host_sync_in_shard_map(tmp_path):
    """Both directions of the host-sync-in-shard-map rule: host reads,
    engine sync entry points, a one-level-down syncing helper and an
    obs.span inside a shard_map/pjit body are errors; the same calls
    outside any shard body (or a clean body) are not."""
    fs = lint_snippet(tmp_path, """
        import jax
        from jax.experimental.shard_map import shard_map
        from nds_tpu.engine import ops
        from nds_tpu.obs import trace as obs

        def _helper(x):
            return ops.count_int(x.nrows)

        def make(mesh, specs):
            def local(x, n):
                with obs.span("inner"):
                    pass
                ops.host_read("tag", lambda: 1)
                n.to_int()
                _helper(x)
                return x
            return shard_map(local, mesh=mesh, in_specs=specs,
                             out_specs=specs)
    """, rel="nds_tpu/parallel/other.py")
    rules = [f.rule for f in fs]
    assert rules == ["host-sync-in-shard-map"] * 4, fs
    assert all(f.severity == "error" for f in fs)
    # clean body + syncs OUTSIDE the body: no findings (the rule must
    # not leak past the shard_map'd function)
    fs = lint_snippet(tmp_path, """
        import jax
        import jax.numpy as jnp
        from nds_tpu.engine import ops
        from nds_tpu.parallel.exchange import shard_map_compat

        def make(mesh, specs):
            def local(x):
                return jax.lax.psum(x, "shard")
            step = shard_map_compat(local, mesh, specs, specs)
            n = ops.count_int(4)          # outside: legal
            return step, n
    """, rel="nds_tpu/parallel/other.py")
    assert not [f for f in fs if f.rule == "host-sync-in-shard-map"], fs


def test_jax_lint_span_outside_jit_ok(tmp_path):
    # the supported shape: open the span AROUND the jitted call
    fs = lint_snippet(tmp_path, """
        import jax
        from nds_tpu.obs import trace as obs
        @jax.jit
        def kern(x):
            return x + 1
        def drive(x):
            with obs.span("drive", chunk=0):
                return kern(x)
    """)
    assert not [f for f in fs if f.rule == "span-in-jit"], \
        "\n".join(str(f) for f in fs)


def test_jax_lint_span_unrelated_callables_ok(tmp_path):
    # .span() on a non-obs owner (re.Match.span) and a bare local helper
    # named span() are NOT trace contexts — must not trip the CI gate
    fs = lint_snippet(tmp_path, """
        import re
        import jax
        @jax.jit
        def kern(x):
            m = re.match("a+", "aaa")
            a, b = m.span()
            def span(v):
                return v + a
            return span(x) + b
    """)
    assert not [f for f in fs if f.rule == "span-in-jit"], \
        "\n".join(str(f) for f in fs)


def test_jax_lint_span_import_alias_flagged(tmp_path):
    # a non-conventional import alias still resolves to the obs module
    fs = lint_snippet(tmp_path, """
        import jax
        import nds_tpu.obs.trace as tr
        from nds_tpu.obs.trace import span as mark
        @jax.jit
        def kern(x):
            with tr.span("a"):
                x = x + 1
            with mark("b"):
                x = x * 2
            return x
    """)
    assert [f.rule for f in fs] == ["span-in-jit"] * 2


def test_jax_lint_factory_form_jit_decorator(tmp_path):
    # @jax.jit(static_argnums=...) — the decorator-factory spelling — must
    # be recognized like @jax.jit and functools.partial(jax.jit, ...)
    fs = lint_snippet(tmp_path, """
        import jax
        @jax.jit(static_argnums=(1,))
        def kern(x, n):
            if n > 2:          # static arg: fine
                x = x + 1
            if x > 0:          # traced arg: hazard
                return x
            return x
    """)
    assert [f.rule for f in fs] == ["tracer-if"]


def test_jax_lint_cache_through_parameter_alias(tmp_path):
    # the planner threads _MASK_FUSE_CACHE/_EXPR_FUSE_CACHE through
    # _fused_run's `cache` parameter: writes, evictions, and key hazards
    # through the alias must count against the module cache
    fs = lint_snippet(tmp_path, """
        _ALIAS_CACHE: dict = {}
        class P:
            def outer(self, cols):
                return self._run(_ALIAS_CACHE, cols)
            def _run(self, cache, cols):
                cache[(len(cols), [c.kind for c in cols])] = cols
                return cols
    """)
    assert sorted(f.rule for f in fs) == ["cache-key-list",
                                         "unbounded-cache"]
    assert all("_ALIAS_CACHE" in f.message for f in fs)
    # eviction through the alias clears unbounded-cache (the _fused_run
    # shape: len() guard + pop through the parameter)
    fs = lint_snippet(tmp_path, """
        _ALIAS_CACHE: dict = {}
        def outer(cols):
            return _run(_ALIAS_CACHE, cols, 16)
        def _run(cache, cols, cap):
            if len(cache) >= cap:
                cache.pop(next(iter(cache)))
            cache[len(cols)] = cols
            return cache[len(cols)]
    """)
    assert not fs, "\n".join(str(f) for f in fs)


def test_jax_lint_cache_rules(tmp_path):
    fs = lint_snippet(tmp_path, """
        _GROW_CACHE: dict = {}
        _BOUND_CACHE: dict = {}
        _MAX = 16
        def remember(key, cols, val):
            _GROW_CACHE[(key, [c.kind for c in cols])] = val
            if len(_BOUND_CACHE) >= _MAX:
                _BOUND_CACHE.pop(next(iter(_BOUND_CACHE)))
            _BOUND_CACHE[key] = val
    """)
    assert sorted(f.rule for f in fs) == ["cache-key-list", "unbounded-cache"]
    assert all("_GROW_CACHE" in f.message for f in fs)


def test_jax_lint_cache_setdefault_counts_as_write(tmp_path):
    # a cache populated only via .setdefault() grows exactly like a
    # subscript store — same hazard, same rule
    fs = lint_snippet(tmp_path, """
        _MISS_CACHE: dict = {}
        def remember(k, cols, v):
            return _MISS_CACHE.setdefault((k, [c.kind for c in cols]), v)
    """)
    assert sorted(f.rule for f in fs) == ["cache-key-list",
                                         "unbounded-cache"]
    fs = lint_snippet(tmp_path, """
        _MISS_CACHE: dict = {}
        def remember(k, v):
            if len(_MISS_CACHE) >= 16:
                _MISS_CACHE.popitem()
            return _MISS_CACHE.setdefault(k, v)
    """)
    assert not fs, "\n".join(str(f) for f in fs)


def test_jax_lint_swallowed_fault(tmp_path):
    """An except clause catching a classified fault (FaultError family,
    bare / attribute-qualified / inside a tuple) must record a
    FaultEvent or re-raise — anything else is an un-auditable recovery
    (DESIGN.md 'Fault-tolerance contract')."""
    fs = lint_snippet(tmp_path, """
        from nds_tpu.engine import faults as _F
        def recover(fn):
            try:
                return fn()
            except _F.FaultInjected:
                return None                      # swallowed: flagged
        def recover2(fn):
            try:
                return fn()
            except (OSError, _F.FaultError) as exc:
                log(exc)                         # swallowed: flagged
        def recover3(fn):
            try:
                return fn()
            except FaultInjected:
                pass                             # bare name: flagged
    """, rel="nds_tpu/engine/stream.py")
    assert [f.rule for f in fs] == ["swallowed-fault"] * 3
    assert all(f.severity == "error" for f in fs)


def test_jax_lint_swallowed_fault_compliant_ok(tmp_path):
    # recording the event, re-raising, or raising a classified
    # replacement all comply; unrelated except clauses never trip
    fs = lint_snippet(tmp_path, """
        from nds_tpu.engine import faults as _F
        def recover(fn):
            try:
                return fn()
            except _F.FaultInjected as exc:
                _F.record_fault_event(exc.seam, "degrade")
                return None
        def reraise(fn):
            try:
                return fn()
            except _F.StatementTimeout:
                raise
        def classify(fn):
            try:
                return fn()
            except _F.FaultError as exc:
                raise RuntimeError("classified") from exc
        def unrelated(fn):
            try:
                return fn()
            except ValueError:
                return None
    """, rel="nds_tpu/engine/stream.py")
    assert not fs, "\n".join(str(f) for f in fs)


def test_jax_lint_swallowed_fault_suppression_and_tree_clean(tmp_path):
    fs = lint_snippet(tmp_path, """
        from nds_tpu.engine import faults as _F
        def recover(fn):
            try:
                return fn()
            # nds-lint: ignore[swallowed-fault]
            except _F.FaultInjected:
                return None
    """, rel="nds_tpu/engine/stream.py")
    assert not fs
    # the real tree's recovery paths all comply (baseline untouched)
    from nds_tpu.analysis.jax_lint import lint_tree
    got = [f for f in lint_tree() if f.rule == "swallowed-fault"]
    assert not got, "\n".join(str(f) for f in got)


def test_jax_lint_chunk_loop_host_sync(tmp_path):
    # in ANY module (not just hot-path files): a sync per streamed chunk
    # is the O(chunks) cost the compiled executor removes
    fs = lint_snippet(tmp_path, """
        import numpy as np
        from nds_tpu.engine import ops as E
        def eager(table, parts):
            outs = []
            for chunk in table.device_chunks():
                n = E.count_int(chunk.nrows)
                outs.append(np.asarray(chunk.data))
                m = chunk.nrows.to_int()
                k = chunk.total.item()
            for chunk in table.padded_chunks():
                E.resolve_counts()
            return outs
    """, rel="nds_tpu/report.py")
    assert [f.rule for f in fs] == ["chunk-loop-host-sync"] * 5
    assert all(f.severity == "warning" for f in fs)


def test_jax_lint_chunk_loop_scoping(tmp_path):
    # the same syncs OUTSIDE a chunk loop (or in a plain loop) are not
    # this rule's findings; device-resident chunk work is clean
    fs = lint_snippet(tmp_path, """
        from nds_tpu.engine import ops as E
        def fine(table, items):
            n = E.count_int(table.nrows)      # not in a loop
            for x in items:                   # not a chunk loop
                y = E.count_int(x.nrows)
            outs = []
            for chunk in table.device_chunks():
                outs.append(chunk)            # sync-free chunk loop
            return outs
    """, rel="nds_tpu/report.py")
    assert not [f for f in fs if f.rule == "chunk-loop-host-sync"]


def test_jax_lint_chunk_loop_helper_sync(tmp_path):
    # the one-level-down gap: a host sync hidden in a module-local helper
    # (bare name or self.method) called from a chunk-loop body is flagged
    # at the call site, with the helper's sync primitive named
    fs = lint_snippet(tmp_path, """
        from nds_tpu.engine import ops as E
        def _resolve(chunk):
            return E.count_int(chunk.nrows)
        class P:
            def _peek(self, chunk):
                return chunk.total.item()
            def run(self, table):
                outs = []
                for chunk in table.device_chunks():
                    n = _resolve(chunk)
                    m = self._peek(chunk)
                    outs.append(chunk)
                return outs
    """, rel="nds_tpu/report.py")
    assert [f.rule for f in fs] == ["chunk-loop-host-sync"] * 2
    assert "_resolve" in fs[0].message and "count_int()" in fs[0].message
    assert "_peek" in fs[1].message and ".item()" in fs[1].message


def test_jax_lint_chunk_loop_helper_scoping(tmp_path):
    # sync-free helpers, helpers called outside chunk loops, and
    # non-local callees (module attributes) are all clean
    fs = lint_snippet(tmp_path, """
        from nds_tpu.engine import ops as E
        def _shape(chunk):
            return chunk.plen
        def run(table, other):
            n = E.count_int(other.nrows)     # outside any chunk loop
            outs = []
            for chunk in table.device_chunks():
                outs.append(_shape(chunk))   # helper does not sync
                outs.append(E.bucket_len(4)) # non-sync engine call
            return outs, n
    """, rel="nds_tpu/report.py")
    assert not [f for f in fs if f.rule == "chunk-loop-host-sync"], \
        "\n".join(str(f) for f in fs)


def test_jax_lint_chunk_loop_helper_class_scoped(tmp_path):
    # a self.method call resolves only against the ENCLOSING class: a
    # same-named method on an unrelated class in the module that does
    # sync is not evidence against this class's sync-free one
    fs = lint_snippet(tmp_path, """
        class A:
            def _peek(self):
                return self.total.item()
        class B:
            def _peek(self, chunk):
                return chunk.plen
            def run(self, table):
                outs = []
                for chunk in table.device_chunks():
                    outs.append(self._peek(chunk))
                return outs
    """, rel="nds_tpu/report.py")
    assert not [f for f in fs if f.rule == "chunk-loop-host-sync"], \
        "\n".join(str(f) for f in fs)


def test_jax_lint_suppression_honored(tmp_path):
    fs = lint_snippet(tmp_path, """
        def drain(cols):
            out = []
            for c in cols:
                # nds-lint: ignore[host-sync-in-loop]
                out.append(c.total.item())
                v = c.n.item()  # nds-lint: ignore[host-sync-in-loop]
                w = c.m.item()  # nds-lint: ignore[tracer-if] (wrong rule)
            return out, v, w
    """)
    # only the wrong-rule suppression still fires
    assert len(fs) == 1 and fs[0].rule == "host-sync-in-loop"


def test_jax_lint_current_tree_clean():
    """The engine itself must stay hazard-free beyond the baseline (which
    carries none for jax-lint today)."""
    from nds_tpu.analysis.jax_lint import lint_tree
    fs = lint_tree(os.path.join(REPO, "nds_tpu"))
    assert not fs, "\n".join(str(f) for f in fs)


# ---------------------------------------------------------------------------
# driver audit
# ---------------------------------------------------------------------------


def driver_snippet(tmp_path, code):
    from nds_tpu.analysis.driver_audit import audit_file
    p = tmp_path / "driver.py"
    p.write_text(textwrap.dedent(code))
    return audit_file(str(p), "tools/driver.py")


def test_driver_audit_rules(tmp_path):
    fs = driver_snippet(tmp_path, """
        import json, os, subprocess
        def run(cmd, out_path, doc):
            try:
                os.system("rm -rf " + cmd)
                subprocess.run(cmd, shell=True)
            except Exception:
                pass
            json.dump(doc, open(out_path, "w"))
    """)
    assert sorted(f.rule for f in fs) == [
        "shell-injection", "shell-injection", "swallowed-exception",
        "unmanaged-file-handle"]


def test_driver_audit_shell_true_through_aliases(tmp_path):
    # shell=True is the hazard regardless of the callee's spelling:
    # `from subprocess import run` and `import subprocess as sp` must not
    # slip past the error-severity gate
    fs = driver_snippet(tmp_path, """
        import subprocess as sp
        from subprocess import run
        def go(cmd):
            run(cmd, shell=True)
            sp.run(cmd, shell=True)
            sp.check_output(cmd, shell=False)
    """)
    assert [f.rule for f in fs] == ["shell-injection"] * 2


def test_driver_audit_managed_patterns_ok(tmp_path):
    fs = driver_snippet(tmp_path, """
        import json, subprocess
        def run(argv, out_path, doc):
            subprocess.run(argv, capture_output=True)
            with open(out_path, "w") as f:
                json.dump(doc, f)
            g = open(out_path + ".tmp", "w")
            try:
                g.write("x")
            finally:
                g.close()
            try:
                return json.load(open(out_path))  # nds-lint: ignore
            except OSError:
                pass
    """)
    assert not fs, "\n".join(str(f) for f in fs)


def test_driver_audit_rebound_handle_leak(tmp_path):
    # reusing a name for two sequential open()s leaks the first handle;
    # close-then-reopen is fine but the second handle needs its own close
    fs = driver_snippet(tmp_path, """
        def two_logs(a, b):
            f = open(a, "w")
            f.write("x")
            f = open(b, "w")
            f.close()
    """)
    assert [f.rule for f in fs] == ["unmanaged-file-handle"]
    assert fs[0].line == 3   # the FIRST open is the leak
    fs = driver_snippet(tmp_path, """
        def two_logs(a, b):
            f = open(a, "w")
            f.close()
            f = open(b, "w")
            f.write("x")
    """)
    assert [(f.rule, f.line) for f in fs] == [("unmanaged-file-handle", 5)]


def test_driver_audit_annotated_assign_handle(tmp_path):
    # f: IO = open(p) tracks like f = open(p): closed is clean, unclosed
    # is a finding
    fs = driver_snippet(tmp_path, """
        def go(p):
            f: object = open(p)
            f.close()
    """)
    assert not fs, "\n".join(str(f) for f in fs)
    fs = driver_snippet(tmp_path, """
        def go(p):
            f: object = open(p)
            return f.read()
    """)
    assert [f.rule for f in fs] == ["unmanaged-file-handle"]


def test_driver_audit_attribute_held_handle_ok(tmp_path):
    # a handle stored on an object has a deliberate cross-method lifetime
    fs = driver_snippet(tmp_path, """
        class Log:
            def start(self, path):
                self.f = open(path, "w")
            def stop(self):
                self.f.close()
    """)
    assert not fs, "\n".join(str(f) for f in fs)


# ---------------------------------------------------------------------------
# exec audit: static execution-path classification + sync bounds
# ---------------------------------------------------------------------------


def exec_audit(sql, streamed=("store_sales",)):
    from nds_tpu.analysis.exec_audit import ExecAuditor
    return ExecAuditor(streamed=set(streamed)).audit_sql(sql)


def test_exec_audit_ab_templates_classification():
    """The A/B templates pinned by test_synccount: the static auditor
    must predict the exact path the runtime takes — every template now
    streams compiled (the multi-pass conversions cleared the IN-subquery
    fallback too), with every compiled scan's steady-state bound inside
    the streamed budget, and the converted shapes carrying their
    mechanism tags."""
    from nds_tpu.analysis.exec_audit import (CLASS_COMPILED, CLASS_EAGER,
                                             SYNC_BUDGET)
    from test_synccount import _STREAM_AB_QUERIES
    reports = [exec_audit(q) for q, _must in _STREAM_AB_QUERIES]
    got = [r.classification for r in reports]
    want = [CLASS_COMPILED if must else CLASS_EAGER
            for _q, must in _STREAM_AB_QUERIES]
    assert got == want, got
    for r in reports:
        if r.classification == CLASS_COMPILED:
            assert r.sync_bound is not None and r.sync_bound <= SYNC_BUDGET
            for s in r.scans:
                assert s.compiled and s.gate_bound <= SYNC_BUDGET
    mechs = [set(m for s in r.scans for m in s.mechanisms)
             for r in reports]
    # ab4 (IN subquery), ab10 (outer gather), ab11 (outer build),
    # ab13 (NOT IN: recorded scalar)
    assert "streamed-subquery" in mechs[3]
    assert "outer-gather" in mechs[9]
    assert "outer-build" in mechs[10]
    assert {"streamed-subquery", "recorded-scalar"} <= mechs[12]


def test_exec_audit_device_resident():
    from nds_tpu.analysis.exec_audit import CLASS_DEVICE
    r = exec_audit("""
        select d_year, i_brand_id, sum(ss_ext_sales_price) s
        from store_sales, date_dim, item
        where ss_sold_date_sk = d_date_sk and ss_item_sk = i_item_sk
        group by d_year, i_brand_id""", streamed=())
    assert r.classification == CLASS_DEVICE
    assert not r.scans
    assert r.sync_bound is not None


def test_exec_audit_reason_codes():
    """Each eager-fallback reason code fires on its canonical shape,
    mirroring the runtime routing of engine/stream.py."""
    # cartesian layout in the streamed graph: _cartesian's host count
    # resolve raises StreamSyncError under stream bounds
    r = exec_audit("select count(*) c from store_sales, item "
                   "where ss_ext_sales_price > 9990 and i_brand_id = 1")
    assert r.classification == "eager-fallback"
    assert r.reasons == ("chunk-dependent-host-read",)
    assert r.sync_bound is None and r.per_chunk >= 1
    # bare scan: the survivor accumulator keeps every chunk row — but the
    # memory proof admits it (pruned SF10 store_sales fits the capacity
    # model), so it streams compiled with the proof-sized accumulator
    r = exec_audit("select ss_item_sk from store_sales")
    assert r.classification == "compiled-stream" and not r.reasons
    # ...and the SAME bare scan against a capacity model that cannot
    # admit the bound keeps the accumulator-overflow fallback (lockstep
    # with the runtime's legacy-ceiling clamp + overflow rerun)
    from nds_tpu.analysis.exec_audit import ExecAuditor
    from nds_tpu.analysis.mem_audit import MemModel
    tiny = ExecAuditor(streamed={"store_sales"},
                       mem_model=MemModel(capacity_bytes=1 << 20))
    r = tiny.audit_sql("select ss_item_sk from store_sales")
    assert r.reasons == ("accumulator-overflow",)
    # an explicit NDS_TPU_STREAM_ACC_ROWS ceiling below the table's rows
    # also forbids the proof (the hard ceiling wins; overflow certain)
    capped = ExecAuditor(streamed={"store_sales"},
                         mem_model=MemModel(acc_ceiling=1 << 10))
    r = capped.audit_sql("select ss_item_sk from store_sales")
    assert r.reasons == ("accumulator-overflow",)
    # chunked scan on the null-introducing side of a LEFT join: the
    # multi-pass outer-build conversion (unmatched-key accumulator,
    # extras at materialize) streams it compiled
    r = exec_audit("select d_year, ss_item_sk from date_dim left join "
                   "store_sales on d_date_sk = ss_sold_date_sk")
    assert r.classification == "compiled-stream"
    assert any("outer-build" in s.mechanisms for s in r.scans)
    # ...but a remaining WHERE conjunct over either side needs the extras
    # to flow through post-join structure: ineligible, the side
    # materializes whole and outer-join-extras still fires
    r = exec_audit("select d_year, ss_item_sk from date_dim left join "
                   "store_sales on d_date_sk = ss_sold_date_sk "
                   "where ss_item_sk > 5 or d_year = 1999")
    assert "outer-join-extras" in r.reasons
    # chunked scan PRESERVED with ON keys that do NOT cover the right
    # side's primary key: no sync-free per-chunk gather exists, the left
    # side materializes whole — outer-join-extras
    r = exec_audit("select ss_item_sk, i_brand_id from store_sales "
                   "left join item on ss_item_sk = i_brand_id")
    assert "outer-join-extras" in r.reasons
    # chunked scan PRESERVED with ON keys = the right side's PK: the
    # outer-gather conversion rides the join into the per-chunk program
    r = exec_audit("select ss_item_sk, i_brand_id from store_sales "
                   "left join item on ss_item_sk = i_item_sk "
                   "where ss_ext_sales_price > 9900")
    assert r.classification == "compiled-stream"
    assert any("outer-gather" in s.mechanisms for s in r.scans)
    # subquery conjunct: formerly the canonical subquery-residual eager
    # fallback — now pre-planned into a device residual, compiled
    r = exec_audit("select count(*) c from store_sales "
                   "where ss_sold_date_sk in "
                   "(select d_date_sk from date_dim where d_moy = 11)")
    assert r.classification == "compiled-stream" and not r.reasons
    assert any("streamed-subquery" in s.mechanisms for s in r.scans)


def test_exec_audit_cte_shadowing_not_streamed():
    # a CTE shadowing a chunked catalog name resolves to the CTE (the
    # planner checks the cte stack first): nothing streams
    from nds_tpu.analysis.exec_audit import CLASS_DEVICE
    r = exec_audit("""
        with store_sales as (select d_date_sk x from date_dim)
        select count(*) c from store_sales""")
    assert r.classification == CLASS_DEVICE


def test_exec_audit_gate_trips_on_sync_heavy_plan():
    """Negative case: a deliberately sync-heavy — but still streamable —
    toy plan must trip the stream-sync-budget gate: two chained non-PK
    outer joins (2 syncs each: probe + batched extras) on top of the
    pipeline's materializing sync, a multi-key grouping (batched resolve
    + packed range probe) and the output resolution exceed the budget."""
    from nds_tpu.analysis.exec_audit import (SYNC_BUDGET,
                                             reports_to_findings)
    r = exec_audit("""
        select ss_item_sk, d_year, count(*) c
        from store_sales
             left join date_dim on ss_sold_date_sk = d_moy
             left join item on ss_item_sk = i_brand_id
        where ss_quantity > 0
        group by ss_item_sk, d_year""")
    assert r.classification == "compiled-stream"
    assert r.scans[0].gate_bound > SYNC_BUDGET
    fs = reports_to_findings([r])
    assert [f.rule for f in fs] == ["stream-sync-budget"]
    assert fs[0].severity == "error"


def test_exec_audit_corpus_full_coverage():
    """Every template statement receives a classification with reasons,
    deterministically, and no streamable plan's static bound exceeds the
    streamed budget — the lint-gate contract over the shipped corpus."""
    from nds_tpu.analysis.exec_audit import (CLASS_COMPILED, CLASS_EAGER,
                                             CLASS_DEVICE, SYNC_BUDGET,
                                             audit_exec_corpus,
                                             reports_to_findings)
    reports = audit_exec_corpus()
    assert len(reports) >= 99
    allowed = {CLASS_COMPILED, CLASS_EAGER, CLASS_DEVICE}
    for r in reports:
        assert r.classification in allowed, (r.query, r.classification)
        if r.classification == CLASS_EAGER:
            assert r.reasons, f"{r.query}: eager with no reason code"
        for s in r.scans:
            if s.compiled:
                assert s.gate_bound <= SYNC_BUDGET, (r.query, s)
    assert not reports_to_findings(reports)
    again = audit_exec_corpus()
    assert [r.to_dict() for r in again] == [r.to_dict() for r in reports]


def test_exec_audit_collective_budget_and_gate():
    """Sharded collective budget: under a forced mesh env the model
    prices the exchange pass from the scan's pruned width and keys, the
    corpus stays within the collective-budget gate, and a hand-built
    over-budget verdict trips the gate (a gate that cannot fail proves
    nothing). Without the env, every budget is zero — the corpus
    classification cannot move."""
    from nds_tpu.analysis.exec_audit import (COLLECTIVE_CHUNK_BUDGET,
                                             COLLECTIVE_FINAL_BUDGET,
                                             ExecReport, ScanVerdict,
                                             reports_to_findings)
    # unsharded default: zero budgets
    r = exec_audit("""
        select ss_item_sk, count(*) c from store_sales, store_returns
        where ss_item_sk = sr_item_sk group by ss_item_sk""")
    assert r.scans[0].shards == 1 and r.scans[0].a2a_chunk == 0
    old = os.environ.get("NDS_TPU_STREAM_SHARDS")
    os.environ["NDS_TPU_STREAM_SHARDS"] = "2"
    try:
        r = exec_audit("""
            select ss_item_sk, count(*) c from store_sales, store_returns
            where ss_item_sk = sr_item_sk group by ss_item_sk""")
        s = r.scans[0]
        assert s.shards == 2
        # keys present: the exchange MAY run — bounded by 2 x width + 2
        assert 0 < s.a2a_chunk <= COLLECTIVE_CHUNK_BUDGET
        assert s.coll_final == 3
        assert not reports_to_findings([r])
        # a keyless scan can never exchange: per-chunk budget zero
        r2 = exec_audit("select ss_item_sk, count(*) c from store_sales "
                        "group by ss_item_sk")
        assert r2.scans[0].a2a_chunk == 0 and r2.scans[0].coll_final == 3
    finally:
        if old is None:
            del os.environ["NDS_TPU_STREAM_SHARDS"]
        else:
            os.environ["NDS_TPU_STREAM_SHARDS"] = old
    # the gate can fail: an over-budget verdict is an error finding
    bad = ExecReport(
        "toy.tpl", "toy", "compiled-stream",
        scans=(ScanVerdict("ss", "store_sales", True, shards=2,
                           a2a_chunk=COLLECTIVE_CHUNK_BUDGET + 1,
                           coll_final=COLLECTIVE_FINAL_BUDGET + 1),))
    fs = reports_to_findings([bad])
    assert [f.rule for f in fs] == ["collective-budget"]
    assert fs[0].severity == "error"


def test_exec_audit_differential_harness():
    """The lockstep contract: static path/sync predictions must match the
    runtime StreamEvent evidence on the A/B templates, and the harness
    must FAIL on the injected model-drift fixture (flipped paths) — a
    gate that cannot fail proves nothing."""
    import importlib.util
    path = os.path.join(REPO, "tools", "exec_audit_diff.py")
    spec = importlib.util.spec_from_file_location("exec_audit_diff", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    queries, _ = mod._load_ab_templates()
    reports = mod.predict(queries)
    evidence = mod.collect_runtime_evidence()
    ok, lines = mod.compare(reports, evidence)
    assert ok, "\n".join(lines)
    drift_ok, drift_lines = mod.compare(reports, evidence,
                                        inject_drift=True)
    assert not drift_ok, "drift fixture failed to fail"
    assert any("MISMATCH" in ln for ln in drift_lines)


def test_exec_audit_sharded_collective_differential():
    """The sharded half of the lockstep contract: the measured
    ``StreamEvent.collectives`` of the shard_map'd pipeline (forced
    2-shard mesh) must fit the static budget ``a2a_chunk x chunks +
    coll_final`` on the sharded A/B subset, the exchange pass must
    charge zero host syncs, and the zeroed-budget drift fixture must
    fail — the partitioned template really crosses shards, so a zero
    budget cannot hold."""
    import importlib.util
    path = os.path.join(REPO, "tools", "exec_audit_diff.py")
    spec = importlib.util.spec_from_file_location("exec_audit_diff2", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    shard_ev, n_shards = mod.collect_sharded_evidence()
    assert shard_ev, "sharded sweep found no multi-device mesh"
    ab = mod._load_ab_module()
    with ab._forced_stream_partitions():
        with ab._forced_stream_shards():
            reports = mod.predict(ab._STREAM_AB_QUERIES)
    ok, lines = mod.compare_sharded(reports, shard_ev, n_shards)
    assert ok, "\n".join(lines)
    drift_ok, drift_lines = mod.compare_sharded(reports, shard_ev,
                                                n_shards,
                                                inject_drift=True)
    assert not drift_ok, "sharded drift fixture failed to fail"
    assert any("collectives > static budget" in ln for ln in drift_lines)


# ---------------------------------------------------------------------------
# mem audit: static peak-HBM bounds + accumulator proofs
# ---------------------------------------------------------------------------


def mem_audit(sql, streamed=("store_sales",), **model_kw):
    from nds_tpu.analysis.mem_audit import MemAuditor, MemModel
    return MemAuditor(streamed=set(streamed),
                      model=MemModel(**model_kw)).audit_sql(sql)


def test_mem_audit_corpus_finite_and_deterministic():
    """Every template statement gets a finite positive byte bound, the
    walk is deterministic, and the partition decomposition clears EVERY
    capacity finding: the 7 former fan-out accumulators
    (query17/24x2/25/29/64/72) are now proven per partition, each
    per-partition bound inside the capacity model."""
    from nds_tpu.analysis.mem_audit import (audit_mem_corpus,
                                            hbm_capacity_bytes,
                                            reports_to_findings)
    reports = audit_mem_corpus()
    assert len(reports) >= 99
    for r in reports:
        assert r.mode in ("streamed", "device"), (r.query, r.detail)
        assert r.peak_bytes > 0 and r.out_rows >= 0
    assert reports_to_findings(reports) == []
    partitioned = {r.query: s for r in reports for s in r.scans
                   if s.partitions > 1}
    # query54 joined the set when its subquery conjuncts became
    # residual-planned filters: the graph turned provable and its
    # whole-statement bound is past capacity, so it decomposes too.
    # query17 LEFT the set when encoded columnar execution shrank its
    # streamed row width: the whole-statement bound now fits capacity,
    # so its static partition count dropped from 4 to 1 (asserted below)
    assert sorted(partitioned) == \
        ["query24_part1", "query24_part2", "query25",
         "query29", "query54", "query64", "query72"]
    cap = hbm_capacity_bytes()
    q17 = [s for r in reports if r.query == "query17" for s in r.scans]
    assert q17 and all(s.partitions == 1 for s in q17)
    assert any(s.provable and s.acc_bytes <= cap for s in q17)
    for q, s in partitioned.items():
        assert s.provable and s.part_bytes <= cap, (q, s)
        assert s.part_rows * s.partitions >= s.acc_rows, \
            (q, "partition shares must cover the whole bound")
    again = audit_mem_corpus()
    assert [r.to_dict() for r in again] == [r.to_dict() for r in reports]


def test_mem_audit_bound_rules():
    """The bound rules of DESIGN.md's static memory model, each on its
    canonical shape."""
    # PK star join: every batch covers a dimension primary key, so the
    # survivor multiplicity is 1 (k=0) and the accumulator is bounded by
    # the fact side's bucketed rows
    r = mem_audit("""
        select d_year, sum(ss_ext_sales_price) s
        from store_sales, date_dim, item
        where ss_sold_date_sk = d_date_sk and ss_item_sk = i_item_sk
        group by d_year""")
    (s,) = r.scans
    assert s.provable and s.fanout_k == 0
    # group-by domain rule: d_year's value domain is at most date_dim's
    # row bound, far below the fact's
    from nds_tpu.analysis.mem_audit import DEFAULT_ROW_BOUNDS
    assert r.out_rows <= DEFAULT_ROW_BOUNDS["date_dim"]
    # non-PK equi join: bounded only by the enforced fanout pair bucket
    r = mem_audit("""
        select count(*) c from store_sales, item
        where ss_item_sk = i_brand_id""")
    (s,) = r.scans
    assert s.provable and s.fanout_k == 1
    assert r.out_rows == 1               # keyless aggregate: one row
    # a subquery conjunct is a residual-planned FILTER (multi-pass
    # streaming): it neither grows rows nor breaks the proof, so the
    # scan keeps the bare-scan bound
    r = mem_audit("""
        select count(*) c from store_sales where ss_sold_date_sk in
        (select d_date_sk from date_dim where d_moy = 11)""")
    assert r.scans and r.scans[0].provable and r.scans[0].fanout_k == 0
    # unconnected parts (cartesian layout): unprovable too
    r = mem_audit("select count(*) c from store_sales, item "
                  "where ss_ext_sales_price > 0 and i_brand_id = 1")
    assert r.scans and not r.scans[0].provable
    # filters assume no reduction: the filtered bare scan keeps the same
    # accumulator bound as the unfiltered one
    a = mem_audit("select ss_item_sk from store_sales")
    b = mem_audit("select ss_item_sk from store_sales "
                  "where ss_item_sk > 10")
    assert a.scans[0].acc_rows == b.scans[0].acc_rows
    # column pruning: referencing fewer columns shrinks the byte bound
    wide = mem_audit("select ss_item_sk, ss_ext_sales_price, "
                     "ss_sold_date_sk from store_sales")
    assert a.scans[0].acc_bytes < wide.scans[0].acc_bytes
    # LIMIT clamps the output-row bound exactly
    r = mem_audit("select ss_item_sk from store_sales "
                  "order by ss_item_sk limit 7")
    assert r.out_rows == 7
    # intersect/except output is a subset of the LEFT branch, never the
    # branch sum
    r = mem_audit("select d_year from date_dim except "
                  "select d_year from date_dim where d_moy = 1",
                  streamed=())
    assert r.out_rows == DEFAULT_ROW_BOUNDS["date_dim"]


def test_mem_audit_capacity_gate():
    """hbm-capacity trips when a proven accumulator bound (streamed) or a
    device-resident peak bound exceeds the configured capacity."""
    from nds_tpu.analysis.mem_audit import reports_to_findings
    r = mem_audit("select ss_item_sk from store_sales",
                  capacity_bytes=1 << 20)
    fs = reports_to_findings([r], capacity_bytes=1 << 20)
    assert [f.rule for f in fs] == ["hbm-capacity"]
    assert "accumulator" in fs[0].message
    # same statement under the default model: clean
    assert not reports_to_findings([mem_audit(
        "select ss_item_sk from store_sales")])
    # device-resident peak gate
    r = mem_audit("select * from customer", streamed=())
    assert r.mode == "device"
    fs = reports_to_findings([r], capacity_bytes=1 << 10)
    assert [f.rule for f in fs] == ["hbm-capacity"]
    assert "device-resident" in fs[0].message


def test_mem_audit_partition_rules(monkeypatch):
    """The grace-style partition proof: choose_partitions picks the
    smallest power-of-two count whose skew-factored per-partition bound
    fits capacity, NDS_TPU_STREAM_PARTITIONS pins it, scans with no
    chunk-side equi key never partition, and the hbm-capacity gate moves
    to the per-partition bound for partitioned scans."""
    from nds_tpu.analysis.mem_audit import (choose_partitions,
                                            partition_row_bound,
                                            reports_to_findings,
                                            stream_partition_keys,
                                            structural_row_bound)
    rows, k, fanout = 28_900_000, 1, 4
    whole = structural_row_bound(rows, k, fanout)
    # auto: whole bound fits -> unpartitioned
    assert choose_partitions(rows, k, fanout, 150,
                             whole * 150 + 1) == (1, None)
    # auto: over capacity -> smallest admitting power of two
    p, bound = choose_partitions(rows, k, fanout, 150, 16 << 30)
    assert p == 4 and bound == partition_row_bound(rows, 4, k, fanout)
    assert bound * 150 <= 16 << 30
    assert partition_row_bound(rows, 2, k, fanout) >= bound
    # the skew-factored shares always cover the whole bound
    assert bound * p >= whole // 2
    # forced count wins, rounded up to a power of two
    assert choose_partitions(rows, k, fanout, 150, 16 << 30,
                             forced=3)[0] == 4
    assert choose_partitions(rows, k, fanout, 150, 16 << 30,
                             forced=1) == (1, None)
    # nothing admits -> (1, None): the runtime keeps the legacy clamp
    assert choose_partitions(rows, k, fanout, 150, 1 << 10) == (1, None)

    # partition keys: the fan-out batch's chunk-side keys win over a
    # PK-covered batch; a bare scan (no equi edge) has none
    from nds_tpu.sql.parser import parse
    from nds_tpu.analysis.exec_audit import _conjuncts_of
    sel = parse("""select 1 from store_sales, date_dim, store_returns
                   where ss_sold_date_sk = d_date_sk
                     and ss_item_sk = sr_item_sk""").body
    part_cols = [{"store_sales.ss_sold_date_sk", "store_sales.ss_item_sk"},
                 {"date_dim.d_date_sk"},
                 {"store_returns.sr_item_sk",
                  "store_returns.sr_ticket_number"}]
    sources = ["store_sales", "date_dim", "store_returns"]
    keys = stream_partition_keys(part_cols, sources, 0,
                                 _conjuncts_of(sel.where))
    assert keys == ("ss_item_sk",)       # the k=1 batch, not the PK one
    assert stream_partition_keys(part_cols[:1], sources[:1], 0, []) is None

    # gate rule: a partitioned scan whose PER-PARTITION bound fits is
    # clean even though the whole-scan bound is past capacity...
    r = mem_audit("""select ss_item_sk, sr_return_amt
                     from store_sales, store_returns
                     where ss_item_sk = sr_item_sk""",
                  capacity_bytes=1 << 30)
    (s,) = r.scans
    assert s.partitions > 1 and s.acc_bytes > (1 << 30)
    assert s.part_bytes <= (1 << 30)
    assert not reports_to_findings([r], capacity_bytes=1 << 30)
    # ...and a forced under-partitioned count that cannot fit IS a
    # finding, named per partition
    monkeypatch.setenv("NDS_TPU_STREAM_PARTITIONS", "2")
    r = mem_audit("""select ss_item_sk, sr_return_amt
                     from store_sales, store_returns
                     where ss_item_sk = sr_item_sk""",
                  capacity_bytes=1 << 30)
    fs = reports_to_findings([r], capacity_bytes=1 << 30)
    assert [f.rule for f in fs] == ["hbm-capacity"]
    assert "per-partition" in fs[0].message


def test_mem_audit_scoped_star_pruning():
    """statement_needed_names mirrors the planner's scoped-star pruning:
    a star over a derived table disables nothing, a star over a catalog
    table adds that table's columns, an unresolvable star disables."""
    from nds_tpu.analysis.mem_audit import statement_needed_names
    from nds_tpu.sql.parser import parse
    got = statement_needed_names(parse(
        "with v as (select d_year y from date_dim) select * from v"))
    assert got is not None and "d_year" in got and "d_moy" not in got
    # a qualified star over an ALIASED CTE reference is still derived —
    # it must not disable pruning for the whole statement
    got = statement_needed_names(parse(
        "with v as (select d_year y from date_dim) select x.* from v x"))
    assert got is not None and "d_moy" not in got
    got = statement_needed_names(parse("select * from warehouse"))
    assert got is not None and "w_warehouse_sq_ft" in got
    got = statement_needed_names(parse("select t.* from nowhere t"))
    assert got is None


def test_mem_audit_env_knobs_read_at_model_build(monkeypatch):
    """MemModel reads NDS_TPU_HBM_BYTES / STREAM_ACC_ROWS / FANOUT at
    construction, not import — the same build-time discipline the
    executor follows."""
    from nds_tpu.analysis.mem_audit import MemModel, hbm_capacity_bytes
    monkeypatch.setenv("NDS_TPU_HBM_BYTES", "12345")
    monkeypatch.setenv("NDS_TPU_STREAM_ACC_ROWS", "777")
    monkeypatch.setenv("NDS_TPU_STREAM_FANOUT", "8")
    m = MemModel()
    assert hbm_capacity_bytes() == 12345
    assert m.capacity_bytes == 12345
    assert m.acc_ceiling == 777
    assert m.fanout == 8


def test_mem_audit_differential_harness():
    """The soundness contract: measured survivor/output counts must fit
    the static bounds on the A/B templates, and the harness must FAIL on
    the injected drift fixture (zeroed bounds)."""
    path = os.path.join(REPO, "tools", "mem_audit_diff.py")
    spec = importlib.util.spec_from_file_location("mem_audit_diff", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    queries, _ = mod._load_ab_templates()
    evidence, bounds = mod.collect_runtime_evidence()
    assert bounds["store_sales"] == 20_000      # the toy session's truth
    reports = mod.predict(queries, bounds)
    ok, lines = mod.compare(reports, evidence)
    assert ok, "\n".join(lines)
    drift_ok, drift_lines = mod.compare(reports, evidence,
                                        inject_drift=True)
    assert not drift_ok, "drift fixture failed to fail"
    assert any("UNSOUND" in ln for ln in drift_lines)


def test_mem_audit_sharded_bound_differential():
    """The sharded half of the soundness contract: every per-shard
    survivor count (``StreamEvent.shard_rows``) of the shard_map'd
    pipeline must fit the proven per-shard bound
    (``mem_audit.shard_row_bound`` — rows/shards x skew through the
    fan-out), the runtime shard count must equal the model's, and the
    zeroed-bound drift fixture must fail."""
    path = os.path.join(REPO, "tools", "mem_audit_diff.py")
    spec = importlib.util.spec_from_file_location("mem_audit_diff2", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    shard_ev, bounds, n_shards = mod.collect_sharded_evidence()
    assert shard_ev, "sharded sweep found no multi-device mesh"
    ab = mod._load_ab_module()
    with ab._forced_stream_partitions():
        with ab._forced_stream_shards():
            reports = mod.predict(ab._STREAM_AB_QUERIES, bounds)
    ok, lines = mod.compare_sharded(reports, shard_ev, n_shards)
    assert ok, "\n".join(lines)
    drift_ok, drift_lines = mod.compare_sharded(reports, shard_ev,
                                                n_shards,
                                                inject_drift=True)
    assert not drift_ok, "sharded drift fixture failed to fail"
    assert any("UNSOUND" in ln for ln in drift_lines)


# ---------------------------------------------------------------------------
# perf auditor: the static byte/roofline cost model
# ---------------------------------------------------------------------------


def _load_perf_diff(name="perf_audit_diff_t"):
    path = os.path.join(REPO, "tools", "perf_audit_diff.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_perf_audit_corpus_prices_clean():
    """Every corpus statement prices host-only with zero findings: no
    compiled scan fell to the unknown-table default width, and every
    compiled-stream statement carries a nonzero byte/roofline wall."""
    from nds_tpu.analysis.perf_audit import (audit_perf_corpus,
                                             reports_to_findings)
    reports = audit_perf_corpus()
    assert len(reports) == 103
    assert reports_to_findings(reports) == []
    for r in reports:
        if r.classification in ("compiled-stream", "device-resident"):
            assert r.roofline_ms > 0, r.query
            assert r.bytes_hbm > 0, r.query
        if r.classification == "compiled-stream":
            assert r.bytes_h2d > 0, r.query
            assert all(s.priced for s in r.scans if s.compiled), r.query


def test_perf_bottleneck_histogram_pinned():
    """The corpus cost story is a tier-1 contract, pinned like the 96/7
    classification counts: a width-model or stage-model change that
    silently shifts which link bounds a statement must fail loudly.
    Update these counts ONLY together with the matching engine/model
    change — the lockstep rule."""
    from nds_tpu.analysis.perf_audit import (audit_perf_corpus,
                                             bottleneck_counts)
    counts = bottleneck_counts(audit_perf_corpus())
    assert counts == {"h2d-bound": 89, "hbm-bound": 14}, counts


def test_perf_roofline_knobs_move_walls_not_bytes(monkeypatch):
    """NDS_TPU_ROOFLINE_*_GBS re-rates the walls (and can flip the
    bottleneck tag) but NEVER the byte totals — rates are frozen at
    auditor construction, bytes are pure chunk-shape arithmetic."""
    from nds_tpu.analysis.mem_audit import MemModel
    from nds_tpu.analysis.perf_audit import PerfAuditor, roofline_gbs
    monkeypatch.setenv("NDS_TPU_ROOFLINE_ICI_GBS", "93")
    assert roofline_gbs()["ici"] == 93.0
    assert roofline_gbs()["hbm"] == 819.0        # untouched -> default
    sql = ("select ss_item_sk, count(*) c from store_sales "
           "group by ss_item_sk")

    def price():
        model = MemModel(row_bounds={"store_sales": 20_000})
        return PerfAuditor(streamed={"store_sales"},
                           model=model).audit_sql(sql)

    base = price()
    assert base.classification == "compiled-stream"
    assert base.bound == "h2d-bound"             # 32 GB/s PCIe vs HBM
    monkeypatch.setenv("NDS_TPU_ROOFLINE_H2D_GBS", "1e9")
    monkeypatch.setenv("NDS_TPU_ROOFLINE_HBM_GBS", "0.001")
    rerated = price()
    assert rerated.bound == "hbm-bound"
    assert rerated.bytes_h2d == base.bytes_h2d
    assert rerated.bytes_hbm == base.bytes_hbm
    assert rerated.wall_hbm_ms > base.wall_hbm_ms


def test_perf_audit_differential_harness():
    """The exactness contract: measured ``StreamEvent.bytes_h2d`` must
    EQUAL the closed-form prediction on every A/B template (live wire
    widths + the toy session's real rows/chunk geometry), warm must be
    byte-identical to cold, and the zeroed-prediction drift fixture must
    fail."""
    import numpy as np
    mod = _load_perf_diff()
    ab = mod._load_ab_module()
    queries = ab._STREAM_AB_QUERIES
    with ab._forced_stream_partitions():
        session = ab._chunked_star_session(np.random.default_rng(42))
        bounds, chunk_rows = mod._session_params(session)
        assert bounds["store_sales"] == 20_000  # the toy session's truth
        assert chunk_rows == 2048       # passed to ChunkedTable, not env
        reports = mod.predict(queries, bounds, chunk_rows,
                              mod._wire_cols(session))
        evidence = mod._run_sweep(ab, session, list(range(len(queries))))
    # live wire widths upgrade every prediction from bound to equality
    assert all(r.h2d_exact for r in reports)
    # ab12's scalar-subquery chain prices TWO store_sales pipelines,
    # both at the statement-level pruning (the planner prunes once)
    assert sum(1 for c in reports[11].scans if c.compiled) == 2
    ok, lines = mod.compare(reports, evidence)
    assert ok, "\n".join(lines)
    drift_ok, drift_lines = mod.compare(reports, evidence, inject=True)
    assert not drift_ok, "drift fixture failed to fail"
    assert any("EXACTNESS LOST" in ln for ln in drift_lines)


def test_perf_audit_sharded_ici_differential():
    """Sharded arm: measured ``StreamEvent.bytes_ici`` must EQUAL the
    static exchange+reduce aval arithmetic (every subset template is
    ici-exact — no outer builds), and zeroed predictions must fail."""
    import jax
    import numpy as np
    mod = _load_perf_diff("perf_audit_diff_t3")
    ab = mod._load_ab_module()
    queries = ab._STREAM_AB_QUERIES
    with ab._forced_stream_partitions():
        with ab._forced_stream_shards() as n_shards:
            assert len(jax.local_devices()) >= n_shards, \
                "sharded arm needs the forced multi-device mesh"
            session = ab._chunked_star_session(np.random.default_rng(42))
            bounds, chunk_rows = mod._session_params(session)
            reports = mod.predict(queries, bounds, chunk_rows,
                                  mod._wire_cols(session))
            evidence = mod._run_sweep(ab, session,
                                      list(ab._STREAM_AB_SHARDED))
    # the exchange pass is live on at least one subset statement (the
    # arm would be vacuous if every pipeline were reduce-only)
    assert any(c.exchange for i in ab._STREAM_AB_SHARDED
               for c in reports[i].scans)
    ok, lines = mod.compare_sharded(reports, evidence, n_shards)
    assert ok, "\n".join(lines)
    drift_ok, drift_lines = mod.compare_sharded(reports, evidence,
                                                n_shards, inject=True)
    assert not drift_ok, "sharded drift fixture failed to fail"
    assert any("EXACTNESS LOST" in ln for ln in drift_lines)


def test_perf_audit_encoded_off_differential():
    """NDS_TPU_ENCODED=0 arm: the same h2d equality at PLAIN widths —
    the arm that catches a width table hard-wired to the encoded path.
    The toy star's int64 columns ride 8+1 wire bytes unencoded."""
    import numpy as np
    mod = _load_perf_diff("perf_audit_diff_t4")
    ab = mod._load_ab_module()
    queries = ab._STREAM_AB_QUERIES
    with mod._encoded_off():
        with ab._forced_stream_partitions():
            session = ab._chunked_star_session(np.random.default_rng(42))
            bounds, chunk_rows = mod._session_params(session)
            wire = mod._wire_cols(session)
            reports = mod.predict(queries, bounds, chunk_rows, wire)
            evidence = mod._run_sweep(ab, session,
                                      list(mod._ENCODED_OFF_SUBSET))
    assert set(wire["store_sales"].values()) == {9}
    ok, lines = mod.compare(reports, evidence)
    assert ok, "\n".join(lines)
    drift_ok, _lines = mod.compare(reports, evidence, inject=True)
    assert not drift_ok, "encoded-off drift fixture failed to fail"


# ---------------------------------------------------------------------------
# baseline diffing + CI gate
# ---------------------------------------------------------------------------


def test_baseline_rejects_only_new_findings():
    from nds_tpu.analysis import Finding, diff_against_baseline
    old = Finding("a.py", "f", "rule-x", "warning", "msg")
    dup = Finding("a.py", "f", "rule-x", "warning", "msg")
    new = Finding("b.py", "g", "rule-y", "error", "other")
    baseline = {old.key(): 1}
    assert diff_against_baseline([old, new], baseline) == [new]
    # a second instance of an accepted finding is NEW (count semantics)
    assert diff_against_baseline([old, dup], baseline) == [dup]
    assert diff_against_baseline([old], {}) == [old]


def test_baseline_roundtrip(tmp_path):
    from nds_tpu.analysis import (Finding, diff_against_baseline,
                                  load_baseline, write_baseline)
    fs = [Finding("a.py", "f", "r", "warning", "m"),
          Finding("a.py", "f", "r", "warning", "m")]
    path = str(tmp_path / "baseline.json")
    write_baseline(fs, path)
    assert diff_against_baseline(fs, load_baseline(path)) == []


def _run_lint(*argv):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint.py"), *argv],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})


@pytest.fixture(scope="module")
def lint_combined(tmp_path_factory):
    """ONE clean-tree lint subprocess shared by every report-plumbing
    test below. The nine passes run identically whichever report flags
    ride, so the per-flag CLI tests differ only in FORMATTING — a single
    combined ``--format json`` run (machine document on stdout, every
    human table on stderr, ``--json`` file alongside) covers them all
    for the price of one subprocess instead of eight on a one-core
    runner. Seeded-corpus and exit-code-contract runs stay per-test."""
    json_path = str(tmp_path_factory.mktemp("lint") / "report.json")
    r = _run_lint("--format", "json", "--json", json_path,
                  "--stream-report", "--mem-report", "--perf-report",
                  "--num-report", "--param-report")
    assert r.returncode == 0, r.stdout + r.stderr
    return r, json.loads(r.stdout), json_path


def test_lint_cli_gate(tmp_path, lint_combined):
    """The shipped baseline gates clean; a seeded bad template fails.
    Rides the shared subprocess to check --num-report plumbing (the
    proof table, on stderr under --format json) and the ``num_report``
    field in the --json file document."""
    r, _doc, json_path = lint_combined
    assert "# num-audit: per-statement value-range/precision proofs" \
        in r.stderr
    assert "proven-safe compiled-stream" in r.stderr
    report = json.load(open(json_path))
    assert report["pass_counts"]["plan-audit"] >= 1
    assert report["pass_counts"]["num-audit"] == 0
    assert len(report["num_report"]) == 103
    assert not report["new"]

    seeded = tmp_path / "templates"
    shutil.copytree(TEMPLATES, seeded)
    (seeded / "querybad.tpl").write_text(
        "select ss_no_such from store_sales, customer_demographics\n")
    with open(seeded / "templates.lst", "a") as f:
        f.write("querybad.tpl\n")
    r = _run_lint("--templates", str(seeded))
    assert r.returncode == 2
    assert "unresolved-column" in r.stdout
    assert "cartesian-join" in r.stdout


def test_lint_cli_format_json(tmp_path, lint_combined):
    """--format json: stable machine-readable findings on stdout (rule,
    file, symbol, count, baselined) with the exit-code contract
    unchanged."""
    _r, doc, _path = lint_combined
    assert doc["version"] == 1
    assert set(doc["pass_counts"]) == {"plan-audit", "exec-audit",
                                       "mem-audit", "perf-audit",
                                       "num-audit", "param-audit",
                                       "jax-lint", "driver-audit",
                                       "conc-audit"}
    entries = doc["findings"]
    assert entries == sorted(
        entries, key=lambda e: (e["rule"], e["file"], e["symbol"]))
    for e in entries:
        assert set(e) == {"rule", "file", "symbol", "severity", "count",
                          "baselined"}
    # the shipped tree is fully baselined: exactly q77's spec-deliberate
    # cartesian (partitioned accumulation cleared the 7 former
    # hbm-capacity fan-out findings), nothing new
    assert doc["new"] == 0
    assert [(e["rule"], e["baselined"]) for e in entries] == \
        [("cartesian-join", True)]
    # a failing corpus keeps stdout pure JSON and still exits 2
    seeded = tmp_path / "templates"
    shutil.copytree(TEMPLATES, seeded)
    (seeded / "querybad.tpl").write_text("select ss_no_such from store_sales\n")
    with open(seeded / "templates.lst", "a") as f:
        f.write("querybad.tpl\n")
    r = _run_lint("--templates", str(seeded), "--format", "json")
    assert r.returncode == 2
    doc = json.loads(r.stdout)
    assert doc["new"] >= 1
    assert any(e["rule"] == "unresolved-column" and not e["baselined"]
               for e in doc["findings"])


def test_lint_cli_stream_report(lint_combined):
    r, doc, _path = lint_combined
    assert "per-template execution-path classification" in r.stderr
    for klass in ("compiled-stream", "device-resident"):
        assert klass in r.stderr
    # multi-pass streaming: the report names the conversion mechanisms
    # that serve the formerly-eager statements
    for mech in ("streamed-subquery", "outer-gather", "outer-build"):
        assert mech in r.stderr
    # --format json: the machine-readable report carries the mechanism
    # field per scan, stdout stays ONE parseable document
    scans = [s for e in doc["stream_report"] for s in e["scans"]]
    assert any("streamed-subquery" in s["mechanisms"] for s in scans)
    assert any("outer-gather" in s["mechanisms"] for s in scans)


def test_stream_report_classification_counts_pinned():
    """The corpus classification is a tier-1 contract, pinned the same
    way baseline.json is: --stream-report drift (a statement silently
    reclassifying to eager-fallback, or a conversion quietly lost) must
    fail loudly, not surface months later in an SF10 campaign. Update
    these counts ONLY together with the matching engine/audit change —
    the lockstep rule."""
    from collections import Counter

    from nds_tpu.analysis.exec_audit import audit_exec_corpus
    counts = Counter(r.classification for r in audit_exec_corpus())
    assert counts == {"compiled-stream": 96, "device-resident": 7}, counts


def test_lint_cli_mem_report(lint_combined):
    r, doc, _path = lint_combined
    assert "per-statement peak-HBM byte bounds" in r.stderr
    assert "capacity model" in r.stderr
    # provable accumulators print their row bound; the multi-pass
    # conversions left no unprovable corpus scan (subquery conjuncts are
    # residual-planned filters now)
    assert "rows, k=" in r.stderr
    assert "unprovable (eager loop)" not in r.stderr
    # --format json keeps stdout a single document with the report inline
    assert len(doc["mem_report"]) >= 99
    assert all(e["peak_bytes"] > 0 for e in doc["mem_report"])


def test_lint_cli_perf_report(lint_combined):
    r, doc, _path = lint_combined
    assert "per-statement static cost model" in r.stderr
    assert "rates GB/s" in r.stderr
    # the pinned histogram rides the summary line
    assert "h2d-bound" in r.stderr and "hbm-bound" in r.stderr
    # --format json keeps stdout ONE parseable document with the full
    # cost table inline — the machine-readable round trip
    entries = doc["perf_report"]
    assert len(entries) == 103
    for e in entries:
        assert e["bound"] in ("h2d-bound", "hbm-bound", "ici-bound",
                              "sync-bound")
        if e["classification"] == "compiled-stream":
            assert e["bytes_h2d"] > 0 and e["roofline_ms"] > 0
            assert e["scans"] and all(s["priced"] for s in e["scans"]
                                      if s["compiled"])


def test_lint_cli_changed_fast_path():
    """--changed lints only the current git diff; in this checkout it must
    still honor the baseline gate, and it is incompatible with
    --update-baseline (which needs the full findings set)."""
    r = _run_lint("--changed")
    assert r.returncode in (0, 2), r.stdout + r.stderr
    assert "changed files)" in r.stdout or "# lint" in r.stdout
    r = _run_lint("--changed", "--update-baseline")
    assert r.returncode != 0
    assert "--changed" in r.stderr


def test_lint_cli_update_baseline_refuses_foreign_corpus(tmp_path):
    """--update-baseline over a --templates corpus must not clobber the
    checked-in baseline; an explicit --baseline path makes it legal."""
    seeded = tmp_path / "templates"
    shutil.copytree(TEMPLATES, seeded)
    shipped = os.path.join(REPO, "nds_tpu", "analysis", "baseline.json")
    before = open(shipped).read()
    r = _run_lint("--templates", str(seeded), "--update-baseline")
    assert r.returncode != 0
    assert "foreign corpus" in r.stderr
    assert open(shipped).read() == before
    alt = str(tmp_path / "alt_baseline.json")
    report = tmp_path / "accepted.json"
    r = _run_lint("--templates", str(seeded), "--update-baseline",
                  "--baseline", alt, "--json", str(report))
    assert r.returncode == 0, r.stdout + r.stderr
    assert os.path.exists(alt)
    # --json alongside --update-baseline still writes the report, showing
    # what was just accepted relative to the pre-update baseline
    assert json.load(open(report))["all"]
    r = _run_lint("--templates", str(seeded), "--baseline", alt)
    assert r.returncode == 0, r.stdout + r.stderr


# ---------------------------------------------------------------------------
# Pallas kernels: lint rule
# ---------------------------------------------------------------------------


def test_jax_lint_host_read_in_pallas(tmp_path):
    """Both directions of the host-read-in-pallas rule: host reads,
    engine sync entry points, a one-level-down syncing helper and an
    obs.span inside a pallas_call kernel body are errors; the same
    calls outside any kernel body (or a clean body) are not."""
    fs = lint_snippet(tmp_path, """
        import jax
        from jax.experimental import pallas as pl
        from nds_tpu.engine import ops
        from nds_tpu.obs import trace as obs

        def _helper(x):
            return ops.count_int(x.nrows)

        def make(x):
            def kernel(in_ref, out_ref):
                with obs.span("inner"):
                    pass
                ops.host_read("tag", lambda: 1)
                in_ref.to_int()
                _helper(in_ref)
                out_ref[:] = in_ref[:]
            return pl.pallas_call(kernel, out_shape=None)(x)
    """, rel="nds_tpu/engine/other.py")
    rules = [f.rule for f in fs]
    assert rules == ["host-read-in-pallas"] * 4, fs
    assert all(f.severity == "error" for f in fs)
    # clean kernel body + syncs OUTSIDE the body: no findings (the rule
    # must not leak past the pallas_call'd function)
    fs = lint_snippet(tmp_path, """
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from nds_tpu.engine import ops

        def make(x):
            def kernel(in_ref, out_ref):
                out_ref[:] = in_ref[:] * 2
            got = pl.pallas_call(kernel, out_shape=None)(x)
            n = ops.count_int(4)          # outside: legal
            return got, n
    """, rel="nds_tpu/engine/other.py")
    assert not [f for f in fs if f.rule == "host-read-in-pallas"], fs


def test_jax_lint_pallas_rule_baseline_untouched():
    """The shipped kernel bodies (engine/kernels.py) must be clean under
    the new rule — the baseline gains nothing."""
    from nds_tpu.analysis.jax_lint import lint_file
    path = os.path.join(REPO, "nds_tpu", "engine", "kernels.py")
    fs = lint_file(path, "nds_tpu/engine/kernels.py")
    assert not [f for f in fs if f.rule == "host-read-in-pallas"], fs


def test_jax_lint_host_sync_in_prefetch_worker(tmp_path):
    """Both directions of the host-sync-in-prefetch-worker rule: host
    reads, engine sync entry points, a one-level-down syncing helper
    and an obs.span inside a callable handed to the prefetch ring
    (positional or prepare=, bare name or self.method) are errors; the
    same calls outside any ring callable (or a clean prepare) are
    not."""
    fs = lint_snippet(tmp_path, """
        from nds_tpu.engine import ops
        from nds_tpu.engine.prefetch import chunk_ring
        from nds_tpu.obs import trace as obs

        def _helper(x):
            return ops.count_int(x.nrows)

        def _prepare(chunk):
            with obs.span("inner"):
                pass
            ops.host_read("tag", lambda: 1)
            n = chunk.nrows.to_int()
            _helper(chunk)
            return chunk

        def drive(chunks):
            ring = chunk_ring(chunks, prepare=_prepare)
            return ring
    """, rel="nds_tpu/engine/other.py")
    rules = [f.rule for f in fs]
    assert rules == ["host-sync-in-prefetch-worker"] * 4, fs
    assert all(f.severity == "error" for f in fs)
    # self.method spelling + constructor form resolve too
    fs = lint_snippet(tmp_path, """
        from nds_tpu.engine import ops
        from nds_tpu.engine.prefetch import ChunkRing

        class Pipe:
            def _prep(self, chunk):
                return ops.resolve_counts()

            def run(self, chunks):
                return ChunkRing(chunks, self._prep, depth=2)
    """, rel="nds_tpu/engine/other.py")
    assert [f.rule for f in fs] == ["host-sync-in-prefetch-worker"], fs
    # the SOURCE iterator's generator body runs on the worker too: a
    # call expression passed as the source resolves by its callee name
    fs = lint_snippet(tmp_path, """
        from nds_tpu.engine import ops
        from nds_tpu.engine.prefetch import chunk_ring

        class Scan:
            def device_chunks(self, planner):
                for c in self.chunks:
                    ops.host_sync(c.nrows)
                    yield c

            def drive(self, planner):
                return chunk_ring(self.device_chunks(planner))
    """, rel="nds_tpu/engine/other.py")
    assert [f.rule for f in fs
            if f.rule == "host-sync-in-prefetch-worker"] == \
        ["host-sync-in-prefetch-worker"], fs
    # clean prepare + syncs OUTSIDE the ring callable: no findings
    fs = lint_snippet(tmp_path, """
        from nds_tpu.engine import ops
        from nds_tpu.engine.prefetch import chunk_ring

        def _prepare(chunk):
            return tuple(chunk.columns.values())

        def drive(chunks):
            ring = chunk_ring(chunks, prepare=_prepare)
            n = ops.count_int(4)          # outside: legal
            return ring, n
    """, rel="nds_tpu/engine/other.py")
    assert not [f for f in fs
                if f.rule == "host-sync-in-prefetch-worker"], fs


def test_jax_lint_prefetch_rule_baseline_untouched():
    """The shipped ring callables (engine/stream.py's prepare methods,
    engine/prefetch.py itself, the planner's eager-loop ring) must be
    clean under the new rule — the baseline gains nothing."""
    from nds_tpu.analysis.jax_lint import lint_file
    for rel in ("nds_tpu/engine/stream.py", "nds_tpu/engine/prefetch.py",
                "nds_tpu/sql/planner.py"):
        fs = lint_file(os.path.join(REPO, *rel.split("/")), rel)
        assert not [f for f in fs
                    if f.rule == "host-sync-in-prefetch-worker"], (rel, fs)


def test_num_audit_threshold_math():
    """Exact rational -> integer threshold mapping of an ordered compare
    (boundaries, non-integral equalities) and the date parse the rebase
    proofs read literals through."""
    from fractions import Fraction

    from nds_tpu.analysis.num_audit import parse_days, value_cmp
    F = Fraction
    assert value_cmp("<", F(11, 2)) == ("ile", 5)    # v < 5.5 -> v <= 5
    assert value_cmp("<=", F(11, 2)) == ("ile", 5)
    assert value_cmp(">", F(11, 2)) == ("ige", 6)
    assert value_cmp(">=", F(11, 2)) == ("ige", 6)
    assert value_cmp("<", F(5)) == ("ile", 4)        # v < 5 -> v <= 4
    assert value_cmp("=", F(11, 2)) == ("false",)
    assert value_cmp("<>", F(11, 2)) == ("true",)
    assert value_cmp("=", F(7)) == ("ieq", 7)
    with pytest.raises(ValueError):
        value_cmp("like", F(1))
    assert parse_days("1970-01-01") == 0
    assert parse_days("2000-03-01") == 11017
    assert parse_days("1969-12-31") == -1
    assert parse_days("not a date") is None


def test_lint_changed_covers_kernels():
    """tools/lint.py --changed: an edit to engine/kernels.py (and the
    other explicitly named roots) must rerun the corpus passes."""
    import importlib.util
    path = os.path.join(REPO, "tools", "lint.py")
    spec = importlib.util.spec_from_file_location("lint_tool_k", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for p in ("nds_tpu/engine/kernels.py",
              # async ingest data plane: the prefetch ring (admission
              # pricing + worker lint contract) and the persistent
              # chunk store (the streamed wire format) rerun the
              # corpus passes on edit
              "nds_tpu/engine/prefetch.py",
              "nds_tpu/io/chunk_store.py",
              # fault-tolerance layer: seam/classification edits move
              # exec_audit's retry-paths row and the swallowed-fault
              # contract
              "nds_tpu/engine/faults.py",
              # campaign driver: its arm-failure handling is a client
              # of the swallowed-fault contract and its fingerprint
              # stamp is the provenance every ledger record keys on
              "nds_tpu/obs/campaign.py",
              # numeric-safety layer: the value-range interpreter and
              # the saturating encoded-compare rebase it models
              "nds_tpu/analysis/num_audit.py",
              "nds_tpu/engine/exprs.py",
              # parameterization layer: the literal-bindability prover
              # whose shared rule the stream dispatcher imports
              "nds_tpu/analysis/param_audit.py"):
        assert p.startswith(mod._CORPUS_ROOTS), \
            f"{p} not covered by _CORPUS_ROOTS"


# ---------------------------------------------------------------------------
# concurrency audit: shared-state classification + lock discipline
# ---------------------------------------------------------------------------


def conc_audit_tree(tmp_path, files, registry=None, entry_points=None):
    """Audit a throwaway package: ``files`` maps name -> source. Default
    entry points make EVERY function a concurrent root (severity error),
    matching how snippet rules are asserted."""
    import shutil
    from nds_tpu.analysis.conc_audit import audit_package
    pkg = tmp_path / "pkg"
    shutil.rmtree(pkg, ignore_errors=True)   # fresh tree per call
    pkg.mkdir()
    for name, code in files.items():
        (pkg / name).write_text(textwrap.dedent(code))
    return audit_package(str(pkg), repo=str(tmp_path),
                         registry=registry if registry is not None else {},
                         entry_points=entry_points or (("", ""),))


def test_conc_audit_accepted_state_classes(tmp_path):
    """Thread-local stores, bounded-ring appends, atomic latch rebinds,
    lock-guarded (consistently) mutations and import-time construction
    are the ACCEPTED classes — none may produce a finding."""
    fs = conc_audit_tree(tmp_path, {"mod.py": """
        import threading
        from collections import deque

        _CACHE: dict = {}
        _LOCK = threading.Lock()
        _tls = threading.local()
        RING = deque(maxlen=10)
        FLAG = False
        IMPORT_BUILT = {}
        IMPORT_BUILT["x"] = 1            # import scope: serialized

        def guarded(k, v):
            with _LOCK:
                if len(_CACHE) >= 8:
                    _CACHE.pop(next(iter(_CACHE)))
                _CACHE[k] = v

        def tls_write():
            _tls.ring = []

        def ring_write(x):
            RING.append(x)

        def latch():
            global FLAG
            FLAG = True
    """})
    assert not [f for f in fs if f.rule != "cache-unregistered"], fs


def test_conc_audit_unguarded_and_rmw(tmp_path):
    """A bare container mutation and an augmented (read-modify-write)
    rebind of a module global are findings; severity is error because
    the snippet entry points make everything concurrently reachable."""
    fs = conc_audit_tree(tmp_path, {"mod.py": """
        _STATE: dict = {}
        COUNT = 0

        def unguarded(k, v):
            _STATE[k] = v

        def rmw():
            global COUNT
            COUNT += 1
    """})
    rules = sorted(f.rule for f in fs)
    assert rules == ["unguarded-mutation", "unguarded-mutation"], fs
    assert all(f.severity == "error" for f in fs)


def test_conc_audit_mixed_guard(tmp_path):
    """State mutated under its lock at one site and off-lock at another:
    the off-lock site is flagged (the lock protects nothing)."""
    fs = conc_audit_tree(tmp_path, {"mod.py": """
        import threading
        _CACHE: dict = {}
        _LOCK = threading.Lock()

        def guarded(k, v):
            with _LOCK:
                _CACHE[k] = v

        def sneaky(k, v):
            _CACHE[k] = v
    """})
    assert [f.rule for f in fs if f.rule == "mixed-guard"], fs
    hit = next(f for f in fs if f.rule == "mixed-guard")
    assert hit.query == "sneaky"


def test_conc_audit_sync_compile_wait_under_lock(tmp_path):
    """host_read-family calls, jax.jit compiles and blocking waits held
    under a lock are errors — directly and one level down into a
    module-local helper."""
    fs = conc_audit_tree(tmp_path, {"mod.py": """
        import threading
        import jax
        from nds_tpu.engine import ops
        _LOCK = threading.Lock()

        def _helper(x):
            return ops.count_int(x)

        def bad(x, f, ev):
            with _LOCK:
                n = x.item()
                g = jax.jit(f)
                ev.wait()
                m = _helper(x)
            return n, g, m

        def good(x, f):
            n = x.item()                 # off-lock: fine
            g = jax.jit(f)
            with _LOCK:
                pass
            return n, g
    """})
    rules = sorted(f.rule for f in fs)
    assert rules == ["compile-under-lock", "sync-under-lock",
                     "sync-under-lock", "wait-under-lock"], fs
    assert all(f.query == "bad" for f in fs)


def test_conc_audit_lock_order_cycle(tmp_path):
    """Opposite-order nested acquisition across functions is a deadlock
    finding; one consistent global order is clean."""
    fs = conc_audit_tree(tmp_path, {"mod.py": """
        import threading
        _A = threading.Lock()
        _B = threading.Lock()

        def ab():
            with _A:
                with _B:
                    pass

        def ba():
            with _B:
                with _A:
                    pass
    """})
    assert [f for f in fs if f.rule == "lock-order-cycle"], fs
    fs = conc_audit_tree(tmp_path, {"mod2.py": """
        import threading
        _A = threading.Lock()
        _B = threading.Lock()

        def ab():
            with _A:
                with _B:
                    pass

        def ab2():
            with _A:
                with _B:
                    pass
    """})
    assert not [f for f in fs if f.rule == "lock-order-cycle"], fs


def test_conc_audit_param_alias(tmp_path):
    """A module cache passed as a plain parameter: mutations inside the
    callee count against the module global with the CALLEE's guard —
    guarded helper clean, unguarded helper flagged (the _identity_cache
    pattern)."""
    guarded = {"mod.py": """
        import threading
        _RANK_CACHE: dict = {}
        _LOCK = threading.Lock()

        def memo(cache, key, value):
            with _LOCK:
                cache[key] = value

        def use(key, value):
            return memo(_RANK_CACHE, key, value)
    """}
    fs = conc_audit_tree(tmp_path, guarded)
    assert not [f for f in fs
                if f.rule in ("unguarded-mutation", "mixed-guard")], fs
    bad = {"mod2.py": """
        _RANK_CACHE: dict = {}

        def memo(cache, key, value):
            cache[key] = value

        def use(key, value):
            return memo(_RANK_CACHE, key, value)
    """}
    fs = conc_audit_tree(tmp_path, bad)
    hits = [f for f in fs if f.rule == "unguarded-mutation"]
    assert hits and "_RANK_CACHE" in hits[0].message, fs


def test_conc_audit_cache_key_completeness(tmp_path):
    """A registered cache whose value-builder reads an env knob the key
    expression never sees is an error; adding the knob to the key (or
    an explicit justified exemption) clears it."""
    from nds_tpu.analysis.conc_audit import CacheSpec
    missing = {"keyed.py": """
        import os
        import threading
        _STEP_CACHE: dict = {}
        _LOCK = threading.Lock()

        def knob():
            return int(os.environ.get("MY_KNOB", "4"))

        def build(n):
            return n * knob()

        def make_key(n):
            return (n,)

        def lookup(n):
            k = make_key(n)
            got = _STEP_CACHE.get(k)
            if got is None:
                built = build(n)
                with _LOCK:
                    got = _STEP_CACHE.setdefault(k, built)
            return got
    """}
    reg = {("pkg/keyed.py", "_STEP_CACHE"): CacheSpec(
        key_fns=("make_key",), builder_fns=("build",),
        modules=("pkg/keyed.py",))}
    fs = conc_audit_tree(tmp_path, missing, registry=reg)
    hits = [f for f in fs if f.rule == "cache-key-missing-knob"]
    assert hits and "MY_KNOB" in hits[0].message, fs
    # knob joins the key expression -> clean
    complete = dict(missing)
    complete["keyed.py"] = missing["keyed.py"].replace(
        "return (n,)", "return (n, knob())")
    fs = conc_audit_tree(tmp_path, complete, registry=reg)
    assert not [f for f in fs if f.rule == "cache-key-missing-knob"], fs
    # ... or an exemption WITH a justification
    reg_ex = {("pkg/keyed.py", "_STEP_CACHE"): CacheSpec(
        key_fns=("make_key",), builder_fns=("build",),
        modules=("pkg/keyed.py",),
        exempt={"MY_KNOB": "fixture: declared stale-safe"})}
    fs = conc_audit_tree(tmp_path, missing, registry=reg_ex)
    assert not [f for f in fs if f.rule == "cache-key-missing-knob"], fs


def test_conc_audit_cache_unregistered(tmp_path):
    """A keyed, query-path-written *_CACHE dict that no CACHE_REGISTRY
    entry declares prompts registration (warning)."""
    fs = conc_audit_tree(tmp_path, {"mod.py": """
        import threading
        _NEW_CACHE: dict = {}
        _LOCK = threading.Lock()

        def put(k, v):
            with _LOCK:
                _NEW_CACHE[k] = v
    """})
    assert [f for f in fs if f.rule == "cache-unregistered"], fs


def test_conc_audit_env_freeze_and_suppression(tmp_path):
    """A module-level os.environ snapshot is flagged; the documented
    in-source suppression (the _MIN_BUCKET process contract) waives it."""
    fs = conc_audit_tree(tmp_path, {"mod.py": """
        import os
        FROZEN = int(os.environ.get("SOME_KNOB", "1"))
    """})
    assert [f.rule for f in fs] == ["env-freeze"], fs
    fs = conc_audit_tree(tmp_path, {"mod2.py": """
        import os
        # nds-lint: ignore[env-freeze]
        FROZEN = int(os.environ.get("SOME_KNOB", "1"))
    """})
    assert not fs, fs


def test_conc_audit_current_tree_clean():
    """The shipped package must pass its own concurrency audit with ZERO
    findings — the acceptance bar: no accepted unguarded-mutation
    findings on the query path, every cache registered and key-complete,
    the deliberate freezes suppressed in-source."""
    from nds_tpu.analysis.conc_audit import audit_concurrency
    fs = audit_concurrency()
    assert not fs, "\n".join(str(f) for f in fs)


def test_conc_audit_differential_harness():
    """The runtime half of the concurrency contract, both directions:
    the threaded stress differential (bit-for-bit rows, exactly-one-
    compile-per-shape, zero cross-thread bleed, lock-liveness probes)
    must pass on the clean tree, and no-op'ing EACH named lock must make
    its probe fail — a gate that cannot fail proves nothing."""
    import importlib.util
    path = os.path.join(REPO, "tools", "conc_audit_diff.py")
    spec = importlib.util.spec_from_file_location("conc_audit_diff", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    ok, lines = mod.run_diff()
    assert ok, "\n".join(lines)
    caught, drift_lines = mod.run_drift()
    assert caught, "\n".join(drift_lines)
    assert sum("ok drift" in ln for ln in drift_lines) == \
        len(mod._named_locks())


def test_lint_jobs_thread_pool_matches_sequential():
    """--jobs N runs the nine passes in a thread pool with identical
    findings/counts — the analysis layer passing its own audit, live."""
    import importlib.util
    path = os.path.join(REPO, "tools", "lint.py")
    spec = importlib.util.spec_from_file_location("lint_tool_j", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    f1, c1, _r1, _m1, _p1, _n1, _pp1, _e1 = mod.run_passes(jobs=1)
    f6, c6, _r6, _m6, _p6, _n6, _pp6, _e6 = mod.run_passes(jobs=6)
    assert c1 == c6
    assert [str(f) for f in f1] == [str(f) for f in f6]
    assert "conc-audit" in c1
    assert "perf-audit" in c1
    assert "num-audit" in c1
    assert "param-audit" in c1


# ---------------------------------------------------------------------------
# numeric-safety audit: value-range/precision proofs + boundary lockstep
# ---------------------------------------------------------------------------


def test_num_ival_abstraction():
    """The interval/scale/mass lattice the proofs run on: scaled decimal
    endpoints, additive mass under union, exact x10^d rescaling, and the
    codec width rules at their edges."""
    from nds_tpu.analysis.num_audit import (FOR16_SPAN, FOR32_SPAN, IVal,
                                            codec_width_verdict,
                                            column_interval)
    iv = column_interval("ss_ext_sales_price", "decimal(7,2)", {})
    assert (iv.lo, iv.hi, iv.scale) == (-(10 ** 7 - 1), 10 ** 7 - 1, 2)
    a = IVal(-3, 5, mass=10)
    b = IVal(0, 9, mass=7)
    u = a.union(b)
    assert (u.lo, u.hi, u.mass) == (-3, 9, 17)
    r = IVal(-25, 50, scale=1).at_scale(3)
    assert (r.lo, r.hi, r.scale) == (-2500, 5000, 3)
    # width rules at the exact spans the codec refuses past
    assert codec_width_verdict(IVal(0, FOR16_SPAN - 1), 8)[0] == 2
    assert codec_width_verdict(IVal(0, FOR16_SPAN), 8)[0] == 4
    assert codec_width_verdict(IVal(0, FOR32_SPAN - 1), 8)[0] == 4
    assert codec_width_verdict(IVal(0, FOR32_SPAN), 8) is None
    assert codec_width_verdict(None, 8) is None


def test_num_audit_corpus_proves_clean():
    """Every corpus statement's numeric proofs land host-only with ZERO
    findings — no codec overflow, no unprovable accumulator, no hash-bit
    spill — and the claim checks hold: the shipped tree's numeric story
    is fully proven, so the baseline carries nothing."""
    import time
    from nds_tpu.analysis.num_audit import (audit_num_corpus, check_counts,
                                            claim_findings,
                                            reports_to_findings)
    t0 = time.time()
    reports = audit_num_corpus()
    elapsed = time.time() - t0
    assert len(reports) == 103
    assert reports_to_findings(reports) == []
    assert claim_findings() == []
    assert elapsed < 60, f"host-only audit took {elapsed:.1f}s"
    # the proof histogram is a tier-1 contract, pinned like the perf
    # bottleneck counts: a rule change that silently drops checks (or
    # un-proves one) must fail loudly — update ONLY together with the
    # matching engine/model change (the lockstep rule)
    assert check_counts(reports) == {
        "agg": (287, 287), "arith": (61, 61), "codec": (237, 237),
        "hash-bits": (150, 150), "rebase": (35, 35), "scale": (24, 24)}
    assert sum(1 for r in reports if r.proven_safe) == 96


def test_num_audit_scale_lockstep():
    """MAX_DEC_SCALE mirrors the engine's decimal-scale ceiling so a
    widened runtime scale cannot outrun the static proofs silently."""
    from nds_tpu.analysis.num_audit import MAX_DEC_SCALE
    from nds_tpu.engine import exprs
    assert MAX_DEC_SCALE == exprs._MAX_DEC_SCALE


def _load_num_diff(name="num_audit_diff_t"):
    path = os.path.join(REPO, "tools", "num_audit_diff.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_num_audit_differential_harness():
    """The boundary-value lockstep: every arm of the sweep (base,
    sharded, encoded-off) returns bit-identical rows to
    the plain-width eager reference over the adversarial tables (FOR
    spans at the int16 edge over 10^9 / negative bases, full dict code
    space, decimal(7,2) extremes, a hot-hash key), and the static
    verdicts agree exactly with the runtime overflow-flag evidence."""
    import numpy as np
    mod = _load_num_diff()
    tables = mod._boundary_tables(np.random.default_rng(1729))
    expect = mod.reference(tables)
    arms = [mod.run_arm(name, env_kv, tables)
            for name, env_kv in mod._ARMS if name != "sharded"]
    import jax
    if jax.device_count() >= 2:
        arms.append(mod.run_arm("sharded",
                                {"NDS_TPU_STREAM_SHARDS": "2"}, tables))
    reports = mod.static_verdicts(
        {k: t.num_rows for k, t in tables.items()})
    ok, lines = mod.compare(expect, arms, reports, arms[0])
    assert ok, "\n".join(lines)
    assert all(r.proven for r in reports)
    # direction A of the drift contract: an explicit accumulator
    # ceiling forces the runtime overflow rerun, contradicting the
    # (still proven) static verdicts — the harness must flag it
    with mod._env(NDS_TPU_STREAM_ACC_ROWS="1024"):
        over = mod.run_arm("base+acc-ceiling", {}, tables)
    ok_a, lines_a = mod.compare(expect, [over], reports, over)
    assert not ok_a, "runtime overflow drift fixture failed to fail"
    assert any("overflow rerun" in ln for ln in lines_a)
    # direction B: widened static ranges (row bounds x10^9) un-prove
    # the accumulator checks against a clean runtime — flagged too
    drift = mod.static_verdicts(
        {k: t.num_rows for k, t in tables.items()}, inflate=10 ** 9)
    ok_b, lines_b = mod.compare(expect, [arms[0]], drift, arms[0])
    assert not ok_b, "widened-range drift fixture failed to fail"
    assert any("statically unproven" in ln for ln in lines_b)


# ---------------------------------------------------------------------------
# parameterization audit: literal bindability + one-compile-many-params
# ---------------------------------------------------------------------------


def test_param_literal_rule():
    """The shared bindability vocabulary: type tags, safe domains and
    the operand conversion the stream dispatcher feeds jnp.asarray."""
    from decimal import Decimal

    from nds_tpu.analysis.param_audit import (SAFE_INT_ABS, domain_contains,
                                              literal_typetag,
                                              slot_param_value)
    assert literal_typetag(42) == "i64"
    assert literal_typetag(1.5) == "f64"
    assert literal_typetag(Decimal("99.99")) == "dec:2"
    assert literal_typetag(Decimal("7")) == "dec:0"
    # None / bool / str never bind (codec selection, plan-time parses)
    for v in (None, True, "GA"):
        assert literal_typetag(v) is None
    # i64: inside the rebase margin, not at it
    assert domain_contains("i64", SAFE_INT_ABS - 1)
    assert not domain_contains("i64", SAFE_INT_ABS + 1)
    # dec:s domains live in LITERAL units; operands in scaled ints
    assert domain_contains("dec:2", Decimal("99999.99"))
    assert slot_param_value(Decimal("99999.99"), "dec:2") == 9999999
    assert slot_param_value(5, "i64") == 5
    # f64 binds at any finite value (no codec or rebase interaction)
    assert domain_contains("f64", 1e300)


def test_param_audit_statement_classification():
    """One statement, every verdict family: direct streamed comparands
    bind; dimension-owned, in-list, subquery and LIMIT literals fold
    with machine-readable reasons."""
    from nds_tpu.analysis.param_audit import ParamAuditor
    a = ParamAuditor()
    rep = a.audit_sql("""
        select ss_item_sk, count(*) c from store_sales, date_dim
        where ss_sold_date_sk = d_date_sk
          and ss_quantity between 5 and 95
          and ss_ext_sales_price > 100.00
          and d_moy = 11
          and ss_item_sk in (1, 2, 3)
          and ss_wholesale_cost > (select avg(ss_wholesale_cost)
                                   from store_sales)
        group by ss_item_sk order by ss_item_sk limit 10""")
    assert rep.classification == "compiled-stream"
    # between low/high + the decimal compare = three bindable slots
    assert rep.n_bindable == 3
    assert rep.signature() == ("ss_quantity:i64, ss_quantity:i64, "
                               "ss_ext_sales_price:dec:2")
    assert all(s.domain for s in rep.slots)
    # 3 in-list members + LIMIT shape the output; d_moy is dimension-
    # owned (its compare replays against a host-gathered dimension)
    assert rep.folds == {"shape-affecting": 4, "replayed-host-read": 1}
    # every literal is accounted for: bound or folded, none dropped
    assert sum(rep.folds.values()) + rep.n_bindable == rep.n_literals


def test_param_skeleton_key_canonicalization():
    """The cache-key half of the contract: swapping a bindable literal's
    VALUE leaves the skeleton key unchanged (one compile serves all
    vectors), while changing its decimal SCALE — a different codec
    layout — changes it."""
    from nds_tpu.analysis.exec_audit import _conjuncts_of
    from nds_tpu.analysis.param_audit import (conjunct_bind_slots,
                                              skeleton_conjunct_key)
    from nds_tpu.sql.parser import parse

    def conj(sql):
        q = parse(sql).body
        return _conjuncts_of(q.where)[0]

    def skel(sql):
        c = conj(sql)
        slots = conjunct_bind_slots(c, owned=True, has_subquery=False)
        assert slots, sql
        return skeleton_conjunct_key(c, [(p, n, t) for p, n, t in slots])

    base = "select 1 from store_sales where ss_ext_sales_price > {}"
    assert skel(base.format("100.00")) == skel(base.format("9999.99"))
    assert skel(base.format("100.00")) != skel(base.format("100.0"))
    # the swap restores the literal value afterwards
    c = conj(base.format("100.00"))
    skeleton_conjunct_key(
        c, [(p, n, t) for p, n, t in
            conjunct_bind_slots(c, owned=True, has_subquery=False)])
    from decimal import Decimal
    assert c.right.value == Decimal("100.00")


def test_param_binding_hook_roundtrip():
    """The engine half: exprs.param_binding overlays a Literal node's
    value as a broadcast device column inside the scope and stands down
    outside it (the planner consults bound_literal before X.literal)."""
    from nds_tpu.engine import exprs as X
    from nds_tpu.sql.parser import parse
    q = parse("select 1 from store_sales where ss_quantity > 5").body
    lit = q.where.right
    assert X.bound_literal(lit, 4) is None
    assert not X.param_bindings_active()
    with X.param_binding({id(lit): ("i64", 37)}):
        assert X.param_bindings_active()
        col = X.bound_literal(lit, 4)
        assert col is not None and int(col.data[0]) == 37
        assert col.data.shape == (4,)
    assert X.bound_literal(lit, 4) is None


def test_param_audit_corpus_counts_pinned():
    """The corpus bindability census is a tier-1 contract, pinned like
    the perf bottleneck and num proof histograms: a rule change that
    silently binds more (unsound) or fewer (lost coverage) literals
    must fail loudly. Update ONLY together with the matching engine
    change — the lockstep rule."""
    import time

    from nds_tpu.analysis.param_audit import (audit_param_corpus,
                                              bindability_counts,
                                              reports_to_findings)
    t0 = time.time()
    reports = audit_param_corpus()
    elapsed = time.time() - t0
    assert len(reports) == 103
    assert reports_to_findings(reports) == []
    assert elapsed < 60, f"host-only audit took {elapsed:.1f}s"
    assert bindability_counts(reports) == {
        "bindable": 63,
        "codec-threshold": 267,
        "date-parse-at-plan": 23,
        "non-comparand": 315,
        "non-streamed-statement": 714,
        "replayed-host-read": 599,
        "residual-key": 13,
        "shape-affecting": 86,
        "statements-with-bindable": 7,
    }
    # every bindable slot the pinned-seed instantiation produced sits
    # inside its proven safe domain with a live signature
    for r in reports:
        for s in r.slots:
            assert s.typetag in ("i64", "f64") or \
                s.typetag.startswith("dec:")
        if r.n_bindable:
            assert r.signature()


def test_param_generator_dials_inside_safe_domains():
    """Satellite lockstep with the stream generator: every numeric dial
    range a template defines (uniform/sample bounds — what
    nds_gen_query_stream substitutes per stream) sits inside the proven
    safe i64 domain, and instantiations under OTHER seeds than the
    audit's pinned one keep every bindable slot value in-domain."""
    import re

    import numpy as np

    from nds_tpu.analysis.param_audit import (SAFE_INT_ABS, ParamAuditor,
                                              domain_contains)
    from nds_tpu.queries import (_DEFINE_RE, instantiate_template,
                                 list_templates, load_template)
    call = re.compile(r"^(\w+)\((.*)\)$", re.DOTALL)
    n_dials = 0
    for name in list_templates():
        for m in _DEFINE_RE.finditer(load_template(name)):
            c = call.match(m.group(2).strip())
            if not c or c.group(1) not in ("uniform", "sample"):
                continue
            args = [a.strip() for a in c.group(2).split(",")]
            bounds = args[-2:] if c.group(1) == "sample" else args
            for tok in bounds:
                if re.fullmatch(r"-?\d+", tok):
                    assert abs(int(tok)) < SAFE_INT_ABS, \
                        f"{name}: dial bound {tok} escapes the domain"
                    n_dials += 1
    assert n_dials >= 20, "the dial scan went dark"
    auditor = ParamAuditor()
    for seed in (7, 4242):
        rng = np.random.default_rng(seed)
        for name in list_templates():
            sql = instantiate_template(load_template(name), rng)
            for stmt in (s for s in sql.split(";") if s.strip()):
                rep = auditor.audit_sql(stmt, file=name, query=name)
                for s in rep.slots:
                    assert s.value is None or \
                        domain_contains(s.typetag, s.value), \
                        (name, s.column, s.value)


def test_lint_cli_param_report(lint_combined):
    """--param-report plumbing under --format json: the ``param_report``
    field rides the SAME single parseable stdout document and the human
    signature table rides stderr (one subprocess covers both — the
    plain-stdout rendering is the same format_param_report text)."""
    r, doc, _path = lint_combined         # single-document stdout
    assert doc["pass_counts"]["param-audit"] == 0
    entries = doc["param_report"]
    assert len(entries) == 103
    assert sum(1 for e in entries if e["slots"]) == 7
    for e in entries:
        for s in e["slots"]:
            assert s["typetag"] in ("i64", "f64") or \
                s["typetag"].startswith("dec:")
    # the human signature table rides stderr, off the parseable stream
    assert "# param-audit: literal bindability" in r.stderr
    assert "ss_quantity:i64" in r.stderr
    assert "bindable: 63" in r.stderr


def test_param_audit_differential_harness():
    """The one-compile-many-params lockstep, live: K=4 boundary
    parameter vectors per bindable template share ONE compiled pipeline
    (singleflight build counters + cache hit/miss metrics) bit-for-bit
    with per-value fresh recording AND the plain-width eager reference,
    fold-required slots keep changing the cache key, and the static
    signatures match the runtime slot counts — across the base,
    partitioned and (mesh permitting) sharded arms."""
    path = os.path.join(REPO, "tools", "param_audit_diff.py")
    spec = importlib.util.spec_from_file_location("param_audit_diff_t",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    ok, lines = mod.run_diff(inject_drift=False)
    assert ok, "\n".join(lines)
    assert any("ONE compile served 4 parameter vectors" in ln
               for ln in lines)
    assert any("fold-required slots changed the key" in ln
               for ln in lines)
    # the drift self-test: misclassifying IN-list members as bindable
    # must be rejected in BOTH directions (wrong results on cache hit,
    # fold slots no longer varying the key)
    ok_d, lines_d = mod.run_diff(inject_drift=True)
    assert ok_d, "\n".join(lines_d)
    assert any("correctly rejected" in ln for ln in lines_d)
