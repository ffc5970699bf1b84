# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""The compile meter (``ops.enable_compile_meter`` + ``nds_tpu/obs/compiles.py``):
one record a program build, told apart as a persistent-cache miss, hit or
neither, as a ``compile`` span under the span that asked for the program
and as a row of the process's table; ``ops.compile_ns()`` keeps its meaning.

Every build here is of a function this file defines under a name no other
test uses, so the table's rows are read by name and other tests' builds in
the same process never matter. A second build of one program is a NEW
function object of the same name and body (no ``jax.clear_caches()``: the
worker's other tests keep their programs)."""

import importlib.util
import json
import os
import sys
import threading

import jax
import jax.numpy as jnp
import pytest

from nds_tpu.engine import ops
from nds_tpu.obs import compiles, evidence
from nds_tpu.obs import export as obs_export
from nds_tpu.obs import trace as obs_trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PARTS = ("backendMs", "readMs", "traceMs", "lowerMs")


def program(name, factor=3):
    """A fresh jitted function called ``name``: its program is
    ``jit(<name>)``."""
    def body(x):
        return jnp.cumsum(x * factor + 1)
    body.__name__ = body.__qualname__ = name
    return jax.jit(body)


def row_of(name):
    rows = [r for r in compiles.table() if r["program"] == f"jit({name})"]
    return rows[0] if rows else None


def compile_spans(records, name=None):
    return [r for r in records if isinstance(r, obs_trace.SpanRecord)
            and r.name == "compile"
            and (name is None or r.attrs["program"] == f"jit({name})")]


@pytest.fixture
def meter():
    ops.enable_compile_meter()
    was = obs_trace.on()
    obs_trace.set_enabled(True)
    obs_trace.attach()
    obs_trace.drain_spans()
    yield
    obs_trace.set_enabled(was)
    obs_trace.drain_spans()


@pytest.fixture
def disk_cache(tmp_path):
    """JAX's persistent compilation cache in a directory of the test's
    own, every program written; the process's settings put back after."""
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "xla"))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    yield
    for k, v in was.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


@pytest.fixture
def no_disk_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


X = jnp.arange(48.0)


def test_a_fresh_program_is_one_record_of_a_miss(meter, disk_cache):
    program("cm_miss_probe")(X)
    (span,) = compile_spans(obs_trace.drain_spans(), "cm_miss_probe")
    a = span.attrs
    assert a["cache"] == "miss"
    assert a["backendMs"] > 0 and a["readMs"] == 0
    assert a["traceMs"] > 0 and a["lowerMs"] > 0
    row = row_of("cm_miss_probe")
    assert (row["builds"], row["hits"], row["misses"]) == (1, 0, 1)
    # the span covers trace through backend, so no part is longer than it
    assert span.dur_ns / 1e6 >= a["backendMs"] + a["lowerMs"]


def test_the_programs_second_build_is_a_hit_with_no_backend_time(
        meter, disk_cache):
    program("cm_hit_probe")(X)
    obs_trace.drain_spans()
    program("cm_hit_probe")(X)        # a new function object: built again
    (span,) = compile_spans(obs_trace.drain_spans(), "cm_hit_probe")
    a = span.attrs
    assert a["cache"] == "hit"
    assert a["readMs"] > 0 and a["backendMs"] == 0
    assert a["traceMs"] > 0 and a["lowerMs"] > 0
    row = row_of("cm_hit_probe")
    assert (row["builds"], row["hits"], row["misses"]) == (2, 1, 1)
    assert row["readMs"] == pytest.approx(a["readMs"], abs=1e-3)


def test_without_the_persistent_cache_a_build_is_off(meter, no_disk_cache):
    program("cm_off_probe")(X)
    (span,) = compile_spans(obs_trace.drain_spans(), "cm_off_probe")
    assert span.attrs["cache"] == "off"
    assert span.attrs["backendMs"] > 0 and span.attrs["readMs"] == 0
    row = row_of("cm_off_probe")
    assert (row["builds"], row["hits"], row["misses"]) == (1, 0, 0)


def test_compile_ns_is_the_tables_backend_plus_read(meter, disk_cache):
    """``ops.compile_ns()`` means what it meant: the closing events'
    durations summed, which the table splits into compiles and reads."""
    t0, c0 = compiles.totals(), ops.compile_ns()
    program("cm_sum_probe")(X)
    program("cm_sum_probe")(X)
    program("cm_sum_probe", factor=5)(X)
    t1, c1 = compiles.totals(), ops.compile_ns()
    assert t1["builds"] - t0["builds"] == 3
    assert t1["hits"] - t0["hits"] == 1
    assert t1["misses"] - t0["misses"] == 2
    table_ns = round(((t1["backendMs"] - t0["backendMs"])
                      + (t1["readMs"] - t0["readMs"])) * 1e6)
    assert c1 - c0 > 0 and table_ns == c1 - c0
    # and the span's own share of the counter is the same split
    spans = compile_spans(obs_trace.drain_spans(), "cm_sum_probe")
    assert sum(s.compile_ns for s in spans) == c1 - c0
    for s in spans:
        assert s.compile_ns == pytest.approx(
            (s.attrs["backendMs"] + s.attrs["readMs"]) * 1e6, abs=1000)


def test_a_compile_is_a_child_of_the_span_that_asked_and_rolls_up(
        meter, disk_cache):
    with obs_trace.op("group_ids") as asked:
        program("cm_child_probe")(X)
        program("cm_child_probe")(X)
    records = obs_trace.drain_spans()
    built = compile_spans(records, "cm_child_probe")
    assert len(built) == 2
    for s in built:
        assert s.parent == asked.sid and s.thread == "driver"
        assert asked.ts_ns <= s.ts_ns
        assert s.ts_ns + s.dur_ns <= asked.ts_ns + asked.dur_ns
    assert built[0].ts_ns + built[0].dur_ns <= built[1].ts_ns
    phases = obs_export.rollup(records)["phases"]
    comp = phases["compile"]
    mine = compile_spans(records)
    assert comp["count"] == len(mine) >= 2
    assert comp["hits"] >= 1 and comp["misses"] >= 1
    for k in PARTS:
        assert comp[k] == pytest.approx(
            sum(s.attrs[k] for s in mine), abs=0.01)
    assert comp["ms"] == pytest.approx(
        sum(s.dur_ns for s in mine) / 1e6, abs=0.01)
    # the parent's self time and self compile share no longer hold them
    asked_phase = phases["op.group_ids"]
    assert asked_phase["selfMs"] == pytest.approx(
        (asked.dur_ns - sum(s.dur_ns for s in mine)) / 1e6, abs=0.01)
    assert asked_phase["compileMs"] == 0.0
    assert comp["compileMs"] == pytest.approx(
        asked.compile_ns / 1e6, abs=0.01)
    # and the Chrome export shows them, attributes and all
    events = [e for e in obs_export.to_chrome(records)["traceEvents"]
              if e["name"] == "compile"]
    assert {e["args"]["cache"] for e in events} >= {"hit", "miss"}
    assert all(e["args"]["parent"] == asked.sid for e in events)


def test_a_compile_outside_any_span_is_a_root(meter, disk_cache):
    program("cm_root_probe")(X)
    (span,) = compile_spans(obs_trace.drain_spans(), "cm_root_probe")
    assert span.parent is None and span.qid is None
    assert obs_export.rollup([span])["phases"]["compile"]["rootMs"] > 0


def test_tracing_off_makes_no_span_and_still_fills_the_table(
        meter, disk_cache):
    obs_trace.set_enabled(False)
    t0 = compiles.thread_sums()
    program("cm_untraced_probe")(X)
    program("cm_untraced_probe")(X)
    obs_trace.set_enabled(True)
    assert not compile_spans(obs_trace.drain_spans())
    row = row_of("cm_untraced_probe")
    assert (row["builds"], row["hits"], row["misses"]) == (2, 1, 1)
    t1 = compiles.thread_sums()
    assert t1["hits"] - t0["hits"] == 1 and t1["misses"] - t0["misses"] == 1
    assert t1["readMs"] > t0["readMs"]
    assert t1["traceMs"] > t0["traceMs"] and t1["lowerMs"] > t0["lowerMs"]
    assert t1["builds"] - t0["builds"] == 2


def test_nested_traces_are_inside_the_outer_one_and_not_added_twice(
        meter, no_disk_cache):
    inner = program("cm_inner_probe")

    @jax.jit
    def cm_outer_probe(x):
        return inner(x) + inner(x * 2)
    cm_outer_probe(X)
    spans = compile_spans(obs_trace.drain_spans())
    assert [s.attrs["program"] for s in spans] == ["jit(cm_outer_probe)"]
    assert row_of("cm_inner_probe") is None      # traced, never built
    a = spans[0].attrs
    # one trace, the outer: what it and the lowering took fits the span
    assert 0 < a["traceMs"] and \
        a["traceMs"] + a["lowerMs"] + a["backendMs"] <= \
        spans[0].dur_ns / 1e6 + 0.01


def test_threads_compiling_at_once_never_mix_their_parts(
        meter, no_disk_cache):
    """More threads than cores, a short switch interval: each thread's
    pending parts and its span ring are its own (a thread's records name
    only the programs it built, each with its own trace and lowering,
    under the span that thread had open), ``compile_ns`` is counted per
    thread, and the shared table loses no update."""
    n_threads, n_programs = (os.cpu_count() or 4) + 4, 2
    out, gate = {}, threading.Barrier(n_threads, timeout=120)

    def build(tag):
        obs_trace.attach()
        obs_trace.drain_spans()
        c0 = ops.compile_ns()
        gate.wait()
        for i in range(n_programs):
            with obs_trace.span(f"asker_{tag}"):
                program(f"cm_thread_{tag}_{i}", factor=i + 2)(X)
        out[tag] = (obs_trace.drain_spans(), ops.compile_ns() - c0)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=build, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
    finally:
        sys.setswitchinterval(interval)
    assert not [t for t in threads if t.is_alive()]
    assert sorted(out) == list(range(n_threads))
    for tag, (records, compile_ns) in out.items():
        spans = compile_spans(records)
        wanted = {f"jit(cm_thread_{tag}_{i})" for i in range(n_programs)}
        assert {s.attrs["program"] for s in spans} >= wanted
        askers = {r.sid for r in records
                  if isinstance(r, obs_trace.SpanRecord)
                  and r.name == f"asker_{tag}"}
        for s in spans:
            if s.attrs["program"] in wanted:
                assert s.attrs["traceMs"] > 0 and s.attrs["lowerMs"] > 0
                assert s.parent in askers
        assert not [s for s in spans if "cm_thread_" in s.attrs["program"]
                    and s.attrs["program"] not in wanted]
        assert sum(s.compile_ns for s in spans) == compile_ns
        for name in wanted:
            (row,) = [r for r in compiles.table() if r["program"] == name]
            assert row["builds"] == 1


def test_table_ranks_by_backend_time_and_totals_sum_its_rows(
        meter, no_disk_cache):
    program("cm_rank_probe")(X)
    rows = compiles.table()
    assert rows == sorted(rows, key=lambda r: (-r["backendMs"],
                                               -r["readMs"], r["program"]))
    assert compiles.table(top=2) == rows[:2]
    totals = compiles.totals()
    assert set(totals) == {"builds", "hits", "misses", *PARTS}
    for k in totals:
        assert totals[k] == pytest.approx(sum(r[k] for r in rows))


def test_a_statements_evidence_splits_its_compile_time(meter, disk_cache):
    program("cm_evidence_probe")(X)
    ev = evidence.begin()
    program("cm_evidence_probe")(X)                 # a hit
    program("cm_evidence_probe", factor=7)(X)       # a miss
    out = ev.end()
    assert out["cacheHits"] == 1 and out["cacheMisses"] == 1
    assert 0 < out["cacheReadMs"] < out["compileMs"]
    assert out["traceLowerMs"] > 0
    comp = out["rollup"]["phases"]["compile"]
    assert comp["readMs"] == pytest.approx(out["cacheReadMs"], abs=0.01)
    assert comp["backendMs"] == pytest.approx(
        out["compileMs"] - out["cacheReadMs"], abs=0.01)


def test_rollup_says_how_long_each_sync_site_waited():
    sites = [obs_trace.SyncSite("sync", "a.py:1:f", 1, 4_000_000, 0, None, 1),
             obs_trace.SyncSite("sync", "a.py:1:f", 1, 1_000_000, 0, None, 1),
             obs_trace.SyncSite("counts3", "b.py:9:g", 3, 500_000, 0, None,
                                1)]
    by_site = {s["site"]: s for s in obs_export.rollup(sites)["syncSites"]}
    assert by_site["a.py:1:f"] == {"site": "a.py:1:f", "tag": "sync",
                                   "syncs": 2, "waitMs": 5.0,
                                   "maxWaitMs": 4.0}
    assert by_site["b.py:9:g"]["waitMs"] == 0.5
    assert by_site["b.py:9:g"]["maxWaitMs"] == 0.5
    # the top sites stay ranked by syncs, not by wait
    assert [s["site"] for s in obs_export.rollup(sites)["syncSites"]] == \
        ["b.py:9:g", "a.py:1:f"]


def test_power_run_reports_the_split_and_the_ledger_ends_with_compiles(
        tmp_path, monkeypatch, capsys):
    """``nds_power.py``'s loop: per query ``cacheReadMs`` / ``traceLowerMs``
    / ``cacheHits`` / ``cacheMisses`` beside ``compileMs`` and ``execMs``
    (whose arithmetic stays), the terminal record's ``compiles`` block,
    and ``tools/trace_report.py`` printing it."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    from collections import OrderedDict

    from nds_tpu import power
    from nds_tpu.obs.ledger import load_ledger
    from nds_tpu.schema import get_schemas
    from nds_tpu.types import to_arrow as to_pa
    fields = get_schemas(use_decimal=True)["item"]
    monkeypatch.setattr(power, "get_schemas",
                        lambda use_decimal: {"item": fields})
    data = tmp_path / "data"
    (data / "item").mkdir(parents=True)
    cols = {f.name: pa.array([None, None], to_pa(f.type)) for f in fields}
    cols["i_item_sk"] = pa.array([1, 2], to_pa(fields[0].type))
    pq.write_table(pa.table(cols), data / "item" / "part-0.parquet")
    ledger_path = tmp_path / "campaign.jsonl"
    jdir = tmp_path / "json"
    power.run_query_stream(str(data), None,
                           OrderedDict(q="select count(*) c from item"),
                           str(tmp_path / "t.csv"),
                           json_summary_folder=str(jdir),
                           ledger_path=str(ledger_path))
    with open(next(jdir.glob("*.json"))) as f:
        summary = json.load(f)
    for k in ("compileMs", "execMs", "cacheReadMs", "traceLowerMs",
              "cacheHits", "cacheMisses"):
        assert k in summary, k
    assert summary["cacheReadMs"] <= summary["compileMs"]
    assert summary["execMs"] == pytest.approx(
        max(summary["queryTimes"][0] - summary["compileMs"], 0.0), abs=0.2)
    led = load_ledger(str(ledger_path))
    rec = led.queries["q"]
    assert rec["cacheReadMs"] == summary["cacheReadMs"]
    assert rec["cacheMisses"] == summary["cacheMisses"]
    block = led.end["compiles"]
    assert block["builds"] > 0 and set(PARTS) <= set(block)
    assert 0 < len(block["programs"]) <= 20
    top = block["programs"][0]
    assert top["program"].startswith("jit(") and top["builds"] >= 1
    assert block["programs"] == sorted(
        block["programs"],
        key=lambda r: (-r["backendMs"], -r["readMs"], r["program"]))

    capsys.readouterr()
    spec = importlib.util.spec_from_file_location(
        "trace_report_c", os.path.join(REPO, "tools", "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main([str(ledger_path), "--top", "3"]) == 0
    out = capsys.readouterr().out
    assert "# compile by program" in out
    assert "(all)" in out and top["program"] in out
    block = mod.compile_report_lines(str(ledger_path), top=3)
    assert len(block) == 1 + 2 + 1 + 3   # blank, head, columns, all, 3 rows
    assert "\n".join(block) in out
    # a ledger from before the block reads as before: no section
    old = tmp_path / "old.jsonl"
    with open(ledger_path) as f, open(old, "w") as g:
        for line in f:
            rec = json.loads(line)
            rec.pop("compiles", None)
            g.write(json.dumps(rec) + "\n")
    assert mod.compile_report_lines(str(old)) == []
