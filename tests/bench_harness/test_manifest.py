# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""BENCHMARK.json against the files it names, and the proof that a cell, a
configuration, a mix or a metric is added with files and entries alone."""

import json
import os
import re
import shutil

import pytest

from benchmark import manifest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    DOC = json.load(_f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in DOC["workloads"]]
METRICS = DOC["end_to_end"] + DOC["per_layer"]


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(REPO)


def test_top_level_keys_are_the_contracts():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= DOC["run_seconds"] <= 51
    for word in DOC["command"]:
        assert not word.startswith("/") and ".." not in word
    assert any(word.startswith(p + "/") for word in DOC["command"]
               for p in DOC["paths"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_finds_its_config_and_traffic_files(man, cell):
    entry = man.cell(cell)
    assert entry["chips"] in (1, 4) and len(entry["why"]) <= 200
    config = man.config(entry["config"])
    traffic = man.traffic(entry["traffic"])
    assert os.path.isfile(os.path.join(REPO, "benchmark", config["reference"]))
    assert traffic["queries"], "a mix with no statement"
    for q in traffic["queries"]:
        assert set(q) >= {"name", "scans", "result", "ordered"}
        assert set(q["result"]) <= {"int", "str", "cents"}


@pytest.mark.parametrize("config", [c["name"] for c in DOC["configs"]])
def test_config_file_states_what_the_manifest_says(man, config):
    entry = next(c for c in DOC["configs"] if c["name"] == config)
    assert entry["file"].startswith(tuple(p + "/" for p in DOC["paths"]))
    body = man.config(config)
    assert body["source"] == entry["source"] and len(entry["source"]) <= 200
    assert sorted(body["reduced"]) == sorted(entry["reduced"])
    for key in entry["reduced"]:
        assert NAME.match(key) and key in body and key in body["published"]
        assert not key.endswith(("_dim", "_rank"))
    assert body["guarantees"]["decimals"].startswith("exact")
    assert any(w["config"] == config for w in DOC["workloads"])


def test_two_deployments_of_one_benchmark_have_sources_that_differ():
    sources = [c["source"] for c in DOC["configs"]]
    files = [c["file"] for c in DOC["configs"]]
    assert len(set(sources)) == len(sources)
    assert len(set(files)) == len(files)


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_metric_name_unit_and_reader(man, metric):
    m = next(x for x in METRICS if x["name"] == metric)
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("device_trace", "program_span", "program_counter",
                           "host_clock")
    per_layer = m in DOC["per_layer"]
    allowed = {"name", "unit", "better", "source", "workloads"} | (
        {"layer", "moves"} if per_layer else {"bound"})
    assert set(m) <= allowed
    section = "metrics" if per_layer else "end_to_end"
    assert callable(man.reader(metric, section))
    if per_layer:
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
    else:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("metric", [m["name"] for m in DOC["per_layer"]])
def test_per_layer_metrics_cells_report_what_it_moves(man, metric):
    m = next(x for x in DOC["per_layer"] if x["name"] == metric)
    assert m["moves"] in {e["name"] for e in DOC["end_to_end"]}
    cells = m["workloads"]          # every per-layer entry states its cells
    assert cells, "a metric no cell reports"
    for cell in cells:
        assert cell in CELLS
        assert m["moves"] in {e["name"] for e in man.end_to_end(cell)}


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_metric_and_a_layer(man, cell):
    e2e = {m["name"] for m in man.end_to_end(cell)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert man.per_layer(cell)


def test_roofline_names_end_in_roofline_with_unit_percent():
    for m in DOC["per_layer"]:
        if "roofline" in m["name"]:
            assert m["name"].endswith("_roofline") and m["unit"] == "%"


def test_no_cell_query_or_metric_name_in_the_harness_code():
    names = CELLS + [m["name"] for m in METRICS if m["name"] != "setup_s"]
    traffic = manifest.Manifest(REPO).traffic(DOC["workloads"][0]["traffic"])
    names += [q["name"] for q in traffic["queries"]]
    for fn in ("run.py", "manifest.py", "window.py", "program.py",
               "datagen.py", "compare.py", "xplane.py", "scanbytes.py",
               "control.py", "prove.py", "peaks.py"):
        with open(os.path.join(REPO, "benchmark", fn)) as f:
            code = "\n".join(ln for ln in f.read().splitlines()
                             if not ln.lstrip().startswith(("#", '"""')))
        body = code.split('"""', 2)[-1]     # past the module docstring
        for name in names:
            assert f'"{name}"' not in body and f"'{name}'" not in body, (
                fn, name)


def test_a_throwaway_cell_loads_from_new_files_alone(tmp_path):
    """A later PR adds a configuration, a mix, a per-layer metric and a cell
    with files and entries only: nothing of the harness is edited."""
    root = tmp_path / "checkout"
    bench = root / "benchmark"
    for sub in ("configs", "traffic", "metrics", "end_to_end"):
        (bench / sub).mkdir(parents=True)
    shutil.copy(os.path.join(REPO, "benchmark", "end_to_end", "setup_s.py"),
                bench / "end_to_end" / "setup_s.py")
    (bench / "configs" / "tiny.json").write_text(json.dumps(
        {"source": "a test", "scale_factor": 0.01, "reduced": [],
         "reference": "reference/sqlite_ref.py"}))
    (bench / "traffic" / "one_scan.json").write_text(json.dumps(
        {"kind": "closed_loop", "clients": 1, "queries": [
            {"name": "query96", "scans": {}, "result": ["int"],
             "ordered": True}]}))
    (bench / "metrics" / "new.rows_per_answer.py").write_text(
        "def read(run):\n"
        "    return sum(len(r['rows']) for r in run['records']) / "
        "len(run['records'])\n")
    doc = {"command": ["python3", "benchmark/run.py"], "paths": ["benchmark"],
           "run_seconds": 10,
           "configs": [{"name": "tiny", "source": "a test",
                        "file": "benchmark/configs/tiny.json", "reduced": [],
                        "why": "a test"}],
           "workloads": [{"name": "tiny.one_scan", "config": "tiny",
                          "traffic": "one_scan", "chips": 1, "why": "a test"}],
           "end_to_end": [{"name": "setup_s", "unit": "s", "better": "lower",
                           "bound": 0.25, "source": "host_clock"}],
           "per_layer": [{"name": "new.rows_per_answer", "unit": "rows",
                          "better": "lower", "source": "program_counter",
                          "layer": "drivers", "moves": "setup_s",
                          "workloads": ["tiny.one_scan"]}]}
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    man = manifest.Manifest(str(root))
    cell = man.cell("tiny.one_scan")
    assert man.config(cell["config"])["scale_factor"] == 0.01
    assert man.traffic(cell["traffic"])["queries"][0]["name"] == "query96"
    (metric,) = man.per_layer("tiny.one_scan")
    read = man.reader(metric["name"])
    assert read({"records": [{"rows": [1, 2]}, {"rows": [3]}]}) == 1.5
    assert man.reader("setup_s", "end_to_end")({"setup_s": 2.5}) == 2.5
    with pytest.raises(manifest.ManifestError):
        man.cell("no.such.cell")
    with pytest.raises(manifest.ManifestError):
        man.reader("no.such.metric")
