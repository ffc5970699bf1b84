# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""BENCHMARK.json and the files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file found by the name the manifest gives it:

    configs/<config>.json      the deployment as it is run
    traffic/<traffic>.json     the mix one general closed-loop driver reads
    metrics/<metric name>.py   one function ``read(run) -> number | None``
    end_to_end/<metric name>.py  the same, for an end-to-end metric

so a later PR adds a cell, a configuration, a mix or a metric with new files
and new entries, and edits nothing that is here. No cell, query or metric
name appears in the harness's code.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class ManifestError(Exception):
    pass


class Manifest:
    def __init__(self, root: str = ROOT):
        self.root = root
        self.bench_dir = os.path.join(root, "benchmark")
        path = os.path.join(root, "BENCHMARK.json")
        try:
            with open(path) as f:
                self.doc = json.load(f)
        except OSError as e:
            raise ManifestError(f"cannot read {path}: {e}") from e

    # -- lookups -------------------------------------------------------------

    def _by_name(self, section: str, name: str) -> dict:
        for entry in self.doc[section]:
            if entry["name"] == name:
                return entry
        known = ", ".join(e["name"] for e in self.doc[section])
        raise ManifestError(f"no {section} entry named {name!r} "
                            f"(there are: {known})")

    def cell(self, name: str) -> dict:
        return self._by_name("workloads", name)

    def config_path(self, config: str) -> str:
        entry = self._by_name("configs", config)
        return os.path.join(self.root, entry["file"])

    def config(self, config: str) -> dict:
        return _read_json(self.config_path(config))

    def traffic_path(self, traffic: str) -> str:
        return os.path.join(self.bench_dir, "traffic", f"{traffic}.json")

    def traffic(self, traffic: str) -> dict:
        return _read_json(self.traffic_path(traffic))

    def reader_path(self, metric: str, section: str = "metrics") -> str:
        return os.path.join(self.bench_dir, section, f"{metric}.py")

    # -- which metrics a cell reports ----------------------------------------

    def end_to_end(self, cell: str) -> list:
        return [m for m in self.doc["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> list:
        """Per-layer metrics this cell reports: those whose ``workloads``
        list it. Every per-layer entry states its cells."""
        return [m for m in self.doc["per_layer"] if cell in m["workloads"]]

    def reader(self, metric: str, section: str = "metrics"):
        """The metric's reader: ``read(run) -> number | None``. ``section``
        is ``metrics`` (per layer) or ``end_to_end``."""
        path = self.reader_path(metric, section)
        if not os.path.isfile(path):
            raise ManifestError(f"metric {metric!r} has no reader at {path}")
        module = load_module(path, "benchmark_metric_" + "".join(
            c if c.isalnum() else "_" for c in metric))
        if not callable(getattr(module, "read", None)):
            raise ManifestError(f"{path} defines no read(run)")
        return module.read


def load_module(path: str, name: str):
    """A Python file of the benchmark, found by its path and not by import
    name (a metric's file is named after the metric, dots included)."""
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _read_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as e:
        raise ManifestError(f"cannot read {path}: {e}") from e
