# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Shared jax-free loader for the campaign evidence ledger module.

``nds_tpu/obs/ledger.py`` is deliberately stdlib-only, but importing it
as ``nds_tpu.obs.ledger`` executes the package root, which imports jax —
unacceptable for the bench.py parent (the chip belongs to
the serving child alone) and needless weight for post-hoc tools. This
helper loads the module BY FILE PATH, once, cached under a canonical
``sys.modules`` name so every caller shares one module object (isinstance
checks across callers stay valid).
"""

import importlib.util
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_NAME = "_nds_ledger_stdlib"
_CAMPAIGN_NAME = "_nds_campaign_stdlib"
# shared with nds_tpu/obs/ledger.py's _metrics_mod(): both loaders must
# resolve to ONE module object so the bench parent's feeds and the
# heartbeat's live-file exporter see the same default registry
_METRICS_NAME = "_nds_metrics_stdlib"


def _load(name, relpath):
    mod = sys.modules.get(name)
    if mod is None:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(REPO, *relpath))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return mod


def ledger_mod():
    """The ledger module, loaded without touching the jax import."""
    return _load(_NAME, ("nds_tpu", "obs", "ledger.py"))


def campaign_mod():
    """The campaign-orchestration module (arm model, env fingerprint,
    manifest) — stdlib-only under the same discipline as the ledger."""
    return _load(_CAMPAIGN_NAME, ("nds_tpu", "obs", "campaign.py"))


def metrics_mod():
    """The live-metrics registry module (rolling rollups, snapshot
    exporter) — stdlib-only under the same discipline as the ledger."""
    return _load(_METRICS_NAME, ("nds_tpu", "obs", "metrics.py"))
