# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Test harness configuration: a virtual 8-device CPU mesh.

Multi-chip hardware is not available in CI, so every distributed test runs
against JAX's host-platform device emulation — the "fake pod" mode the
reference lacks entirely (its multi-node behavior is only exercised on real
clusters; SURVEY.md §4). Must run before jax initialises its backends.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "1")

# Re-pin to CPU after import too, so tests never initialise a device
# backend whatever set jax_platforms before this file ran.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)
