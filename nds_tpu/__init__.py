# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""nds-tpu: a TPU-native decision-support (TPC-DS derived) benchmark framework.

Rebuilds the capabilities of the NDS v2.0 harness (spark-rapids-benchmarks)
on a JAX/XLA/Pallas stack: columnar execution on TPU HBM, pjit/shard_map
partitioning over a device mesh, and ICI all-to-all exchange in place of the
network shuffle. See SURVEY.md at the repo root for the structural map of the
reference this build follows.
"""

__version__ = "0.1.0"

# The engine's exact-decimal path is int64 fixed point and date arithmetic is
# 64-bit; x64 must be on before any jax array is created.
import os as _os  # noqa: E402

import jax as _jax  # noqa: E402

_jax.config.update("jax_enable_x64", True)

_comp_cache_enabled = False
_comp_cache_unwritable = False

# default persistent-cache root: a FIXED path inside the checkout (the
# path is part of a cache entry's key, so a directory built from a pid, a
# time or tempfile never hits). Used only when the caller has not placed
# the cache from outside with JAX_COMPILATION_CACHE_DIR.
_DEFAULT_CACHE_ROOT = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
    ".jax_cache")


def _cpu_fingerprint() -> str:
    """XLA:CPU AOT artifacts bake the compile host's vector ISA, and
    loading one on a host without those features segfaults/SIGILLs mid-run
    (seen: a cross-machine cache killed a 103-query sweep at query 81), so
    CPU entries live in a per-machine sub-directory."""
    import hashlib
    import platform
    try:
        with open("/proc/cpuinfo") as f:
            flags = [ln for ln in f if ln.startswith("flags")][0]
    except (OSError, IndexError):  # pragma: no cover - non-Linux
        flags = platform.processor()
    return "cpu_" + hashlib.sha1(flags.encode()).hexdigest()[:12]


def enable_compile_cache() -> bool:
    """Enable the persistent XLA compilation cache (idempotent).

    A Power Run compiles ~100 query pipelines; caching them across processes
    is the TPU analog of the reference's warmed JVM (ref: nds/README.md
    Power Run notes). Called lazily from Session creation, when the backend
    is resolved: CPU is excluded because XLA:CPU AOT reload is
    machine-feature sensitive (SIGILL risk) and the CPU platform only backs
    tests — NDS_TPU_COMP_CACHE=force opts CPU in anyway (same-machine dev
    loops like the coverage sweep); NDS_TPU_NO_COMP_CACHE disables entirely.

    Placement: where ``JAX_COMPILATION_CACHE_DIR`` is set the cache is
    JAX's own setting and this function sets NO directory; unset, it goes
    to ``<checkout>/.jax_cache`` (CPU entries in a machine-fingerprint
    sub-directory).
    """
    global _comp_cache_enabled, _comp_cache_unwritable
    if _comp_cache_enabled or _comp_cache_unwritable or \
            _os.environ.get("NDS_TPU_NO_COMP_CACHE"):
        return _comp_cache_enabled
    on_cpu = _jax.default_backend() == "cpu"
    if on_cpu and _os.environ.get("NDS_TPU_COMP_CACHE") != "force":
        return False
    if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        cache_dir = _DEFAULT_CACHE_ROOT
        if on_cpu:
            cache_dir = _os.path.join(cache_dir, _cpu_fingerprint())
        try:
            _os.makedirs(cache_dir, exist_ok=True)
        except OSError as e:
            # the cache is an optimisation: a read-only checkout runs
            # without it, and says so once
            import sys
            print(f"# compile cache disabled: cannot create {cache_dir} "
                  f"({e})", file=sys.stderr)
            _comp_cache_unwritable = True
            return False
        _jax.config.update("jax_compilation_cache_dir", cache_dir)
    # eager table-at-a-time execution makes many small compilations, so
    # cache everything (the default 1s floor would skip nearly all of it)
    _jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _comp_cache_enabled = True
    return True
