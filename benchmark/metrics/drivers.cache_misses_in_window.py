# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Programs XLA really compiled inside the traced pass: misses of JAX's
persistent compilation cache, counted by the benchmark's own listener.
Expected 0; a cache hit (a program re-read from disk) is not counted here
but is charged by ``drivers.compile_ms_in_window``."""


def read(run):
    return sum(r["cache_misses"] for r in run["records"])
