# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""XLA compiles of the process (s): ``backendMs`` of the program's compile
table, the backend step of every build the persistent cache did not serve.

The first readers to ask the program directly: ``run`` carries no record of
the warm-up, so ``read`` imports ``nds_tpu.obs.compiles`` (the program is
imported by then) and reads the PROCESS's totals at the end of the run:
set-up (load and warm-up passes) and the window together. The window's
share is ``drivers.compile_ms_in_window`` / ``drivers.cache_misses_in_window``,
0 on every accepted line, so the number is set-up's. Nothing where the
program has no such module (a tree from before PR 37)."""


def read(run):
    try:
        from nds_tpu.obs import compiles
    except ImportError:
        return None
    return compiles.totals()["backendMs"] / 1e3
