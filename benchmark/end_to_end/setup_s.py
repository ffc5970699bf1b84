# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Everything before the window: data children, attach, load, warm-up (s)."""


def read(run):
    return run["setup_s"]
