# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""XLA compilation charged inside the traced pass (ms); expected 0."""


def read(run):
    return sum(r["compile_ms"] for r in run["records"])
