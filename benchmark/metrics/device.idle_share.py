# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""1 - union of device-operation intervals over the traced pass (%)."""


def read(run):
    trace = run.get("trace")
    return 100.0 * trace["idle_share"] if trace else None
