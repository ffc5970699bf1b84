# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""The 24-table load: sum of the ``CreateTempView`` rows ``setup_tables``
returns (s)."""


def read(run):
    rows = [ms for _app, label, ms in run["load_rows"]
            if label.startswith("CreateTempView")]
    return sum(rows) / 1e3 if rows else None
