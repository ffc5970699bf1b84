# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""``peak_bytes_in_use`` of the fullest device after the window (bytes)."""


def read(run):
    return run["device"]["memory_peak_bytes"] or None
