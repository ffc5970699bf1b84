# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""The chip's published peaks, keyed by ``device_kind``. A device that is
not in the table is an error, never a default."""

from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str, path: str = _PATH) -> dict:
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r} in {path} "
            f"(known: {', '.join(sorted(table))})")
    return table[device_kind]
