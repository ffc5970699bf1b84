# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Tests of the benchmark harness (``benchmark/``): CPU only, no TPU
topology is described and libtpu is never loaded."""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

