# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""The benchmark: one command runs one cell of BENCHMARK.json once.
See README.md in this directory."""
