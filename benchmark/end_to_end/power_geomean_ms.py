# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Geometric mean over the statement names of each name's mean wall over all
its executions in the window (ms): guards the light statements."""


from benchmark import window


def read(run):
    return window.power_geomean_ms(run["records"])
