# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""The window's wall over the statements completed in it (ms): the cell's
share of TPC-DS TPower, a stall between statements included."""


from benchmark import window


def read(run):
    return window.power_query_ms(run["records"])
