# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Share of the HBM roofline: the time the chip would need at its published
bandwidth to read the bytes the pass's scans must read (benchmark's own
count, ``scanbytes``), over the device's busy time in the traced pass (%).
Bound by bytes. Nothing without a device trace or a known peak."""


def read(run):
    trace, peaks = run.get("trace"), run.get("peaks")
    if not trace or not peaks or trace["busy_s"] <= 0:
        return None
    scan_bytes = sum(run["queries"][r["name"]]["scan_bytes"]
                     for r in run["records"] if r["ok"])
    if scan_bytes <= 0:
        return None
    least_s = scan_bytes / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / trace["busy_s"]
