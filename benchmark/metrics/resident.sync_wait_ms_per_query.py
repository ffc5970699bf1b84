# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Mean time blocked on device-to-host reads per statement (ms)."""


def read(run):
    recs = run["records"]
    return (sum(r["sync_wait_ms"] for r in recs) / len(recs)
            if recs else None)
