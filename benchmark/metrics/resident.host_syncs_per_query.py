# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Mean host syncs per statement (the program's ``ops.sync_count``)."""


def read(run):
    recs = run["records"]
    return sum(r["host_syncs"] for r in recs) / len(recs) if recs else None
