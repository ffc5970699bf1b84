# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Mean time the chunk loop waited on the prefetch ring per statement (ms);
nothing where no scan streamed."""


def read(run):
    recs = [r for r in run["records"] if r["stream_scans"]]
    if not recs:
        return None
    total = sum(s.get("prefetchStallMs", 0.0)
                for r in recs for s in r["stream_scans"])
    return total / len(run["records"])
