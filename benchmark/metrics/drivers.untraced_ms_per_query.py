# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Mean time per statement that no span of the program explains (ms): the
call's wall minus the durations of the program's parentless spans
(``rootMs`` of every phase). Nothing where the program marks no roots."""


def read(run):
    recs = [r for r in run["records"]
            if any("rootMs" in p for p in r["phases"].values())]
    if not recs:
        return None
    gaps = [r["call_ms"] - sum(p.get("rootMs", 0.0)
                               for p in r["phases"].values())
            for r in recs]
    return sum(gaps) / len(run["records"])
