# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Mean host time per statement issuing engine primitives (ms): over the
program's ``op.*`` spans, self time minus the self share of the time
blocked on device reads. Nothing where the program has no such span."""


def read(run):
    recs = run["records"]
    vals = [p["selfMs"] - p.get("syncWaitMs", 0.0)
            for r in recs for name, p in r["phases"].items()
            if name.startswith("op.") and "selfMs" in p]
    return sum(vals) / len(recs) if vals else None
