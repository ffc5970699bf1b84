# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Mean host time per statement inside window functions (ms): the
inclusive ``ms`` of the program's ``op.window`` spans (the shared sort of a
(partition, order) spec, the boundary scans and the scatter back; the
``op.expr`` and ``op.sort`` spans a window opens are inside it).

WAIT-ATTRIBUTED host time, as ``resident.join_ms_per_query`` says of
itself: ``engine/window.py`` reads nothing, so this is its dispatch alone
unless a read inside the window's key expressions waits for the work
queued before it. Nothing where no statement has such a span."""


def read(run):
    recs = run["records"]
    vals = [r["phases"]["op.window"]["ms"] for r in recs
            if "ms" in r["phases"].get("op.window", {})]
    return sum(vals) / len(recs) if vals else None
