# Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
"""Mean cells per statement read by the subquery evaluators' own
decorrelation: the program's ``phases["op.subquery"]["cells"]``, the key
arrays (data and validity) of the outer and the inner side at their buckets
and, where an ``EXISTS`` has a non-equality residual, every array of both
sides gathered at the pairs' bucket and the two pair-index arrays. Stated
from host-known shapes; the ``op.join`` / ``op.semi_join`` / ``op.gather``
spans inside state their own. Nothing where no statement reports it."""


def read(run):
    recs = run["records"]
    vals = [r["phases"]["op.subquery"]["cells"] for r in recs
            if "cells" in r["phases"].get("op.subquery", {})]
    return sum(vals) / len(recs) if vals else None
