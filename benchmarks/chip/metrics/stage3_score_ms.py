"""Device ms per rebalance request in object selection's ordering metric,
the per-phase comm (or coord) score (scope stage3-objects/.../score,
core/object_selection.select_objects)."""
from chipbench import layers, marks


def read(run):
    return marks.scope_ms_per_unit(
        run, lambda p: layers.STAGE3 in p and marks.under(p, marks.SCORE),
        "requests")
