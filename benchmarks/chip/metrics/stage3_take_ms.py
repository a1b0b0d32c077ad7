"""Device ms per rebalance request in object selection's take-while: the
per-node sort, prefix sum and scatter, and the shipped, assignment and
budget updates (scope stage3-objects/.../take,
core/object_selection.select_objects)."""
from chipbench import layers, marks


def read(run):
    return marks.scope_ms_per_unit(
        run, lambda p: layers.STAGE3 in p and marks.under(p, marks.TAKE),
        "requests")
