"""Device ms per rebalance request in object selection
(scope lb-plan/stage3-objects)."""
from chipbench import layers, readers


def read(run):
    return readers.scope_ms_per_request(run, lambda p: layers.STAGE3 in p)
