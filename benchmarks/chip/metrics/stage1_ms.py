"""Device ms per rebalance request in neighbour selection
(scope lb-plan/stage1-neighbors)."""
from chipbench import layers, readers


def read(run):
    return readers.scope_ms_per_request(run, lambda p: layers.STAGE1 in p)
