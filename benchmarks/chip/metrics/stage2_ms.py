"""Device ms per rebalance request in virtual diffusion
(scope lb-plan/stage2-diffusion)."""
from chipbench import layers, readers


def read(run):
    return readers.scope_ms_per_request(run, lambda p: layers.STAGE2 in p)
