"""Device ms per replay step in the particle push of one chip's slab
(scope kernel/pic-push), the mean over the cell's chips."""
from chipbench import chips, layers


def read(run):
    return chips.ms_per(run, lambda p: layers.PUSH in p, chips.STEPS)
