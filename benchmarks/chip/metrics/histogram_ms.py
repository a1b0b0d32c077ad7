"""Device ms per replay step in the chare-load histogram
(scope kernel/histogram)."""
from chipbench import layers, readers


def read(run):
    return readers.scope_ms_per_step(run, lambda p: layers.HISTOGRAM in p)
