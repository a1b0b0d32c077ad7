"""Device ms per replay step in one chip's chare-load histogram (scope
kernel/histogram), the mean over the cell's chips."""
from chipbench import chips, layers


def read(run):
    return chips.ms_per(run, lambda p: layers.HISTOGRAM in p, chips.STEPS)
