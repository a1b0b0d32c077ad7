"""Share of the traced window in which no op ran on the device, in %,
averaged over the chips (replay cells)."""
from chipbench import readers


def read(run):
    return readers.idle_share_pct(run, "steps")
