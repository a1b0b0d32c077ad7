"""Share of the traced window in which no op ran on the device, in %
(rebalance cells: the host path of Strategy.run sits in the gaps)."""
from chipbench import readers


def read(run):
    return readers.idle_share_pct(run, "requests")
