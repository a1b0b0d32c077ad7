"""Device ms per fired rebalance in the sharded replay's ring all-to-all
(scope exchange/ring, runtime/migrate.ring_exchange), the mean over the
cell's chips."""
from chipbench import chips, marks


def read(run):
    return chips.ms_per(run, lambda p: marks.under(p, "exchange/ring"),
                        chips.FIRES)
