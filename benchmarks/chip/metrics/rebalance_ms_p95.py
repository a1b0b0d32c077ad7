"""95th percentile of the latency of every rebalance request in the
window, submit to assignment on the host (host clock)."""
import numpy as np


def read(run):
    if run.unit != "requests" or not run.latencies_s:
        return None
    return 1e3 * float(np.percentile(run.latencies_s, 95))
