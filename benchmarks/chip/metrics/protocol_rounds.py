"""Stage-1 handshake rounds per rebalance request (PlanStats, read by
Strategy.run)."""
from chipbench import readers


def read(run):
    return readers.mean_counter(run, "protocol_rounds", "requests")
