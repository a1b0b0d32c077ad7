"""Stage-2 diffusion sweeps per rebalance request (PlanStats, read by
Strategy.run)."""
from chipbench import readers


def read(run):
    return readers.mean_counter(run, "diffusion_iters", "requests")
