"""Blocking device-to-host reads per rebalance request, from the program's
counters lb.plan.host_reads and lb.plan.requests (repro.obs.metrics,
bumped by core/engine)."""
from chipbench import marks


def read(run):
    return marks.counter_per_request(run, "lb.plan.host_reads")
