"""Host ms per rebalance request in the five PlanStats scalar reads, from
the program's counters lb.plan.stats_ns and lb.plan.requests
(repro.obs.metrics, bumped by core/engine._host_tail)."""
from chipbench import marks


def read(run):
    ns = marks.counter_per_request(run, "lb.plan.stats_ns")
    return None if ns is None else 1e-6 * ns
