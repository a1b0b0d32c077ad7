"""Device ms per fired rebalance in the executed particle exchange, read
from its own scope (exchange/migrate, runtime/migrate.build_and_apply):
manifest build and payload gathers, with no structural heuristic."""
from chipbench import marks


def read(run):
    fires = run.counters.get("window_fires")
    if run.unit != "steps" or not fires:
        return None
    s = marks.scope_s(run, lambda p: marks.under(p, marks.MIGRATE))
    return None if s is None else 1e3 * s / fires
