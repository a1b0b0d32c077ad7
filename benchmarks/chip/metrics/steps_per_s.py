"""Replay steps completed per second over the whole window, rebalances
included (host clock)."""


def read(run):
    if run.unit != "steps" or run.window_s <= 0:
        return None
    return run.units / run.window_s
