"""Device ms per fired rebalance in the executed particle exchange
(chipbench.layers.exchange).  Until the program puts an ``exchange/``
scope on the scanned step's exchange, its ops are known by lying in a
``cond`` branch outside the planner; the reading is left out where that
does not give one stretch of exchange ops per fired step."""
from chipbench import layers, readers


def read(run):
    fires = run.counters.get("window_fires")
    if run.trace is None or not fires or not readers.exchange_attributed(run):
        return None
    return readers.traced_ms_per(run, run.trace.self_s(layers.exchange),
                                 fires)
