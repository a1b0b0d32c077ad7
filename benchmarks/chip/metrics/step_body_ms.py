"""Device ms per replay step outside every named layer: chare lookup,
owner gathers, handoff accounting, trigger statistics.  Left out where
the exchange cannot be told apart (``readers.exchange_attributed``)."""
from chipbench import readers


def read(run):
    if (run.trace is None or run.unit != "steps"
            or not readers.exchange_attributed(run)):
        return None
    return readers.traced_ms_per(run, readers.step_body_s(run), run.units)
