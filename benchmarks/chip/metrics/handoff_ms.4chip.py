"""Device ms per replay step in one chip's particle handoff: the chare
lookup, the moved mask, the source and destination PE gathers and the
handoff counts (scope replay/handoff, distributed/replay_shard), the
mean over the cell's chips."""
from chipbench import chips, marks


def read(run):
    return chips.ms_per(run, lambda p: marks.under(p, marks.HANDOFF),
                        chips.STEPS)
