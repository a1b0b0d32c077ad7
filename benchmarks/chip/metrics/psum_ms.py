"""Device ms per replay step in the psums that complete the handoff
counts and the chare histogram across the chips (scope replay/psum,
distributed/replay_shard), the mean over the cell's chips."""
from chipbench import chips, marks


def read(run):
    return chips.ms_per(run, lambda p: marks.under(p, "replay/psum"),
                        chips.STEPS)
