"""Device ms per replay step in one chip's owner maps, the old and new
owner of every slot's chare, computed on every step and read on a fired
one (scope replay/owners, distributed/replay_shard), the mean over the
cell's chips."""
from chipbench import chips, marks


def read(run):
    return chips.ms_per(run, lambda p: marks.under(p, marks.OWNERS),
                        chips.STEPS)
