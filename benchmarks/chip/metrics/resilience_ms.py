"""Device ms per replay step in the resilience work of the sharded
replay: health projection, masked trigger statistics and the stranded
check (resilience/health), plan validation and the per-shard capacity
test (resilience/guard), and re-homing a dead PE's chares
(resilience/rehome); the mean over the cell's chips."""
from chipbench import chips


def read(run):
    return chips.ms_per(run, lambda p: "/resilience/" in f"/{p}",
                        chips.STEPS)
