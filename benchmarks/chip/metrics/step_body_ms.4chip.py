"""Device ms per replay step of the sharded step outside every layer
with a metric of its own (push, histogram, planner, exchange, psums,
resilience): handoff, owner maps, load statistics and the trigger; the
mean over the cell's chips."""
from chipbench import chips


def read(run):
    return chips.step_body_ms(run)
