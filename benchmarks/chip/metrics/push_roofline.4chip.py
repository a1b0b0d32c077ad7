"""Least time one chip needs for the push of its slab (chipbench.roofline
on ``replay_capacity`` slots, memory bound on v5e) over the measured
push time per chip, in %.  The push runs on every slot of the slab, live
or not."""
from chipbench import chips, layers, roofline


def read(run):
    ms = chips.ms_per(run, lambda p: layers.PUSH in p, chips.STEPS)
    if not ms:
        return None
    n = run.cell.config["system"]["replay_capacity"]
    least, _ = roofline.least_time(*roofline.push_work(n), run.device_kind)
    return 100.0 * least / (ms * 1e-3)
