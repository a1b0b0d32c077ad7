"""Least time the chip needs for the push's work (chipbench.roofline,
memory bound on v5e) over the measured push time, in %."""
from chipbench import layers, readers, roofline


def read(run):
    ms = readers.scope_ms_per_step(run, lambda p: layers.PUSH in p)
    if not ms:
        return None
    n = run.cell.config["system"]["n_particles"]
    least, _ = roofline.least_time(*roofline.push_work(n), run.device_kind)
    return 100.0 * least / (ms * 1e-3)
