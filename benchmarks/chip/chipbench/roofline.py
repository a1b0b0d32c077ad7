"""Work of a kernel computed from its shapes, and the chip's peaks.

The work counted is the algorithm's, whatever implements it (the Pallas
kernel, XLA gathers beside it, or a later fused kernel), so that a
roofline share stays comparable across implementations.
"""
from __future__ import annotations

import json
import pathlib
from typing import Dict, Tuple

PEAKS_FILE = pathlib.Path(__file__).resolve().parents[1] / "peaks.json"

# PRK PIC push, per particle (kernels/pic_push: four corner charges,
# Coulomb force, kick and drift):
#   cell index: floor(x), floor(y)                                   2
#   per corner (x4): dx, dy with corner offset 4; r2 3; sqrt 1;
#     f = q*qc/max(r2, e) 3; fx += f*dx/max(r, e) 4; fy += ... 4    76
#   a = f/m 2; x, y drift with mod L 12; v += a*dt 4                 18
PUSH_FLOPS_PER_PARTICLE = 96
# read x, y, vx, vy, q and four corner charges; write x, y, vx, vy
PUSH_BYTES_PER_PARTICLE = 4 * (5 + 4 + 4)


def push_work(n_particles: int) -> Tuple[float, float]:
    """(flops, bytes) of one push over ``n_particles``."""
    return (float(PUSH_FLOPS_PER_PARTICLE) * n_particles,
            float(PUSH_BYTES_PER_PARTICLE) * n_particles)


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip; an unknown device is an error."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def least_time(flops: float, nbytes: float, device_kind: str):
    """(seconds, bound) the chip needs at least: the larger of the
    compute and the memory time, and which of the two it is."""
    p = peaks(device_kind)
    tc = flops / p["flops_per_s"]
    tm = nbytes / p["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
