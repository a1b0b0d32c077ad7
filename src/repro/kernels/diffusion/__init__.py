from repro.kernels.diffusion.ops import diffusion_nsweeps, sweep_impl
from repro.kernels.diffusion.kernel import (
    diffusion_nsweeps_pallas,
    diffusion_sweep_pallas,
)

__all__ = [
    "diffusion_nsweeps",
    "diffusion_nsweeps_pallas",
    "diffusion_sweep_pallas",
    "sweep_impl",
]
