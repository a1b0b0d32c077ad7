"""Pure-jnp oracle for the diffusion kernels: exactly the chunk the
balancer runs (core/virtual_lb.py), in the fused kernel's signature.
"""
from repro.core.virtual_lb import reference_nsweeps


def diffusion_nsweeps_ref(x, own, flow, it, res, stall, nbr_idx, nbr_mask,
                          rev, alpha, *, n_sweeps: int, single_hop: bool,
                          tol, max_iters):
    """S-sweep chunk oracle for ``diffusion_nsweeps_pallas``."""
    return reference_nsweeps(
        x, own, flow, it, res, stall, nbr_idx, nbr_mask, rev, alpha,
        n_sweeps=n_sweeps, single_hop=single_hop, tol=tol,
        max_iters=max_iters)
