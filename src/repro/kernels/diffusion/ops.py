"""Stage-2 dispatch: the S-sweep ``chunk_fn`` the planner runs.

``diffusion_nsweeps`` matches the fused S-sweep ``chunk_fn`` signature of
``core.virtual_lb.virtual_balance`` (the production planning path).

Implementation selection (``sweep_impl``): every backend runs the
``"reference"`` chunk, the pure-jnp S-sweep loop compiled by XLA.  The
rule: the sweep is a gather over an arbitrary neighbor table
(``x[nbr]``, ``push[nbr, rev]``), and Mosaic (the Pallas TPU compiler)
lowers neither that gather nor a ``dynamic_slice`` of a loaded ref, so
neither Pallas diffusion kernel compiles for a TPU.  Both kernels
(``kernel.py``: fused multi-sweep and streaming two-pass) stay, with
their interpret-mode parity tests, as the starting point for a
gather-free rewrite; nothing dispatches to them.
"""
from __future__ import annotations

from repro.distributed import compat
from repro.core.virtual_lb import reference_nsweeps


def sweep_impl(P: int, K: int) -> str:
    """Which implementation ``diffusion_nsweeps`` selects for (P, K):
    ``"reference"`` on every backend (see the module docstring)."""
    del P, K
    return "reference"


def diffusion_nsweeps(x, own, flow, it, res, stall, nbr_idx, nbr_mask, rev,
                      alpha, *, n_sweeps: int, single_hop: bool, tol,
                      max_iters):
    """S-sweep block (``chunk_fn`` for ``virtual_balance``), dispatched
    per :func:`sweep_impl`."""
    impl = sweep_impl(*nbr_idx.shape)
    with compat.named_scope(f"kernel/diffusion-nsweeps-{impl}"):
        return reference_nsweeps(
            x, own, flow, it, res, stall, nbr_idx, nbr_mask, rev, alpha,
            n_sweeps=n_sweeps, single_hop=single_hop, tol=tol,
            max_iters=max_iters)
