"""Pallas TPU kernels for the virtual-LB diffusion sweep (paper §III.B).

The sweep is the iterated hot loop of the balancer: at simulator scale
(P ~ 10^5-10^6 nodes, K ≤ 16 neighbors) hundreds of sweeps run per LB round.
Two kernels cover the P spectrum.  Neither compiles for a TPU: Mosaic
refuses their neighbor gathers (``jnp.take`` over a whole VMEM vector or
table) and their ``dynamic_slice`` of loaded refs, so ``ops.py``
dispatches to neither and they run only in the interpret-mode tests.

**Fused multi-sweep kernel** (``diffusion_nsweeps_pallas``) — for working
sets that fit VMEM.  One ``pallas_call`` runs S
sweeps back-to-back: the neighbor/mask/reverse tables are loaded into VMEM
*once per S-sweep block* (instead of twice per sweep), push+recv fuse into
a single gather-only pass per sweep via the symmetric-graph identity
    recv[i, k] = push[nbr[i, k], rev[i, k]]
(the push matrix never round-trips HBM), and the (P, K) flow accumulator
plus the neighborhood residual stay on-chip across the whole block.  Each
sweep is gated by the same early-exit predicate the outer fixed-point loop
checks (convergence / iteration cap / stall), so the block is bit-for-bit
equal to S steps of the per-sweep loop — the sweep body is the *shared*
``core.virtual_lb.sweep_chunk_body``, identical by construction.

**Streaming two-pass kernel** (``diffusion_sweep_pallas``) — for large
P.  Computes one sweep with the tables streamed through VMEM in
``block_p`` node blocks (touched once per pass):
  * the load vector ``x`` (P f32 ≤ 4 MB at P = 10^6) and ``own`` stay fully
    VMEM-resident for every grid step — they are the gather targets;
  * pass A computes the scaled ``push`` matrix (single-hop row scale);
  * pass B gathers ``recv`` from the completed push matrix and forms
    outputs — gather-only, no scatters (scatters serialize on TPU).

The fixed-point loop lives in ``core/virtual_lb.py`` (a
``jax.lax.while_loop`` over S-sweep chunks); the fused kernel has its
``chunk_fn`` signature and the streaming kernel its per-sweep
``step_fn`` signature.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.virtual_lb import sweep_chunk_body, reference_sweep


def _push_kernel(x_ref, own_ref, nbr_ref, mask_ref, alpha_ref,
                 push_ref, *, single_hop: bool):
    """Pass A: push[i, k] = alpha * (x_i - x_nbr) clamped ≥ 0, row-rescaled
    so a node never ships more than its remaining own load (single-hop)."""
    x = x_ref[...]                       # (P,) — whole vector in VMEM
    nbr = nbr_ref[...]                   # (bp, K) node block
    mask = mask_ref[...]
    alpha = alpha_ref[0]
    i0 = pl.program_id(0) * nbr.shape[0]
    xi = jax.lax.dynamic_slice(x, (i0,), (nbr.shape[0],))      # (bp,)
    xn = jnp.where(mask, jnp.take(x, jnp.where(mask, nbr, 0), axis=0,
                                  mode="clip"), xi[:, None])
    push = jnp.maximum(alpha * (xi[:, None] - xn), 0.0)
    push = jnp.where(mask, push, 0.0)
    if single_hop:
        own = jax.lax.dynamic_slice(own_ref[...], (i0,), (nbr.shape[0],))
        tot = push.sum(axis=1)
        scale = jnp.where(tot > 0.0,
                          jnp.minimum(1.0, own / (tot + 1e-30)), 1.0)
        push = push * scale[:, None]
    push_ref[...] = push


def _recv_kernel(x_ref, own_ref, push_ref, nbr_ref, mask_ref, rev_ref,
                 x_out_ref, own_out_ref, flow_ref):
    """Pass B: recv[i,k] = push[nbr[i,k], rev[i,k]]; form outputs."""
    nbr = nbr_ref[...]                   # (bp, K)
    mask = mask_ref[...]
    rev = rev_ref[...]
    K = nbr.shape[1]
    i0 = pl.program_id(0) * nbr.shape[0]
    push_all = push_ref[...]             # (P, K) VMEM-resident
    my_push = jax.lax.dynamic_slice(
        push_all, (i0, 0), (nbr.shape[0], K))
    flat = jnp.where(mask, nbr, 0) * K + jnp.where(mask, rev, 0)
    recv = jnp.where(
        mask, jnp.take(push_all.reshape(-1), flat, axis=0, mode="clip"), 0.0)
    sent = my_push.sum(axis=1)
    xi = jax.lax.dynamic_slice(x_ref[...], (i0,), (nbr.shape[0],))
    own = jax.lax.dynamic_slice(own_ref[...], (i0,), (nbr.shape[0],))
    x_out_ref[...] = xi - sent + recv.sum(axis=1)
    own_out_ref[...] = own - sent
    flow_ref[...] = my_push - recv


def _pad_to(a, n, axis=0):
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, n - a.shape[axis])
    return jnp.pad(a, pad)


@functools.partial(
    jax.jit,
    static_argnames=("single_hop", "block_p", "interpret"),
)
def diffusion_sweep_pallas(
    x: jax.Array,          # (P,) f32 current virtual loads
    own: jax.Array,        # (P,) f32 remaining own (originating) load
    nbr_idx: jax.Array,    # (P, K) i32, -1 padded
    nbr_mask: jax.Array,   # (P, K) bool
    rev: jax.Array,        # (P, K) i32 reverse slots
    alpha,
    single_hop: bool = True,
    *,
    block_p: int = 512,
    interpret: bool = False,
):
    """One diffusion sweep. Returns (x_new, own_new, net_flow (P,K))."""
    P, K = nbr_idx.shape
    Pp = -(-P // block_p) * block_p
    xp = _pad_to(x.astype(jnp.float32), Pp)
    ownp = _pad_to(own.astype(jnp.float32), Pp)
    nbrp = _pad_to(nbr_idx, Pp)
    maskp = _pad_to(nbr_mask, Pp)
    revp = _pad_to(rev, Pp)
    alpha_arr = jnp.full((1,), alpha, jnp.float32)
    grid = (Pp // block_p,)

    vec_full = pl.BlockSpec((Pp,), lambda i: (0,))          # VMEM-resident
    tab_full = pl.BlockSpec((Pp, K), lambda i: (0, 0))
    tab_blk = pl.BlockSpec((block_p, K), lambda i: (i, 0))
    vec_blk = pl.BlockSpec((block_p,), lambda i: (i,))

    push = pl.pallas_call(
        functools.partial(_push_kernel, single_hop=single_hop),
        grid=grid,
        in_specs=[vec_full, vec_full, tab_blk, tab_blk,
                  pl.BlockSpec((1,), lambda i: (0,))],
        out_specs=tab_blk,
        out_shape=jax.ShapeDtypeStruct((Pp, K), jnp.float32),
        interpret=interpret,
    )(xp, ownp, nbrp, maskp, alpha_arr)

    x_new, own_new, flow = pl.pallas_call(
        _recv_kernel,
        grid=grid,
        in_specs=[vec_full, vec_full, tab_full, tab_blk, tab_blk, tab_blk],
        out_specs=[vec_blk, vec_blk, tab_blk],
        out_shape=[
            jax.ShapeDtypeStruct((Pp,), jnp.float32),
            jax.ShapeDtypeStruct((Pp,), jnp.float32),
            jax.ShapeDtypeStruct((Pp, K), jnp.float32),
        ],
        interpret=interpret,
    )(xp, ownp, push, nbrp, maskp, revp)

    return x_new[:P], own_new[:P], flow[:P]


# ------------------------------------------------------ fused multi-sweep --


def _nsweeps_kernel(x_ref, own_ref, flow_ref, nbr_ref, mask_ref, rev_ref,
                    fscal_ref, iscal_ref,
                    x_out_ref, own_out_ref, flow_out_ref, fstat_ref,
                    istat_ref, *, n_sweeps: int, single_hop: bool, P: int):
    """S early-exit-gated sweeps over fully VMEM-resident state.

    The whole working set — ``x``/``own`` vectors, the (P, K) tables, the
    flow accumulator and the per-sweep push/recv intermediates — lives in
    VMEM for the entire block; HBM is touched exactly once on the way in
    and once on the way out.  The sweep body is the shared
    ``core.virtual_lb.sweep_chunk_body`` (gather-only, one pass per sweep),
    so the block is bit-for-bit the per-sweep reference loop.  Padding rows
    (layout alignment) are sliced off before compute: reductions (residual
    mean, stall detection) see exactly the (P,) problem the reference sees.
    """
    pad = x_ref.shape[0] - P
    x = x_ref[...][:P]
    own = own_ref[...][:P]
    flow = flow_ref[...][:P]
    nbr = nbr_ref[...][:P]
    mask = mask_ref[...][:P]
    rev = rev_ref[...][:P]
    alpha, tol, res0 = fscal_ref[0], fscal_ref[1], fscal_ref[2]
    it0, max_iters, stall0 = iscal_ref[0], iscal_ref[1], iscal_ref[2]

    body = sweep_chunk_body(reference_sweep, nbr, mask, rev, alpha,
                            single_hop, tol, max_iters)
    x, own, flow, it, res, stall = jax.lax.fori_loop(
        0, n_sweeps, body, (x, own, flow, it0, res0, stall0))

    x_out_ref[...] = jnp.pad(x, (0, pad))
    own_out_ref[...] = jnp.pad(own, (0, pad))
    flow_out_ref[...] = jnp.pad(flow, ((0, pad), (0, 0)))
    fstat_ref[...] = res[None]
    istat_ref[...] = jnp.stack([it, stall])


@functools.partial(
    jax.jit,
    static_argnames=("n_sweeps", "single_hop", "interpret"),
)
def diffusion_nsweeps_pallas(
    x: jax.Array,          # (P,) f32 current virtual loads
    own: jax.Array,        # (P,) f32 remaining own (originating) load
    flow: jax.Array,       # (P, K) f32 accumulated net flow (carried)
    it: jax.Array,         # scalar i32 sweeps executed so far
    res: jax.Array,        # scalar f32 current neighborhood residual
    stall: jax.Array,      # scalar i32 consecutive stalled sweeps
    nbr_idx: jax.Array,    # (P, K) i32, -1 padded
    nbr_mask: jax.Array,   # (P, K) bool
    rev: jax.Array,        # (P, K) i32 reverse slots
    alpha,
    *,
    n_sweeps: int,
    single_hop: bool = True,
    tol=0.02,
    max_iters=512,
    interpret: bool = False,
):
    """Fused S-sweep block.  Returns the updated
    ``(x, own, flow, it, res, stall)`` carry — the ``chunk_fn`` contract of
    ``core.virtual_lb.virtual_balance`` (see :func:`reference_nsweeps`)."""
    P, K = nbr_idx.shape
    Pp = -(-P // 8) * 8                       # f32 sublane alignment
    xp = _pad_to(x.astype(jnp.float32), Pp)
    ownp = _pad_to(own.astype(jnp.float32), Pp)
    flowp = _pad_to(flow.astype(jnp.float32), Pp)
    nbrp = _pad_to(nbr_idx, Pp)
    maskp = _pad_to(nbr_mask, Pp)
    revp = _pad_to(rev, Pp)
    fscal = jnp.stack([jnp.float32(alpha), jnp.float32(tol),
                       jnp.float32(res)])
    iscal = jnp.stack([jnp.int32(it), jnp.int32(max_iters),
                       jnp.int32(stall)])

    # no grid: one program, every operand fully VMEM-resident for the block
    x_new, own_new, flow_new, fstat, istat = pl.pallas_call(
        functools.partial(_nsweeps_kernel, n_sweeps=n_sweeps,
                          single_hop=single_hop, P=P),
        out_shape=[
            jax.ShapeDtypeStruct((Pp,), jnp.float32),
            jax.ShapeDtypeStruct((Pp,), jnp.float32),
            jax.ShapeDtypeStruct((Pp, K), jnp.float32),
            jax.ShapeDtypeStruct((1,), jnp.float32),
            jax.ShapeDtypeStruct((2,), jnp.int32),
        ],
        interpret=interpret,
    )(xp, ownp, flowp, nbrp, maskp, revp, fscal, iscal)

    return (x_new[:P], own_new[:P], flow_new[:P],
            istat[0], fstat[0], istat[1])
