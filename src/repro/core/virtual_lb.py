"""Stage 2 — virtual load balancing (paper §III.B).

First-order diffusion (Cybenko [3], Hu-Blake [15]) restricted to the stage-1
neighbor graph.  Only load *magnitudes* are exchanged; the output is the
per-edge net transfer each node should realize with objects in stage 3.

Paper constraint — **single-hop migrations**: load received during the
iteration is frozen (it may not be re-sent), so every unit of transferred
load traverses exactly one edge from its originating node.  This is the
default; ``single_hop=False`` gives the classic unconstrained scheme.

Representation: padded neighbor lists ``nbr_idx/nbr_mask (P, K)``.  Because
the graph is symmetric, "receive" is also a gather: node i receives from
neighbor j exactly what j's row pushed toward i, located via the precomputed
reverse-slot table.  This keeps the sweep gather-only (no scatters), which is
what the Pallas kernel (kernels/diffusion) exploits.

The inner sweep is pluggable: ``step_fn=None`` uses the pure-jnp reference.

The fixed-point loop runs in *chunks*: ``virtual_balance`` is a
``jax.lax.while_loop`` over ``sweep_chunk``-sweep blocks, each block
applying up to S masked sweeps with per-sweep early exit
(:func:`reference_nsweeps`).  Chunk granularity changes only how often the
host-visible loop condition is evaluated — the per-sweep activity mask
replicates the per-sweep ``while_loop`` decisions exactly, so results are
bit-for-bit independent of ``sweep_chunk``.  ``chunk_fn`` swaps in a fused
implementation of the whole S-sweep block (the production path passes
``kernels.diffusion.ops.diffusion_nsweeps``).
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp


class VirtualLBResult(NamedTuple):
    target_loads: jax.Array  # (P,) converged virtual node loads
    flows: jax.Array         # (P, K) net load to send to each neighbor (+=send)
    iters: jax.Array         # scalar i32
    residual: jax.Array      # scalar f32 — final neighborhood imbalance


def reverse_slots(nbr_idx: jax.Array, nbr_mask: jax.Array) -> jax.Array:
    """(P, K) i32: rev[i, k] = slot of node i in the list of nbr_idx[i, k].

    Defined only where nbr_mask; padded slots get 0 (masked out by callers).
    """
    j = jnp.where(nbr_mask, nbr_idx, 0)                 # (P, K)
    their_lists = nbr_idx[j]                            # (P, K, K)
    me = jnp.arange(nbr_idx.shape[0])[:, None, None]
    hit = their_lists == me                             # (P, K, K)
    return jnp.where(nbr_mask, jnp.argmax(hit, axis=-1), 0).astype(jnp.int32)


def reference_sweep(x, own, nbr_idx, nbr_mask, rev, alpha, single_hop):
    """One diffusion sweep.  Returns (x_new, own_new, net_flow_step (P,K)).

    Pure-jnp oracle for the Pallas kernel (kernels/diffusion/ref.py re-exports
    this).  Gather-only; see module docstring.
    """
    # gathers use the flattened jnp.take(..., mode="clip") forms the Pallas
    # kernels share (see kernels/diffusion); a gather copies elements
    # exactly, so this is bit-identical to advanced indexing
    safe_nbr = jnp.where(nbr_mask, nbr_idx, 0)
    xn = jnp.where(nbr_mask, jnp.take(x, safe_nbr, axis=0, mode="clip"),
                   x[:, None])
    push = jnp.maximum(alpha * (x[:, None] - xn), 0.0) * nbr_mask
    if single_hop:
        tot = push.sum(axis=1)
        scale = jnp.where(tot > 0, jnp.minimum(1.0, own / (tot + 1e-30)), 1.0)
        push = push * scale[:, None]
    # recv[i, k]: what neighbor j = nbr_idx[i,k] pushed toward i this sweep.
    K = nbr_idx.shape[1]
    flat = safe_nbr * K + jnp.where(nbr_mask, rev, 0)
    recv = jnp.where(
        nbr_mask, jnp.take(push.reshape(-1), flat, mode="clip"), 0.0)
    x_new = x - push.sum(axis=1) + recv.sum(axis=1)
    own_new = own - push.sum(axis=1)
    return x_new, own_new, push - recv


def sweep_chunk_body(sweep, nbr_idx, nbr_mask, rev, alpha, single_hop,
                     tol, max_iters, *, residual_fn=None, sum_fn=None,
                     mean_abs_fn=None):
    """``(i, carry) -> carry`` applying one masked early-exit sweep.

    ``carry = (x, own, flow, it, res, stall)``.  The activity predicate is
    the same one the outer fixed-point loop checks, evaluated *before* the
    sweep — once it goes false mid-chunk the state passes through
    unchanged, so a chunk of S masked sweeps is bit-for-bit equal to S
    steps of the per-sweep ``while_loop``.  Shared by the pure-jnp chunk
    (:func:`reference_nsweeps`) and the fused Pallas kernel
    (``kernels/diffusion/kernel.py``), which keeps the two paths
    semantically identical by construction.

    The three reduction hooks default to the local (single-device) forms;
    the mesh-sharded planner (``distributed/lb_shard.py``) passes
    collective equivalents (``psum``/``pmax`` over the node shards) so the
    early-exit and stall decisions — the only global state in the loop —
    are made on the same quantities, keeping the sharded and single-device
    iteration counts identical by construction.
    """
    if residual_fn is None:
        residual_fn = lambda x2: neighborhood_residual(  # noqa: E731
            x2, nbr_idx, nbr_mask)
    if sum_fn is None:
        sum_fn = lambda v: v.sum()                       # noqa: E731
    if mean_abs_fn is None:
        mean_abs_fn = lambda x2: jnp.abs(x2).mean()      # noqa: E731

    def body(_, carry):
        x, own, flow, it, res, stall = carry
        active = (it < max_iters) & (res > tol) & (stall < 3)
        x2, own2, df = sweep(x, own, nbr_idx, nbr_mask, rev, alpha,
                             single_hop)
        moved = sum_fn(jnp.abs(x2 - x))
        stalled = moved <= 1e-6 * (mean_abs_fn(x2) + 1e-30)
        res2 = residual_fn(x2)

        def keep(new, old):
            return jnp.where(active, new, old)

        return (keep(x2, x), keep(own2, own), keep(flow + df, flow),
                keep(it + 1, it), keep(res2, res),
                keep(jnp.where(stalled, stall + 1, jnp.int32(0)), stall))

    return body


def reference_nsweeps(x, own, flow, it, res, stall, nbr_idx, nbr_mask, rev,
                      alpha, *, n_sweeps: int, single_hop: bool, tol,
                      max_iters, step_fn: Optional[Callable] = None):
    """Pure-jnp S-sweep chunk with per-sweep early exit.

    The CPU production path and the oracle for the fused Pallas kernel
    (``diffusion_nsweeps_pallas``).  Returns the updated
    ``(x, own, flow, it, res, stall)`` carry.
    """
    body = sweep_chunk_body(step_fn or reference_sweep, nbr_idx, nbr_mask,
                            rev, alpha, single_hop, tol, max_iters)
    return jax.lax.fori_loop(0, n_sweeps, body,
                             (x, own, flow, it, res, stall))


def neighborhood_deviation(x, xn, nbr_mask):
    """(P,) max |load - neighborhood mean| over {i}∪N(i), given the
    *pre-gathered* neighbor loads ``xn`` (P, K).

    The local core of :func:`neighborhood_residual`, shared with the
    mesh-sharded planner (``distributed/lb_shard.py``), whose ``xn``
    arrives via the ppermute halo ring — keeping the two residuals
    identical by construction."""
    allx = jnp.concatenate([x[:, None], xn], axis=1)       # (P, K+1)
    m = jnp.concatenate([jnp.ones_like(x[:, None], bool), nbr_mask], axis=1)
    cnt = m.sum(axis=1)
    mean = jnp.where(cnt > 0, (allx * m).sum(axis=1) / cnt, x)
    return jnp.where(m, jnp.abs(allx - mean[:, None]), 0.0).max(axis=1)


def neighborhood_residual(x, nbr_idx, nbr_mask):
    """max over nodes of (max deviation in {i}∪N(i)) / global mean load."""
    safe_nbr = jnp.where(nbr_mask, nbr_idx, 0)
    xn = jnp.where(nbr_mask, jnp.take(x, safe_nbr, axis=0, mode="clip"),
                   x[:, None])
    dev = neighborhood_deviation(x, xn, nbr_mask)
    gmean = x.mean() + 1e-30
    return (dev / gmean).max()


@functools.partial(
    jax.jit,
    static_argnames=("max_iters", "single_hop", "step_fn", "sweep_chunk",
                     "chunk_fn"),
)
def virtual_balance(
    node_loads: jax.Array,
    nbr_idx: jax.Array,
    nbr_mask: jax.Array,
    *,
    alpha: Optional[float] = None,
    tol: float = 0.02,
    max_iters: int = 512,
    single_hop: bool = True,
    step_fn: Optional[Callable] = None,
    sweep_chunk: int = 8,
    chunk_fn: Optional[Callable] = None,
) -> VirtualLBResult:
    """Iterate diffusion sweeps until every neighborhood is balanced.

    Args:
      node_loads: (P,) current per-node load.
      nbr_idx / nbr_mask: (P, K) stage-1 neighbor table.
      alpha: diffusion coefficient; default 1/(K+1) (stable first-order
        scheme for max degree K).
      tol: convergence threshold on max neighborhood deviation / mean load
        (the paper's "load variance in each neighborhood below a threshold").
      single_hop: freeze received load (paper default).
      step_fn: sweep implementation (defaults to :func:`reference_sweep`);
        used only when ``chunk_fn`` is None.
      sweep_chunk: sweeps per ``while_loop`` body (S).  Results are
        bit-for-bit independent of this value (per-sweep activity mask);
        larger chunks amortize the loop condition and, with a fused
        ``chunk_fn``, the HBM table traffic.
      chunk_fn: fused S-sweep block implementation with the
        :func:`reference_nsweeps` signature (minus ``step_fn``).  The
        production path passes ``kernels.diffusion.ops.diffusion_nsweeps``.
    """
    P, K = nbr_idx.shape
    if alpha is None:
        alpha = 1.0 / (K + 1.0)
    alpha = jnp.float32(alpha)
    rev = reverse_slots(nbr_idx, nbr_mask)
    n_sweeps = max(1, min(int(sweep_chunk), int(max_iters)))
    if chunk_fn is None:
        chunk_fn = functools.partial(reference_nsweeps, step_fn=step_fn)

    class S(NamedTuple):
        x: jax.Array
        own: jax.Array
        flows: jax.Array
        it: jax.Array
        res: jax.Array
        stall: jax.Array   # consecutive sweeps with negligible load movement

    def cond(s: S):
        # Stop on convergence, iteration cap, or stall: under the single-hop
        # constraint the scheme can freeze (all "own" load spent) while the
        # residual is still above tol — further sweeps are no-ops.
        return (s.it < max_iters) & (s.res > tol) & (s.stall < 3)

    def body(s: S):
        return S(*chunk_fn(
            s.x, s.own, s.flows, s.it, s.res, s.stall,
            nbr_idx, nbr_mask, rev, alpha,
            n_sweeps=n_sweeps, single_hop=single_hop, tol=tol,
            max_iters=max_iters,
        ))

    x0 = node_loads.astype(jnp.float32)
    init = S(
        x0, x0, jnp.zeros_like(nbr_mask, jnp.float32), jnp.int32(0),
        neighborhood_residual(x0, nbr_idx, nbr_mask), jnp.int32(0),
    )
    s = jax.lax.while_loop(cond, body, init)
    return VirtualLBResult(s.x, s.flows, s.it, s.res)
