"""Mesh-sharded distributed LB planner — the balancer as it actually runs.

The paper's balancer is distributed by construction (§III, §V–VI): each
of the P nodes exchanges load only with its stage-1 graph neighbors.  The
single-device ``LBEngine`` realizes the same fixed point with dense
arrays on one chip; this module executes it **across a JAX device mesh**
(``shard_map`` over a 1-D ``"lb"`` axis), with the P balancer nodes
row-sharded over the mesh:

  * **stage 2 (virtual diffusion)** — the hot loop.  Per-node state
    (loads ``x``, frozen ``own`` budget, ``(P, K)`` flow accumulators)
    lives sharded; each sweep's neighbor reads are **ring halo
    exchanges**: the local block rotates around the mesh via
    ``lax.ppermute`` (D-1 hops) and every shard takes exactly the entries
    its neighbor table points at as they pass — O(P/D) working set per
    hop, no global all-gather of the load vector.  Gathers copy values
    exactly, so each sharded sweep is bit-for-bit the reference sweep.
    The loop-control scalars (residual, movement, stall) are completed
    with ``psum``/``pmax``, through the *same* masked chunk body as the
    single-device path (``virtual_lb.sweep_chunk_body`` with collective
    reduction hooks), so the iteration counts agree by construction.
  * **stage 1 (neighbor selection)** — the O(E) reduction that builds the
    node-communication matrix runs on the **edge shards** and is
    completed with a ``psum``; likewise the (N,)-object load reduction
    feeding stage 2.  The handshake protocol itself then runs replicated
    on every shard (its state is O(P²) *bits* and P protocol rounds are
    cheap — the paper's asynchronous protocol is not the scaling
    bottleneck; the per-edge preference assembly is).
  * **stage 3 (object selection)** — the per-phase object↔target comm
    scores (an O(E) segment reduction per direction) run on the edge
    shards and are ``psum``-completed inside
    ``object_selection.select_objects`` (``score_psum_axis``); the
    take-while selection over the scored objects is replicated.

Numerical parity: all data movement (gathers, ppermute, all_gather) is
exact, and control flow is shared with the single-device engine, so the
only divergence source is **floating-point reassociation of psum'd
reductions** (a psum of per-shard partial sums orders additions
differently from one flat segment-sum).  With integer-valued edge bytes
and loads (every stencil workload) the sums are exact in f32 and the
sharded plan matches ``LBEngine.plan_fn`` **bit-for-bit**; otherwise it
is within a few ulps on the flows and virtually always identical in the
final assignments (tests/test_lb_shard.py asserts exact assignment
equality on an 8-virtual-device CPU mesh).

Run on a CPU mesh of 8 virtual devices with::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 python ...

``diff-comm-sharded`` / ``diff-coord-sharded`` are registered as
strategies (host-eager: they carry their own mesh), so the PIC driver
and the benchmarks can plan with genuinely distributed execution.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P_

from repro.core import comm_graph, hierarchical
from repro.core import engine as core_engine
from repro.core import neighbor_selection as ns
from repro.core import object_selection as osel
from repro.core import virtual_lb as vlb

AXIS = "lb"


# ------------------------------------------------------- halo primitives --


def _ring_gather_values(vec_local, owner, idx_local, axis: str, D: int):
    """Gather ``vec[global]`` from a row-sharded flat vector via a
    ``ppermute`` ring.

    ``vec_local`` is this shard's (m,) block of the global vector;
    ``owner``/``idx_local`` (any shape, i32) name the shard and in-shard
    position of every wanted entry.  The block rotates D-1 hops around
    the ring; each shard takes the entries it needs as the owning block
    passes.  Pure data movement — every output element is an exact copy.
    """
    me = jax.lax.axis_index(axis)
    out = jnp.zeros(owner.shape, vec_local.dtype)
    buf = vec_local
    safe = jnp.clip(idx_local, 0, vec_local.shape[0] - 1)
    for s in range(D):
        vals = jnp.take(buf, safe, mode="clip")
        out = jnp.where(owner == (me + s) % D, vals, out)
        if s + 1 < D:
            # buf becomes the block of the next shard around the ring
            buf = jax.lax.ppermute(
                buf, axis, [(d, (d - 1) % D) for d in range(D)])
    return out


def _sharded_sweep_fn(axis: str, D: int, rpd: int):
    """One diffusion sweep over the local row block — the sharded twin of
    ``virtual_lb.reference_sweep`` (same math per row; neighbor loads and
    push-back values arrive via the ppermute ring instead of a local
    gather).  Signature matches the ``sweep`` slot of
    ``virtual_lb.sweep_chunk_body``."""

    def sweep(x, own, nbr_idx, nbr_mask, rev, alpha, single_hop):
        safe_nbr = jnp.where(nbr_mask, nbr_idx, 0)
        owner = safe_nbr // rpd
        xn = jnp.where(
            nbr_mask,
            _ring_gather_values(x, owner, safe_nbr % rpd, axis, D),
            x[:, None])
        push = jnp.maximum(alpha * (x[:, None] - xn), 0.0) * nbr_mask
        if single_hop:
            tot = push.sum(axis=1)
            scale = jnp.where(
                tot > 0, jnp.minimum(1.0, own / (tot + 1e-30)), 1.0)
            push = push * scale[:, None]
        # recv[i, k]: what neighbor j pushed toward i — entry
        # [j % rpd, rev] of j's shard of the (P, K) push table
        K = nbr_idx.shape[1]
        flat_local = (safe_nbr % rpd) * K + jnp.where(nbr_mask, rev, 0)
        recv = jnp.where(
            nbr_mask,
            _ring_gather_values(push.reshape(-1), owner, flat_local,
                                axis, D),
            0.0)
        x_new = x - push.sum(axis=1) + recv.sum(axis=1)
        own_new = own - push.sum(axis=1)
        return x_new, own_new, push - recv

    return sweep


def _sharded_residual_fn(nbr_loc, mask_loc, axis: str, D: int, rpd: int,
                         P: int):
    """Sharded twin of ``virtual_lb.neighborhood_residual``: per-row
    deviations are local once the halo ring delivers the neighbor loads;
    the global mean and max complete with psum/pmax."""

    def residual(x):
        safe_nbr = jnp.where(mask_loc, nbr_loc, 0)
        owner = safe_nbr // rpd
        xn = jnp.where(
            mask_loc,
            _ring_gather_values(x, owner, safe_nbr % rpd, axis, D),
            x[:, None])
        dev = vlb.neighborhood_deviation(x, xn, mask_loc)
        gmean = jax.lax.psum(x.sum(), axis) / P + 1e-30
        return jax.lax.pmax((dev / gmean).max(), axis)

    return residual


# ----------------------------------------------------------- plan body --


def _plan_body(loads_sh, assign_sh, loads, assignment, coords,
               e_src, e_dst, e_bytes, *, variant: str, k: int, tol: float,
               max_iters: int, max_rounds: int, single_hop: bool,
               sweep_chunk: int, P: int, D: int, axis: str):
    """Per-shard planning body (runs under ``shard_map``).

    ``loads_sh``/``assign_sh`` are object shards (padded with zero-load
    objects), ``e_*`` are edge shards (padded with the standard
    ``(-1, -1, 0.0)``), ``loads``/``assignment``/``coords`` replicated.
    Returns a replicated ``(assignment, PlanStats)``.
    """
    rpd = P // D

    # -- stage 1: preference assembly on the edge shards (psum) ---------
    valid = e_src >= 0
    src_n = jnp.where(valid, assignment[jnp.where(valid, e_src, 0)], 0)
    dst_n = jnp.where(valid, assignment[jnp.where(valid, e_dst, 0)], 0)
    w = jnp.where(valid, e_bytes, 0.0)
    m_part = jax.ops.segment_sum(
        w, src_n * P + dst_n, num_segments=P * P).reshape(P, P)
    node_comm = jax.lax.psum(m_part, axis)
    node_comm = node_comm + node_comm.T
    if variant == "comm":
        pref = ns.comm_preference(node_comm)
    else:
        cent = osel.centroids(coords, assignment, P)
        pref = ns.coordinate_preference(cent)
    # the handshake itself is replicated compute: O(P^2) bits of protocol
    # state, identical on every shard (deterministic), sliced per shard
    # below for the sharded diffusion loop
    nres = ns.select_neighbors(pref, k=k, max_rounds=max_rounds)
    rev = vlb.reverse_slots(nres.nbr_idx, nres.nbr_mask)

    # -- stage 2: sharded virtual diffusion -----------------------------
    nl_part = jax.ops.segment_sum(loads_sh, assign_sh, num_segments=P)
    nloads = jax.lax.psum(nl_part, axis)                    # (P,)
    me = jax.lax.axis_index(axis)
    sl = me * rpd
    x0 = jax.lax.dynamic_slice(nloads.astype(jnp.float32), (sl,), (rpd,))
    nbr_loc = jax.lax.dynamic_slice(nres.nbr_idx, (sl, 0),
                                    (rpd, nres.nbr_idx.shape[1]))
    mask_loc = jax.lax.dynamic_slice(nres.nbr_mask, (sl, 0),
                                     (rpd, nres.nbr_mask.shape[1]))
    rev_loc = jax.lax.dynamic_slice(rev, (sl, 0), (rpd, rev.shape[1]))

    K = nres.nbr_idx.shape[1]
    alpha = jnp.float32(1.0 / (K + 1.0))        # virtual_balance default
    n_sweeps = max(1, min(int(sweep_chunk), int(max_iters)))
    residual = _sharded_residual_fn(nbr_loc, mask_loc, axis, D, rpd, P)
    chunk_body = vlb.sweep_chunk_body(
        _sharded_sweep_fn(axis, D, rpd), nbr_loc, mask_loc, rev_loc,
        alpha, single_hop, tol, max_iters,
        residual_fn=residual,
        sum_fn=lambda v: jax.lax.psum(v.sum(), axis),
        mean_abs_fn=lambda x2: jax.lax.psum(jnp.abs(x2).sum(), axis) / P)

    def cond(s):
        _, _, _, it, res, stall = s
        return (it < max_iters) & (res > tol) & (stall < 3)

    def body(s):
        return jax.lax.fori_loop(0, n_sweeps, chunk_body, s)

    init = (x0, x0, jnp.zeros((rpd, K), jnp.float32), jnp.int32(0),
            residual(x0), jnp.int32(0))
    x_fin, _own, flows_loc, iters, res_fin, _stall = jax.lax.while_loop(
        cond, body, init)

    # -- stage 3: selection with edge-sharded scores --------------------
    flows = jax.lax.all_gather(flows_loc, axis, tiled=True)   # (P, K)
    problem_loc = comm_graph.LBProblem(
        loads=loads, assignment=assignment, edges_src=e_src,
        edges_dst=e_dst, edges_bytes=e_bytes, num_nodes=P,
        coords=None if variant == "comm" else coords)
    sres = osel.select_objects(
        problem_loc, nres.nbr_idx, nres.nbr_mask, flows,
        metric="comm" if variant == "comm" else "coord",
        score_psum_axis=axis)

    stats = core_engine.PlanStats(
        protocol_rounds=nres.rounds.astype(jnp.int32),
        mean_degree=jnp.mean(nres.degree.astype(jnp.float32)),
        diffusion_iters=iters.astype(jnp.int32),
        diffusion_residual=res_fin.astype(jnp.float32),
        unrealized_flow=jnp.abs(sres.residual).sum().astype(jnp.float32),
    )
    return sres.assignment.astype(jnp.int32), stats


# -------------------------------------------------------------- engine --


def _pad_to(a, n, fill):
    return jnp.pad(a, [(0, n - a.shape[0])] + [(0, 0)] * (a.ndim - 1),
                   constant_values=fill)


class ShardedLBEngine:
    """The three-stage planner executed across a device mesh.

    Mirrors :class:`repro.core.engine.LBEngine`'s interface (``plan_fn``
    traceable, ``plan`` eager, optional ``threads_per_node`` fourth
    stage) with the P balancer nodes sharded over a 1-D mesh.  Requires
    ``P % num_shards == 0``; edge and object arrays are padded to the
    shard multiple internally (standard padding conventions, masked
    everywhere).
    """

    def __init__(
        self,
        *,
        mesh: Optional[Mesh] = None,
        num_shards: Optional[int] = None,
        variant: str = "comm",
        k: int = 4,
        tol: float = 0.02,
        max_iters: int = 512,
        max_rounds: int = 64,
        single_hop: bool = True,
        sweep_chunk: int = 8,
        threads_per_node: Optional[int] = None,
    ):
        if variant not in ("comm", "coord"):
            raise ValueError(f"unknown variant {variant!r}")
        if mesh is None:
            devs = jax.devices()
            if num_shards is not None:
                if not 1 <= num_shards <= len(devs):
                    raise ValueError(
                        f"num_shards={num_shards} outside "
                        f"[1, {len(devs)}] available devices")
                devs = devs[:num_shards]
            mesh = Mesh(np.asarray(devs), (AXIS,))
        elif num_shards is not None:
            raise ValueError("pass either mesh or num_shards, not both")
        if len(mesh.axis_names) != 1:
            raise ValueError("ShardedLBEngine needs a 1-D mesh")
        self.mesh = mesh
        self.axis_name = mesh.axis_names[0]
        self.num_shards = int(np.prod(mesh.devices.shape))
        self.variant = variant
        self.k = int(k)
        self.tol = float(tol)
        self.max_iters = int(max_iters)
        self.max_rounds = int(max_rounds)
        self.single_hop = bool(single_hop)
        self.sweep_chunk = int(sweep_chunk)
        self.threads_per_node = (None if threads_per_node is None
                                 else int(threads_per_node))
        self._jitted = jax.jit(self.plan_fn)
        self._jitted_hier = (jax.jit(self.plan_hier_fn)
                             if self.threads_per_node else None)

    # ------------------------------------------------------ traced path --

    def plan_fn(
        self, problem: comm_graph.LBProblem
    ) -> Tuple[jax.Array, core_engine.PlanStats]:
        """Sharded neighbor selection → diffusion → selection.

        Traceable; one ``shard_map`` call over the engine's mesh.  Output
        matches ``LBEngine.plan_fn`` (see module docstring for the fp
        parity contract)."""
        P = problem.num_nodes
        D = self.num_shards
        ax = self.axis_name
        if P % D:
            raise ValueError(
                f"num_nodes={P} must divide over the {D}-device mesh")
        if self.variant == "coord" and problem.coords is None:
            raise ValueError("coordinate variant needs coords")

        loads = jnp.asarray(problem.loads, jnp.float32)
        assignment = jnp.asarray(problem.assignment, jnp.int32)
        e_src = jnp.asarray(problem.edges_src, jnp.int32)
        e_dst = jnp.asarray(problem.edges_dst, jnp.int32)
        e_bytes = jnp.asarray(problem.edges_bytes, jnp.float32)
        N, E = loads.shape[0], e_src.shape[0]
        Np, Ep = -(-N // D) * D, -(-E // D) * D
        # object pad: zero-load objects on node 0 contribute nothing to
        # the psum'd load reduction; edge pad is the standard convention
        loads_sh = _pad_to(loads, Np, 0.0)
        assign_sh = _pad_to(assignment, Np, 0)
        coords = (jnp.zeros((1, 1), jnp.float32) if problem.coords is None
                  else jnp.asarray(problem.coords, jnp.float32))

        body = functools.partial(
            _plan_body, variant=self.variant, k=self.k, tol=self.tol,
            max_iters=self.max_iters, max_rounds=self.max_rounds,
            single_hop=self.single_hop, sweep_chunk=self.sweep_chunk,
            P=P, D=D, axis=ax)
        fn = jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(P_(ax), P_(ax), P_(), P_(), P_(),
                      P_(ax), P_(ax), P_(ax)),
            out_specs=(P_(), P_()),
            check_vma=False)
        return fn(loads_sh, assign_sh, loads, assignment, coords,
                  _pad_to(e_src, Ep, -1), _pad_to(e_dst, Ep, -1),
                  _pad_to(e_bytes, Ep, 0.0))

    def plan_hier_fn(
        self, problem: comm_graph.LBProblem
    ) -> Tuple[jax.Array, jax.Array, core_engine.PlanStats]:
        """Sharded plan + within-node LPT (replicated — §III.D is
        thread-local refinement).  Same contract as
        ``LBEngine.plan_hier_fn``."""
        if not self.threads_per_node:
            raise ValueError(
                "plan_hier_fn needs threads_per_node configured")
        assignment, stats = self.plan_fn(problem)
        thread = hierarchical.lpt_threads(
            problem.loads, assignment, num_nodes=problem.num_nodes,
            threads_per_node=self.threads_per_node)
        return assignment, thread, stats

    # ---------------------------------------------------- sharded apply --

    def apply(self, owner_new, arrays, *, num_nodes: int,
              capacity: Optional[int] = None,
              on_overflow: str = "strict"):
        """Execute a plan across this engine's mesh: relocate per-item
        payload between the shard-owned slot regions.

        ``owner_new`` is the (n,) post-plan node id per item (e.g.
        ``assignment[chare_id]`` per particle); ``arrays`` are the
        row-sharded payload buffers; ``num_nodes`` is the planner's P
        (must divide the mesh, like :meth:`plan_fn`).  Delegates to
        ``runtime.migrate.migrate_sharded`` — a ``ppermute`` ring
        all-to-all whose concatenated valid prefixes reproduce the
        single-device bucketed layout bit-for-bit.  ``capacity`` is the
        static per-shard slot budget; the ``None`` default sizes it
        from the plan's own max per-shard inflow
        (``runtime.migrate.planned_capacity``).  ``on_overflow`` picks
        the degradation mode for an undersized budget: ``"strict"``
        raises the structured ``CapacityOverflowError``; ``"spill"``
        clamps per-shard inflow, keeps overflow items on their source
        shard and additionally returns the deferred count (see
        ``runtime.migrate.migrate_sharded``)."""
        from repro.runtime import migrate as rt_migrate

        return rt_migrate.migrate_sharded(
            owner_new, arrays, num_nodes=num_nodes, mesh=self.mesh,
            capacity=capacity, on_overflow=on_overflow)

    # -------------------------------------------------------- host path --

    def plan(self, problem: comm_graph.LBProblem):
        """Eager plan with wall-clock timing and the legacy info dict."""
        return core_engine.eager_plan(
            self, problem, f"diff-{self.variant}-sharded",
            extra_info=dict(num_shards=self.num_shards))


# --------------------------------------------------------------- cache --


_SHARDED_CACHE: Dict[tuple, ShardedLBEngine] = {}
_SHARDED_CACHE_MAX = 16   # each entry pins a Mesh + compiled executables


def get_sharded_engine(*, mesh: Optional[Mesh] = None,
                       **cfg) -> ShardedLBEngine:
    """Sharded-engine cache (canonical key, like ``engine.get_engine``).

    Only default-mesh engines are cached — the key includes the current
    device count, so a re-run under different ``XLA_FLAGS`` rebuilds.  An
    explicit ``mesh`` constructs uncached."""
    if mesh is not None:
        return ShardedLBEngine(mesh=mesh, **cfg)
    defaults = dict(num_shards=None, variant="comm", k=4, tol=0.02,
                    max_iters=512, max_rounds=64, single_hop=True,
                    sweep_chunk=8, threads_per_node=None)
    unknown = set(cfg) - set(defaults)
    if unknown:
        raise TypeError(
            f"get_sharded_engine() got unexpected keyword arguments "
            f"{sorted(unknown)}")
    c = {**defaults, **cfg}
    key = (len(jax.devices()),
           None if c["num_shards"] is None else int(c["num_shards"]),
           str(c["variant"]), int(c["k"]),
           float(c["tol"]), int(c["max_iters"]), int(c["max_rounds"]),
           bool(c["single_hop"]), int(c["sweep_chunk"]),
           None if c["threads_per_node"] is None
           else int(c["threads_per_node"]))
    eng = _SHARDED_CACHE.get(key)
    if eng is None:
        eng = _SHARDED_CACHE[key] = ShardedLBEngine(**c)
        while len(_SHARDED_CACHE) > _SHARDED_CACHE_MAX:  # drop oldest
            _SHARDED_CACHE.pop(next(iter(_SHARDED_CACHE)))
    return eng


# ---------------------------------------------------------- strategies --


def best_shards(num_nodes: int) -> int:
    """Largest device count ≤ the available devices dividing ``P`` (the
    row sharding needs ``P % D == 0``; e.g. P=4 on an 8-device mesh runs
    4-way)."""
    D = min(len(jax.devices()), int(num_nodes))
    while num_nodes % D:
        D -= 1
    return D


def _sharded_plan_fn(variant: str):
    def plan_fn(problem, **params):
        params.setdefault("num_shards", best_shards(problem.num_nodes))
        return get_sharded_engine(variant=variant, **params)._jitted(problem)
    return plan_fn


# jittable=False: the sharded planner carries its own mesh and is meant
# to be dispatched eagerly (the replay layers' scanned paths keep using
# the single-device engine; the two agree — that is the parity test)
core_engine.register(core_engine.Strategy(
    "diff-comm-sharded", _sharded_plan_fn("comm"), jittable=False,
    variant="comm"))
core_engine.register(core_engine.Strategy(
    "diff-coord-sharded", _sharded_plan_fn("coord"), jittable=False,
    variant="coord"))
