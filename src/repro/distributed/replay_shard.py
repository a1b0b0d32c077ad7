"""Sharded replay runtime: the whole step loop in one ``shard_map``.

The paper's headline results are end-to-end distributed: diffusion
planning *and* object exchange run on-node across the machine, and the
cost that matters is the coupled step loop, not the planner in isolation
(Demiralp et al., PAPERS.md).  The planner (``ShardedLBEngine``) and the
exchange (``migrate_sharded``) were already mesh-resident — but only as
standalone calls; every replayed trajectory still planned and migrated
single-device.  This module closes that gap: the **entire** simulation
step — workload evolve / PIC particle push, trigger evaluation,
three-stage diffusion planning, and the executed payload exchange — runs
inside a single ``shard_map`` over the 1-D ``"lb"`` mesh, with one
``jax.lax.scan`` carrying the per-shard state (payload slabs, owner
slabs, trigger state) across steps.  Nothing round-trips through the
host or through replicated staging between steps: plan → manifest →
apply compose on the same mesh and axis.

Two entries:

  * :func:`run_series_sharded` — the mesh twin of
    ``sim.simulator.run_series``'s scanned path.  The P balancer nodes
    are row-sharded; each step's stage-2 diffusion runs as ``ppermute``
    ring halo exchanges over O(P/D) rows per shard (the planner's hot
    loop — same sweeps as ``distributed.lb_shard``).
  * :func:`run_pic_sharded` — the mesh twin of the scanned PIC driver
    (``PICConfig(sharded_replay=True)``).  The particle slabs are
    row-sharded: push, handoff counting and the per-chare histogram run
    on the local slab (partial counts completed with exact integer
    ``psum``), and every fired rebalance executes
    ``runtime.migrate.ring_exchange`` — the ``ppermute`` ring
    all-to-all, whose per-shard placement is the shared sort-free
    counting-scatter op (``kernels.migrate.bucket_ranks``) — to
    re-bucket the slabs into PE-owned slot regions *inside the scan*.

Parity contract (the reason this file exists as a *replay* subsystem and
not just a loop around the standalone pieces): both entries are
**bit-for-bit** equal to the single-device scanned paths — identical
per-step metrics, trigger fire steps, migration counts, final
assignments and (PIC) final particle order.  The mechanism:

  * all data movement (``ppermute`` rings, ``all_gather``) copies values
    exactly;
  * every *reduction that feeds a decision or a metric* is evaluated
    with the **same expression graph on the same full-size operands** as
    the single-device path — either on replicated values, or on locally
    exact per-shard values gathered back to full size first.  Float
    ``psum`` of partial sums reassociates additions and is a few-ulp
    hazard (the documented contract of ``lb_shard``'s planner-only
    entry), so the replay's loop-control scalars gather-then-reduce
    instead; the PIC histogram / handoff partial sums are
    integer-valued, where ``psum`` is exact.

Trigger completion: the trigger's ``load_stats`` are computed on the
replicated (C,)/(N,) loads on every shard — identical inputs, identical
expression graph — so all shards fire on identical steps by
construction; the PIC loads themselves are ``psum``-completed exact
integer counts.

Capacity rule (PIC): the scan's payload slabs are static at
``capacity`` slots per shard.  The default is the worst case
``n_particles`` (always safe); production runs size it down with
``PICConfig.replay_capacity`` — the post-hoc overflow check raises
``ValueError`` (payload is never dropped silently), and the eager
``migrate_sharded`` entry can plan the tight per-plan bound via
``runtime.migrate.planned_capacity``.

Run on a CPU mesh of 8 virtual devices with::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 python ...
"""
from __future__ import annotations

import functools
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P_

from repro.distributed import compat
from repro.distributed import lb_shard
from repro.core import comm_graph, metrics
from repro.core import engine as core_engine
from repro.core import neighbor_selection as ns
from repro.core import object_selection as osel
from repro.core import virtual_lb as vlb
from repro.obs import telemetry as obs_telemetry
from repro.runtime import migrate as rt_migrate
from repro.runtime import resilience as rt_resilience
from repro.runtime import triggers as rt_triggers

#: one mesh axis shared by planning halo rings and the payload exchange —
#: the composition contract of the issue ("plan → manifest → apply
#: composes without re-gathering")
AXIS = lb_shard.AXIS

#: static planner configuration a diff-* strategy can carry into the
#: sharded replay (mirrors ``core.engine.LBEngine`` defaults)
_ENGINE_DEFAULTS = dict(k=4, tol=0.02, max_iters=512, max_rounds=64,
                        single_hop=True, sweep_chunk=8)


def _engine_params(strat: core_engine.Strategy,
                   strategy_kwargs: Optional[Dict]) -> Dict:
    """Planner configuration for the sharded twin of ``strat``.

    Merges the strategy's registered defaults under the caller kwargs
    exactly as ``Strategy.bind`` would, then validates against the
    static knobs the sharded planner supports."""
    merged = strat.params(**(strategy_kwargs or {}))
    unknown = sorted(set(merged) - set(_ENGINE_DEFAULTS))
    if unknown:
        raise ValueError(
            f"sharded replay cannot honor strategy kwargs {unknown}; "
            f"supported: {sorted(_ENGINE_DEFAULTS)}")
    out = {**_ENGINE_DEFAULTS, **merged}
    return {k: (bool(v) if k == "single_hop" else
                float(v) if k == "tol" else int(v))
            for k, v in out.items()}


def _resolve_resilience(faults, guard, D: int, strategy: str, trig):
    """Normalize the resilience knobs of a replay entry.

    Empty schedules vanish (``faults=None`` downstream selects the exact
    pre-resilience trace — the bit-parity contract's static elision) and
    ``guard`` defaults to on exactly when a schedule is active.  An
    active schedule needs a live LB strategy (a dead shard's objects can
    only be evacuated by a fired plan) and may only reference shards
    that exist on the mesh."""
    if faults is not None:
        if not isinstance(faults, rt_resilience.FaultSchedule):
            raise TypeError(
                "faults must be a runtime.resilience.FaultSchedule")
        if faults.empty:
            faults = None
    guard = (faults is not None) if guard is None else bool(guard)
    if faults is not None:
        if strategy == "none" or trig.never:
            raise ValueError(
                "fault injection needs an active LB strategy/trigger — "
                "with planning disabled a dead shard's objects can never "
                "be evacuated")
        if faults.max_shard() >= D:
            raise ValueError(
                f"fault schedule references shard {faults.max_shard()} "
                f"but the mesh has only {D} shards")
    return faults, guard


def _series_setup(initial, evolve, strategy: str,
                  strategy_kwargs: Optional[Dict], trigger, lb_every: int,
                  mesh: Optional[Mesh], num_shards: Optional[int],
                  faults, guard):
    """Shared validation/configuration of the series replay entries."""
    strategy_kwargs = strategy_kwargs or {}
    strat = core_engine.get_strategy(strategy)
    if not strat.jittable:
        raise ValueError(
            f"strategy {strategy!r} is not jittable; the sharded replay "
            "needs a traceable plan_fn (diff-* / none)")
    if strategy != "none" and strat.variant is None:
        raise ValueError(
            f"strategy {strategy!r} has no diffusion variant; the "
            "sharded replay can only distribute diff-* strategies")
    if not getattr(evolve, "jittable", False):
        raise ValueError(
            "the sharded replay needs a scan-safe evolve (scenarios from "
            "sim/scenarios.py are)")
    trig = rt_triggers.resolve_for_strategy(trigger, lb_every=lb_every,
                                            strategy=strategy)
    P = initial.num_nodes
    mesh = _resolve_mesh(mesh, num_shards, (P,))
    D = int(np.prod(mesh.devices.shape))
    faults, guard = _resolve_resilience(faults, guard, D, strategy, trig)
    eng = None
    if strategy != "none":
        eng = dict(_engine_params(strat, strategy_kwargs),
                   variant=strat.variant)
    return strategy_kwargs, trig, P, mesh, faults, guard, eng


def _resolve_mesh(mesh: Optional[Mesh], num_shards: Optional[int],
                  must_divide: Tuple[int, ...]) -> Mesh:
    """A 1-D ``"lb"`` mesh whose size divides every extent in
    ``must_divide`` (auto-shrinks to the largest viable device count)."""
    if mesh is not None:
        if num_shards is not None:
            raise ValueError("pass either mesh or num_shards, not both")
        if len(mesh.axis_names) != 1:
            raise ValueError("sharded replay needs a 1-D mesh")
        D = int(np.prod(mesh.devices.shape))
        bad = [m for m in must_divide if m % D]
        if bad:
            raise ValueError(
                f"extents {bad} do not divide over the {D}-device mesh")
        return mesh
    devs = jax.devices()
    if num_shards is not None:
        if not 1 <= num_shards <= len(devs):
            raise ValueError(
                f"num_shards={num_shards} outside [1, {len(devs)}] "
                "available devices")
        bad = [m for m in must_divide if m % num_shards]
        if bad:
            raise ValueError(
                f"extents {bad} do not divide over num_shards="
                f"{num_shards}")
        D = num_shards
    else:
        D = min(len(devs), min(must_divide))
        while any(m % D for m in must_divide):
            D -= 1
    return Mesh(np.asarray(devs[:D]), (AXIS,))


def resolve_mesh(mesh: Optional[Mesh], num_shards: Optional[int],
                 must_divide: Tuple[int, ...]) -> Mesh:
    """Public :func:`_resolve_mesh`: the one place a 1-D ``"lb"`` replay
    mesh is derived from a ``mesh``/``num_shards`` spec.  The serving
    replay (``serve/replay.py``) shares it so its sharded KV exchanges
    ride the same mesh-selection rules as the sim/PIC replays."""
    return _resolve_mesh(mesh, num_shards, must_divide)


# ------------------------------------------------- sharded planning step --


def _plan_step_sharded(problem: comm_graph.LBProblem, *, variant: str,
                       k: int, tol: float, max_iters: int, max_rounds: int,
                       single_hop: bool, sweep_chunk: int, P: int, D: int,
                       axis: str, alive=None, speed=None):
    """One three-stage plan inside the replay's ``shard_map`` body.

    The mesh twin of ``LBEngine.plan_fn`` under the replay's parity
    contract: stage 2 — the hot loop — runs genuinely sharded (O(P/D)
    rows per shard, neighbor loads and push-backs via the ``ppermute``
    halo ring of ``lb_shard``), while the O(E) stage-1/3 reductions and
    the handshake run replicated so every reduction keeps the
    single-device expression graph.  The loop-control scalars
    (residual, movement, stall) **gather-then-reduce** — the ring moved
    exact copies, so evaluating the single-device reduction on the
    gathered (P,) vector keeps every early-exit decision bitwise equal
    to ``LBEngine.plan_fn`` (unlike the planner-only
    ``ShardedLBEngine``, whose ``psum`` completion is documented as a
    few-ulp contract).  Traceable; called under ``lax.cond`` inside the
    replay scan.

    ``alive`` / ``speed`` are the optional (P,) node health mask and
    speed vector of the resilient replay paths (the mesh twin of
    ``LBEngine.plan_health_fn``): dead nodes' objects are re-homed onto
    alive communication partners before planning, slowed nodes' loads
    are scaled by the reciprocal speed, and the stage-1 preference
    rows/columns of dead nodes are zeroed so no flow or object ever
    targets them.  ``alive=None`` (the default) adds nothing to the
    trace."""
    if alive is not None:
        problem = rt_resilience.degrade_problem(problem, alive, speed)
    # -- stage 1: preference assembly + handshake (replicated) ----------
    with compat.named_scope("lb-plan/stage1-neighbors"):
        if variant == "comm":
            node_comm = comm_graph.node_comm_matrix(problem)
            pref = ns.comm_preference(node_comm)
        else:
            cent = osel.centroids(problem.coords, problem.assignment, P)
            pref = ns.coordinate_preference(cent)
        if alive is not None:
            pref = rt_resilience.mask_preference(pref, alive)
        nres = ns.select_neighbors(pref, k=k, max_rounds=max_rounds)
        rev = vlb.reverse_slots(nres.nbr_idx, nres.nbr_mask)

    # -- stage 2: sharded virtual diffusion (the hot loop) --------------
    nloads = comm_graph.node_loads(problem)
    rpd = P // D
    me = jax.lax.axis_index(axis)
    sl = me * rpd
    K = nres.nbr_idx.shape[1]
    x0 = jax.lax.dynamic_slice(nloads.astype(jnp.float32), (sl,), (rpd,))
    nbr_loc = jax.lax.dynamic_slice(nres.nbr_idx, (sl, 0), (rpd, K))
    mask_loc = jax.lax.dynamic_slice(nres.nbr_mask, (sl, 0), (rpd, K))
    rev_loc = jax.lax.dynamic_slice(rev, (sl, 0), (rpd, K))
    alpha = jnp.float32(1.0 / (K + 1.0))        # virtual_balance default
    n_sweeps = max(1, min(int(sweep_chunk), int(max_iters)))

    def gather(v):
        return jax.lax.all_gather(v, axis, tiled=True)

    # exact loop control: per-row sweep state is bitwise the reference
    # sweep (gathers copy exactly), so reducing the *gathered* full
    # vector with the single-device expressions reproduces
    # virtual_balance's early-exit/stall decisions bit-for-bit
    def residual_fn(x_loc):
        return vlb.neighborhood_residual(gather(x_loc), nres.nbr_idx,
                                         nres.nbr_mask)

    chunk_body = vlb.sweep_chunk_body(
        lb_shard._sharded_sweep_fn(axis, D, rpd), nbr_loc, mask_loc,
        rev_loc, alpha, single_hop, tol, max_iters,
        residual_fn=residual_fn,
        sum_fn=lambda v: gather(v).sum(),
        mean_abs_fn=lambda x2: jnp.abs(gather(x2)).mean())

    def cond(s):
        _, _, _, it, res, stall = s
        return (it < max_iters) & (res > tol) & (stall < 3)

    def body(s):
        return jax.lax.fori_loop(0, n_sweeps, chunk_body, s)

    init = (x0, x0, jnp.zeros((rpd, K), jnp.float32), jnp.int32(0),
            residual_fn(x0), jnp.int32(0))
    with compat.named_scope("lb-plan/stage2-diffusion"):
        _x_fin, _own, flows_loc, iters, res_fin, _stall = \
            jax.lax.while_loop(cond, body, init)

    # -- stage 3: selection on the gathered flows (replicated) ----------
    with compat.named_scope("lb-plan/stage3-objects"):
        flows = gather(flows_loc)                            # (P, K) exact
        sres = osel.select_objects(
            problem, nres.nbr_idx, nres.nbr_mask, flows,
            metric="comm" if variant == "comm" else "coord")

    stats = core_engine.PlanStats(
        protocol_rounds=nres.rounds.astype(jnp.int32),
        mean_degree=jnp.mean(nres.degree.astype(jnp.float32)),
        diffusion_iters=iters.astype(jnp.int32),
        diffusion_residual=res_fin.astype(jnp.float32),
        unrealized_flow=jnp.abs(sres.residual).sum().astype(jnp.float32),
    )
    return sres.assignment.astype(jnp.int32), stats


# ----------------------------------------------------- series replay ----


_SERIES_CACHE: Dict[tuple, object] = {}
_PIC_CACHE: Dict[tuple, object] = {}
_CACHE_MAX = 16   # each entry pins a Mesh + a compiled whole-replay scan


def _mesh_key(mesh: Mesh) -> tuple:
    return tuple(d.id for d in mesh.devices.flat)


def _cached(cache: Dict, key: tuple, build):
    fn = cache.get(key)
    if fn is None:
        fn = cache[key] = build()
        while len(cache) > _CACHE_MAX:          # drop oldest entry
            cache.pop(next(iter(cache)))
    return fn


def _make_series_step(mesh: Mesh, evolve, strategy: str,
                      eng_params: Optional[Dict], trig,
                      threads_per_node: Optional[int], P: int,
                      faults, guard: bool, tel=None):
    """Shared per-step body of the series replay scans.

    Returns ``(step, track)`` where ``track`` says whether the step
    emits the extra ``plan_rejected`` output.  With ``faults is None``
    and ``guard`` off the emitted trace is **exactly** the
    pre-resilience step (every ``if`` below is static), preserving the
    bit-for-bit parity contract; the resilient variant adds
    health-masked trigger stats/planning, forced fires on health
    transitions or stranded objects, and the ``validate_plan`` rollback
    guardrail.

    ``tel`` (an enabled ``obs.telemetry.TelemetryConfig``) appends a
    replicated :class:`~repro.obs.telemetry.TelemetryState` to the scan
    carry and records one StepRecord per step — every recorded quantity
    (loads, fire bit, sweeps, moved counts) is already replicated under
    the parity contract, so the ring stays replicated for free.
    ``tel=None`` follows the same static-elision rule as ``faults``."""
    from repro.sim import simulator as sim   # local: sim imports us lazily

    D = int(np.prod(mesh.devices.shape))
    ax = mesh.axis_names[0]
    do_lb_at_all = strategy != "none" and not trig.never
    resilient = faults is not None
    track = resilient or bool(guard)
    tkind = obs_telemetry.trigger_kind(trig) if tel else 0
    plan = None
    if do_lb_at_all:
        eng_params = dict(eng_params)
        plan = functools.partial(_plan_step_sharded, P=P, D=D, axis=ax,
                                 variant=eng_params.pop("variant"),
                                 **eng_params)

    def step(carry, t):
        if tel:
            problem, tstate, obs_state = carry
        else:
            problem, tstate = carry
        problem = evolve(problem, t)
        prev = problem.assignment
        rejected = jnp.float32(0.0)
        health_changed = jnp.float32(0.0)
        if do_lb_at_all:
            if resilient:
                alive_n, speed_n = faults.node_health(t, P, D)
                mx, av, tot = rt_triggers.load_stats_masked(
                    problem.loads, problem.assignment, P, alive_n,
                    speed_n)
            else:
                alive_n = speed_n = None
                mx, av, tot = rt_triggers.load_stats(
                    problem.loads, problem.assignment, problem.num_nodes)
            do, tstate = trig.decide(tstate, t, mx, av, tot)
            if resilient:
                # a health transition or an object stranded on a dead
                # node must fire a rebalance regardless of the policy
                stranded = (~jnp.take(
                    alive_n, jnp.clip(prev, 0, P - 1))).any()
                health_changed = faults.changed_at(
                    t, D).astype(jnp.float32)
                do = do | faults.changed_at(t, D) | stranded
                planned, stats = jax.lax.cond(
                    do,
                    lambda op: plan(op[0], alive=op[1], speed=op[2]),
                    lambda op: (op[0].assignment.astype(jnp.int32),
                                core_engine.zero_stats()),
                    (problem, alive_n, speed_n),
                )
            else:
                planned, stats = jax.lax.cond(
                    do,
                    plan,
                    lambda p: (p.assignment.astype(jnp.int32),
                               core_engine.zero_stats()),
                    problem,
                )
            if track:
                # guardrail: adopt only validated plans; otherwise keep
                # the last-good assignment (prev is valid by induction)
                ok = rt_resilience.validate_plan(
                    planned, problem.loads, num_nodes=P, alive=alive_n)
                adopt = do & ok
                rejected = (do & ~ok).astype(jnp.float32)
                new_assignment = jnp.where(adopt, planned, prev)
            else:
                adopt = do
                new_assignment = planned
            delta = new_assignment != prev
            moved = jnp.where(
                adopt, jnp.mean(delta.astype(jnp.float32)), 0.0)
            migrated_load = jnp.where(
                adopt,
                jnp.where(delta,
                          jnp.asarray(problem.loads, jnp.float32),
                          0.0).sum(),
                0.0)
            tstate = trig.observe(tstate, migrated_load, do)
            fired = do.astype(jnp.float32)
            sweeps = stats.diffusion_iters.astype(jnp.float32)
            moved_n = jnp.where(adopt, delta.sum().astype(jnp.float32),
                                0.0)
            problem = problem.with_assignment(new_assignment)
        else:
            moved = jnp.float32(0.0)
            migrated_load = jnp.float32(0.0)
            fired = jnp.float32(0.0)
            sweeps = jnp.float32(0.0)
            moved_n = jnp.float32(0.0)
        m = metrics.evaluate_device(problem)
        if threads_per_node:
            tma = sim._thread_max_avg(problem.loads, problem.assignment,
                                      problem.num_nodes, threads_per_node)
        else:
            tma = jnp.float32(0.0)
        ys = (m.max_avg_load, m.ext_int_comm, moved, tma, fired,
              m.max_load, migrated_load)
        if track:
            ys = ys + (rejected,)
        if tel:
            obs_state = obs_telemetry.record(
                obs_state, tel, t=t,
                node_loads=obs_telemetry.node_loads(
                    problem.loads, problem.assignment, P),
                fired=fired, trigger_kind=tkind, plan_rejected=rejected,
                sweeps=sweeps, moved_items=moved_n,
                moved_bytes=migrated_load,
                health_changed=health_changed)
            return (problem, tstate, obs_state), ys
        return (problem, tstate), ys

    return step, track


def _series_runner(mesh: Mesh, evolve, steps: int, strategy: str,
                   eng_params: Optional[Dict], trig,
                   threads_per_node: Optional[int], P: int,
                   has_coords: bool, faults=None, guard: bool = False,
                   tel=None):
    """Compile-once ``shard_map`` wrapping the whole series replay."""
    step, track = _make_series_step(mesh, evolve, strategy, eng_params,
                                    trig, threads_per_node, P, faults,
                                    guard, tel)
    nys = 8 if track else 7
    nobs = 3 if tel else 0   # TelemetryState leaves (count, records, loads)

    def body(loads, assignment, e_src, e_dst, e_bytes, coords):
        problem = comm_graph.LBProblem(
            loads=loads, assignment=assignment, edges_src=e_src,
            edges_dst=e_dst, edges_bytes=e_bytes, num_nodes=P,
            coords=coords if has_coords else None)
        init = (problem, trig.init_state())
        if tel:
            init = init + (obs_telemetry.init_state(tel, P),)
        carry, ys = jax.lax.scan(step, init, jnp.arange(steps))
        out = (carry[0].assignment.astype(jnp.int32),)
        if tel:
            out = out + tuple(carry[2])   # replicated ring — exits as-is
        return out + ys

    # the problem arrays enter replicated: per-shard state materializes
    # *inside* the step (dynamic_slice by axis index for the diffusion
    # rows), so the scan carry never re-gathers between steps
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P_(),) * 6,
        out_specs=(P_(),) * (1 + nobs + nys),
        check_vma=False)
    return jax.jit(fn)


def _series_chunk_runner(mesh: Mesh, evolve, chunk: int, strategy: str,
                         eng_params: Optional[Dict], trig,
                         threads_per_node: Optional[int], P: int,
                         has_coords: bool, faults=None,
                         guard: bool = False):
    """Chunked series runner: scan ``chunk`` steps from an explicit carry.

    The checkpoint/restart entry (``runtime.resilience.
    run_series_checkpointed``) drives the replay through this runner —
    same per-step program as :func:`_series_runner` (the step closure is
    shared), but the scan carry (problem arrays + trigger-state leaves)
    crosses the call boundary so the supervisor can snapshot and restore
    it.  Scanning ``t0 + arange(chunk)`` instead of ``arange(steps)``
    changes nothing numerically — chunked trajectories are bit-for-bit
    the one-shot scan."""
    step, track = _make_series_step(mesh, evolve, strategy, eng_params,
                                    trig, threads_per_node, P, faults,
                                    guard)
    nys = 8 if track else 7

    def body(loads, assignment, e_src, e_dst, e_bytes, coords, t0,
             last_lb, armed, history, hist_len, last_moved):
        problem = comm_graph.LBProblem(
            loads=loads, assignment=assignment, edges_src=e_src,
            edges_dst=e_dst, edges_bytes=e_bytes, num_nodes=P,
            coords=coords if has_coords else None)
        tstate = rt_triggers.TriggerState(last_lb, armed, history,
                                          hist_len, last_moved)
        (pfin, ts), ys = jax.lax.scan(
            step, (problem, tstate),
            jnp.asarray(t0, jnp.int32) + jnp.arange(chunk))
        carry_out = (pfin.loads, pfin.assignment.astype(jnp.int32),
                     pfin.edges_src, pfin.edges_dst, pfin.edges_bytes,
                     pfin.coords if has_coords else coords,
                     ts.last_lb, ts.armed, ts.history, ts.hist_len,
                     ts.last_moved)
        return carry_out + ys

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P_(),) * 12,
        out_specs=(P_(),) * (11 + nys),
        check_vma=False)
    return jax.jit(fn)


def run_series_sharded(
    initial: comm_graph.LBProblem,
    evolve,
    *,
    steps: int,
    lb_every: int,
    strategy: str = "diff-comm",
    strategy_kwargs: Optional[Dict] = None,
    trigger=None,
    mesh: Optional[Mesh] = None,
    num_shards: Optional[int] = None,
    threads_per_node: Optional[int] = None,
    faults=None,
    guard: Optional[bool] = None,
    telemetry=None,
):
    """Mesh-sharded ``run_series``: the whole replay in one ``shard_map``.

    The drop-in distributed twin of ``sim.simulator.run_series``'s
    scanned path: one compiled ``shard_map`` over the 1-D ``"lb"`` mesh
    contains the full ``lax.scan`` over ``steps`` — evolve, trigger
    decision (``runtime.triggers``, identical fire steps on every
    shard), ``lax.cond``-gated **sharded** three-stage planning (stage-2
    diffusion as ``ppermute`` ring halo exchanges over O(P/D) rows per
    shard), and the per-step metrics — with zero host transfers inside
    the loop and **bit-for-bit** the single-device scanned replay's
    ``SeriesResult`` (see the module docstring for the parity
    mechanism; ``tests/test_replay_shard.py`` asserts it on an
    8-virtual-device CPU mesh).

    Args mirror ``run_series`` (the strategy must be a jittable diff-*
    registration — its ``Strategy.variant`` configures the sharded
    planner; host baselines cannot be distributed).  ``mesh`` /
    ``num_shards`` pick the device mesh: the default uses the largest
    available device count dividing ``initial.num_nodes`` (shrinking to
    1 device degenerates to the single-device graph).  ``trigger``
    resolves exactly as in ``run_series`` (strategy-registered policy,
    then the fixed ``lb_every`` cadence).

    Resilience (``runtime.resilience``): ``faults`` takes a
    ``FaultSchedule`` whose die/slow/recover events are honored inside
    the scan — trigger stats and planning see the health mask, health
    transitions force a rebalance, and a dead shard's objects are
    re-homed onto alive communication partners.  ``guard`` (default: on
    whenever ``faults`` is set) runs every fired plan through
    ``validate_plan`` and rolls back to the last-good assignment on
    rejection; either flag adds the per-step ``plan_rejected`` series to
    the result.  An empty/None schedule with ``guard`` unset adds
    *nothing* to the trace — the bit-for-bit parity contract above is
    untouched.

    ``telemetry`` (an ``obs.telemetry.TelemetryConfig`` / level string)
    threads the scan-carried StepRecord ring through the shard_map —
    replicated, since every recorded quantity already is under the
    parity contract — and attaches the snapshot to the result.  Off /
    absent is bit-for-bit free, exactly as in ``run_series``.
    """
    from repro.sim import simulator as sim   # local: sim imports us lazily

    strategy_kwargs, trig, P, mesh, faults, guard, eng = _series_setup(
        initial, evolve, strategy, strategy_kwargs, trigger, lb_every,
        mesh, num_shards, faults, guard)
    tel = obs_telemetry.resolve(telemetry)
    tel = tel if tel.enabled else None

    key = (_mesh_key(mesh), evolve, int(steps), int(lb_every), strategy,
           tuple(sorted(strategy_kwargs.items())), trig,
           None if threads_per_node is None else int(threads_per_node),
           initial.coords is not None, P, faults, guard, tel)
    runner = _cached(
        _SERIES_CACHE, key,
        lambda: _series_runner(mesh, evolve, int(steps), strategy,
                               None if eng is None else dict(eng), trig,
                               threads_per_node, P,
                               initial.coords is not None, faults, guard,
                               tel))

    prob = sim._canonical(initial)
    coords = (prob.coords if prob.coords is not None
              else jnp.zeros((prob.num_objects, 1), jnp.float32))
    t_start = time.perf_counter()
    out = runner(prob.loads, prob.assignment, prob.edges_src,
                 prob.edges_dst, prob.edges_bytes, coords)
    if tel:
        obs_state = obs_telemetry.TelemetryState(*out[1:4])
        final_assignment, ys = out[0], out[4:]
    else:
        obs_state = None
        final_assignment, ys = out[0], out[1:]
    track = (faults is not None) or guard
    ys = jax.device_get(ys)
    if track:
        ma, ei, mig, tma, fired, mxl, migl, rej = ys
    else:
        ma, ei, mig, tma, fired, mxl, migl = ys
        rej = None
    final_assignment = np.asarray(jax.device_get(final_assignment),
                                  np.int32)
    wall = time.perf_counter() - t_start
    return sim.SeriesResult(
        np.asarray(ma, np.float64), np.asarray(ei, np.float64),
        np.asarray(mig, np.float64), wall, scanned=True, wall_seconds=wall,
        thread_max_avg=(np.asarray(tma, np.float64) if threads_per_node
                        else None),
        lb_fired=np.asarray(fired, np.float64),
        max_load=np.asarray(mxl, np.float64),
        migrated_load=np.asarray(migl, np.float64),
        final_assignment=final_assignment,
        plan_rejected=(None if rej is None
                       else np.asarray(rej, np.float64)),
        telemetry=(obs_telemetry.snapshot(obs_state, tel)
                   if tel else None))


class _PreparedSeries:
    """Chunk-driving handle over the sharded series replay.

    Built by :func:`prepare_series` and consumed by
    ``runtime.resilience.run_series_checkpointed``: the supervisor owns
    the scan carry between chunks (so it can snapshot/restore it) and
    calls :meth:`run_chunk` per chunk; :meth:`package` turns the final
    carry + concatenated per-step outputs into the same ``SeriesResult``
    ``run_series_sharded`` returns.  The per-step program is shared with
    the one-shot runner, so chunked trajectories are bit-for-bit the
    uninterrupted scan."""

    def __init__(self, *, mesh, evolve, lb_every, strategy,
                 strategy_kwargs, trig, threads_per_node, P, has_coords,
                 faults, guard, prob, coords):
        self.mesh = mesh
        self.evolve = evolve
        self.lb_every = int(lb_every)
        self.strategy = strategy
        self.strategy_kwargs = dict(strategy_kwargs)
        self.trig = trig
        self.threads_per_node = threads_per_node
        self.P = int(P)
        self.has_coords = bool(has_coords)
        self.faults = faults
        self.guard = bool(guard)
        self.track = (faults is not None) or bool(guard)
        self._prob = prob
        self._coords = coords
        strat = core_engine.get_strategy(strategy)
        self._eng = (dict(_engine_params(strat, self.strategy_kwargs),
                          variant=strat.variant)
                     if strategy != "none" else None)

    def initial_carry(self):
        """The scan carry at t=0: 6 problem arrays + 5 trigger leaves."""
        p = self._prob
        return (p.loads, p.assignment, p.edges_src, p.edges_dst,
                p.edges_bytes, self._coords) + tuple(self.trig.init_state())

    def _runner(self, chunk: int):
        key = ("chunk", _mesh_key(self.mesh), self.evolve, int(chunk),
               self.lb_every, self.strategy,
               tuple(sorted(self.strategy_kwargs.items())), self.trig,
               None if self.threads_per_node is None
               else int(self.threads_per_node),
               self.has_coords, self.P, self.faults, self.guard)
        return _cached(
            _SERIES_CACHE, key,
            lambda: _series_chunk_runner(
                self.mesh, self.evolve, int(chunk), self.strategy,
                None if self._eng is None else dict(self._eng), self.trig,
                self.threads_per_node, self.P, self.has_coords,
                self.faults, self.guard))

    def run_chunk(self, carry, t_start: int, chunk: int):
        """Advance ``chunk`` steps from ``carry``; returns
        ``(new_carry, per_step_outputs)``.  ``carry`` may be host
        snapshots (restored) or live device arrays."""
        carry = tuple(jnp.asarray(a) for a in carry)
        out = self._runner(int(chunk))(
            *carry[:6], jnp.asarray(int(t_start), jnp.int32), *carry[6:])
        return out[:11], out[11:]

    def package(self, carry, ys, *, wall_seconds: float):
        """Final carry + concatenated chunk outputs → ``SeriesResult``."""
        from repro.sim import simulator as sim

        if self.track:
            ma, ei, mig, tma, fired, mxl, migl, rej = ys
        else:
            ma, ei, mig, tma, fired, mxl, migl = ys
            rej = None
        final_assignment = np.asarray(jax.device_get(carry[1]), np.int32)
        return sim.SeriesResult(
            np.asarray(ma, np.float64), np.asarray(ei, np.float64),
            np.asarray(mig, np.float64), wall_seconds, scanned=True,
            wall_seconds=wall_seconds,
            thread_max_avg=(np.asarray(tma, np.float64)
                            if self.threads_per_node else None),
            lb_fired=np.asarray(fired, np.float64),
            max_load=np.asarray(mxl, np.float64),
            migrated_load=np.asarray(migl, np.float64),
            final_assignment=final_assignment,
            plan_rejected=(None if rej is None
                           else np.asarray(rej, np.float64)))


def prepare_series(
    initial: comm_graph.LBProblem,
    evolve,
    *,
    steps: int,
    lb_every: int,
    strategy: str = "diff-comm",
    strategy_kwargs: Optional[Dict] = None,
    trigger=None,
    mesh: Optional[Mesh] = None,
    num_shards: Optional[int] = None,
    threads_per_node: Optional[int] = None,
    faults=None,
    guard: Optional[bool] = None,
) -> _PreparedSeries:
    """Validate + stage a series replay for external chunk driving.

    Same arguments and validation as :func:`run_series_sharded` (the
    ``steps`` total is accepted for symmetry; the chunk driver decides
    the actual schedule), but instead of running the scan it returns a
    :class:`_PreparedSeries` whose ``initial_carry`` / ``run_chunk`` /
    ``package`` methods let a supervisor — in practice
    ``runtime.resilience.run_series_checkpointed`` — own the carry
    between chunks for checkpoint/restart."""
    from repro.sim import simulator as sim   # local: sim imports us lazily

    if int(steps) < 1:
        raise ValueError("steps must be >= 1")
    strategy_kwargs, trig, P, mesh, faults, guard, _eng = _series_setup(
        initial, evolve, strategy, strategy_kwargs, trigger, lb_every,
        mesh, num_shards, faults, guard)
    prob = sim._canonical(initial)
    coords = (prob.coords if prob.coords is not None
              else jnp.zeros((prob.num_objects, 1), jnp.float32))
    return _PreparedSeries(
        mesh=mesh, evolve=evolve, lb_every=lb_every, strategy=strategy,
        strategy_kwargs=strategy_kwargs, trig=trig,
        threads_per_node=threads_per_node, P=P,
        has_coords=initial.coords is not None, faults=faults, guard=guard,
        prob=prob, coords=coords)


# -------------------------------------------------------- PIC replay ----


def _pic_runner(mesh: Mesh, L: int, cx: int, cy: int, num_pes: int,
                k: int, vy0: float, lb_every: int, strategy: str,
                kw_items: tuple, bpp: float, use_kernel: Optional[bool],
                steps: int, capacity: int,
                threads_per_node: Optional[int], trig,
                faults=None, on_overflow: str = "strict", tel=None):
    """Compile-once ``shard_map`` wrapping the whole PIC replay.

    Per-shard carry: the (capacity,) particle payload slabs (x, y, vx,
    vy, q, chare id, particle id) with a live-prefix count, plus the
    replicated chare→PE assignment and trigger state.  Each step pushes
    the local slab, ``psum``-completes the handoff counts and the
    per-chare histogram (integer-valued — exact), decides the trigger on
    the replicated loads, plans (sharded over the PE rows when
    ``num_pes`` divides the mesh, else replicated — the chare problem is
    O(C) tiny either way), and executes a fired plan as the masked
    ``ring_exchange`` re-bucketing the slabs into PE-owned slot regions.

    Resilience: an active ``faults`` schedule masks trigger stats and
    planning with the node health at ``t``, forces a fire on every
    health transition or stranded chare, and gates plan adoption through
    ``validate_plan`` (strict mode additionally rejects plans whose
    per-shard inflow would overflow the static slabs — payload is never
    dropped).  ``on_overflow="spill"`` swaps the exchange for the
    admission-clamped spill ring: overflow particles stay on their
    source shard (their desired owner is preserved, so the next fired
    rebalance retries them) and the per-step ``deferred`` count is
    emitted.  ``faults=None`` + strict mode is the exact pre-resilience
    trace.
    """
    from repro.kernels.histogram.ops import histogram
    from repro.kernels.pic_push.ops import pic_push
    from repro.pic import chares as ch
    from repro.pic.grid import alternating_grid
    from repro.core import hierarchical

    D = int(np.prod(mesh.devices.shape))
    ax = mesh.axis_names[0]
    n_chares = cx * cy
    grid_q = jnp.asarray(alternating_grid(L))
    lb_on = strategy != "none" and not trig.never
    strat = core_engine.get_strategy(strategy) if lb_on else None
    resilient = faults is not None
    spill = on_overflow == "spill"
    track = resilient or spill
    tkind = obs_telemetry.trigger_kind(trig) if tel else 0
    # the chare-level plan: sharded over the PE rows when the mesh
    # divides them (plan → manifest → apply on ONE mesh), else the
    # replicated single-device graph — bit-for-bit either way
    plan_sharded = lb_on and strat.variant is not None and num_pes % D == 0
    if plan_sharded:
        eng = _engine_params(strat, dict(kw_items))
        plan = functools.partial(_plan_step_sharded, P=num_pes, D=D,
                                 axis=ax, variant=strat.variant, **eng)
    elif lb_on and resilient:
        # replicated health-masked planning: the engine method is the
        # single-device twin of the masked sharded plan
        plan = core_engine.get_engine(
            variant=strat.variant,
            **_engine_params(strat, dict(kw_items))).plan_health_fn
    elif lb_on:
        plan = strat.bind(**dict(kw_items))
    else:
        plan = None

    def step(carry, t):
        if tel:
            (x, y, vx, vy, q, chare_id, assignment, perm, count, tstate,
             obs_state) = carry
        else:
            (x, y, vx, vy, q, chare_id, assignment, perm, count,
             tstate) = carry
        xn, yn, vxn, vyn = pic_push(grid_q, x, y, vx, vy, q, L=L,
                                    use_kernel=use_kernel)
        new_chare = ch.chare_of_device(xn, yn, L, cx, cy)
        live = jnp.arange(capacity, dtype=jnp.int32) < count
        # particle handoffs: chare changed → bytes move; PE boundary →
        # ext.  Partial counts are integers — psum completion is exact,
        # so the f32 byte totals match the single-device path bitwise.
        moved = (new_chare != chare_id) & live
        src_pe = assignment[chare_id]
        dst_pe = assignment[new_chare]
        ext = jax.lax.psum(
            (moved & (src_pe != dst_pe)).sum(), ax).astype(jnp.float32) \
            * bpp
        intra = jax.lax.psum(
            (moved & (src_pe == dst_pe)).sum(), ax).astype(jnp.float32) \
            * bpp

        loads = jax.lax.psum(
            histogram(new_chare, live.astype(xn.dtype), C=n_chares,
                      use_kernel=use_kernel), ax)
        pe_loads = jax.ops.segment_sum(loads, assignment,
                                       num_segments=num_pes)
        pe_max = pe_loads.max()
        ma = pe_max / (pe_loads.mean() + 1e-30)
        rejected = jnp.float32(0.0)
        deferred_n = jnp.int32(0)
        health_changed = jnp.float32(0.0)
        sweeps = jnp.float32(0.0)
        moved_n = jnp.int32(0)

        if lb_on:
            if resilient:
                alive_n, speed_n = faults.node_health(t, num_pes, D)
                mx, av, tot = rt_triggers.load_stats_masked(
                    loads, assignment, num_pes, alive_n, speed_n)
            else:
                alive_n = speed_n = None
                mx, av, tot = rt_triggers.load_stats(loads, assignment,
                                                     num_pes)
            do, tstate = trig.decide(tstate, t, mx, av, tot)
            if resilient:
                # evacuate dead PEs now: fire on every health transition
                # and while any chare is still owned by a dead PE
                stranded = (~jnp.take(
                    alive_n, jnp.clip(assignment, 0, num_pes - 1))).any()
                health_changed = faults.changed_at(
                    t, D).astype(jnp.float32)
                do = do | faults.changed_at(t, D) | stranded

            def do_plan(args):
                loads_, assignment_ = args
                problem = ch.build_problem(
                    loads_, assignment_, L=L, cx=cx, cy=cy,
                    num_pes=num_pes, k=k, vy0=vy0, lb_period=lb_every,
                    bytes_per_particle=bpp)
                if resilient:
                    a2, stats = plan(problem, alive=alive_n,
                                     speed=speed_n)
                else:
                    a2, stats = plan(problem)
                return a2, stats.diffusion_iters.astype(jnp.float32)

            planned, sweeps = jax.lax.cond(
                do, do_plan,
                lambda a: (a[1].astype(jnp.int32), jnp.float32(0.0)),
                (loads, assignment))
            if resilient:
                # guardrail: only adopt validated plans — owners alive
                # and in range and, in strict mode, per-shard inflow
                # within the static slab budget (a plan that does not
                # fit would drop payload; spill clamps instead)
                ok = rt_resilience.validate_plan(
                    planned, loads, num_nodes=num_pes, alive=alive_n)
                if not spill:
                    pe_new = jax.ops.segment_sum(
                        loads, jnp.clip(planned, 0, num_pes - 1),
                        num_segments=num_pes)
                    per_shard = pe_new.reshape(D, num_pes // D).sum(1)
                    ok = ok & (per_shard <= capacity).all()
                adopt = do & ok
                rejected = (do & ~ok).astype(jnp.float32)
                new_assignment = jnp.where(adopt, planned, assignment)
            else:
                adopt = do
                new_assignment = planned
            delta = new_assignment != assignment
            migf = jnp.where(
                adopt, jnp.mean(delta.astype(jnp.float32)), 0.0)

            # execute the plan inside the scan: the masked ppermute ring
            # all-to-all re-buckets the live slab prefixes into PE-owned
            # slot regions — concatenated prefixes reproduce the
            # single-device bucketed layout bit-for-bit
            owner_old = jnp.take(assignment, new_chare)
            owner_new = jnp.take(new_assignment, new_chare)

            if spill:
                def do_move(args):
                    _owner, outs, count_me, dfr = rt_migrate.ring_exchange(
                        owner_new, args, num_nodes=num_pes, D=D,
                        capacity=capacity, axis=ax, count_loc=count,
                        mode="spill")
                    want = jax.lax.psum(
                        ((owner_old != owner_new) & live)
                        .astype(jnp.int32).sum(), ax)
                    return outs, count_me, want - dfr, dfr

                (xn, yn, vxn, vyn, q, new_chare, perm), count, moved_n, \
                    deferred_n = jax.lax.cond(
                        adopt, do_move,
                        lambda args: (args, count, jnp.int32(0),
                                      jnp.int32(0)),
                        (xn, yn, vxn, vyn, q, new_chare, perm))
            else:
                def do_move(args):
                    _owner, outs, count_me = rt_migrate.ring_exchange(
                        owner_new, args, num_nodes=num_pes, D=D,
                        capacity=capacity, axis=ax, count_loc=count)
                    moved_ct = jax.lax.psum(
                        ((owner_old != owner_new) & live)
                        .astype(jnp.int32).sum(), ax)
                    return outs, count_me, moved_ct

                (xn, yn, vxn, vyn, q, new_chare, perm), count, moved_n = \
                    jax.lax.cond(
                        adopt, do_move,
                        lambda args: (args, count, jnp.int32(0)),
                        (xn, yn, vxn, vyn, q, new_chare, perm))
            tstate = trig.observe(tstate, moved_n.astype(jnp.float32), do)
            migb = moved_n.astype(jnp.float32) * bpp
            fired = do.astype(jnp.float32)
            assignment = new_assignment
        else:
            migf = jnp.float32(0.0)
            migb = jnp.float32(0.0)
            fired = jnp.float32(0.0)

        if threads_per_node:
            thr = hierarchical.lpt_threads(
                loads, assignment, num_nodes=num_pes,
                threads_per_node=threads_per_node)
            tl = hierarchical.thread_loads(
                loads, assignment, thr, num_nodes=num_pes,
                threads_per_node=threads_per_node)
            tma = (tl.max() / (tl.mean() + 1e-30)).astype(jnp.float32)
        else:
            tma = jnp.float32(0.0)

        ys = (ma, pe_max, ext, intra, migf, migb, tma, fired,
              count[None])
        if track:
            ys = ys + (rejected, deferred_n.astype(jnp.float32))
        new_carry = (xn, yn, vxn, vyn, q, new_chare, assignment, perm,
                     count, tstate)
        if tel:
            obs_state = obs_telemetry.record(
                obs_state, tel, t=t,
                node_loads=jax.ops.segment_sum(loads, assignment,
                                               num_segments=num_pes),
                fired=fired, trigger_kind=tkind, plan_rejected=rejected,
                sweeps=sweeps,
                moved_items=moved_n.astype(jnp.float32), moved_bytes=migb,
                deferred=deferred_n.astype(jnp.float32),
                health_changed=health_changed)
            new_carry = new_carry + (obs_state,)
        return new_carry, ys

    def body(x, y, vx, vy, q, chare_id, perm, count0, assignment):
        carry = (x, y, vx, vy, q, chare_id, assignment, perm,
                 count0[0], trig.init_state())
        if tel:
            carry = carry + (obs_telemetry.init_state(tel, num_pes),)
        carry, ys = jax.lax.scan(step, carry, jnp.arange(steps))
        (x, y, perm, count) = (carry[0], carry[1], carry[7], carry[8])
        out = ys + (x, y, perm, count[None])
        if tel:
            out = out + tuple(carry[10])   # replicated ring — exits as-is
        return out

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P_(ax),) * 8 + (P_(),),
        out_specs=((P_(),) * 8               # per-step replicated metrics
                   + (P_(None, ax),)         # per-step per-shard counts
                   + ((P_(),) * 2 if track else ())  # rejected, deferred
                   + (P_(ax),) * 4           # final slabs + counts
                   + ((P_(),) * 3 if tel else ())),  # TelemetryState
        check_vma=False)
    return jax.jit(fn)


def _pad_slabs(arrays, n: int, D: int, capacity: int):
    """Distribute (n,) buffers into (D*capacity,) per-shard slabs with
    n/D live items at each shard's prefix."""
    per = n // D
    out = []
    for a in arrays:
        a = np.asarray(a)
        slab = np.zeros((D, capacity), a.dtype)
        slab[:, :per] = a.reshape(D, per)
        out.append(jnp.asarray(slab.reshape(-1)))
    return out


def run_pic_sharded(cfg, cost) -> "PICResult":  # noqa: F821
    """Mesh-sharded scanned PIC driver (``PICConfig(sharded_replay=True)``).

    The whole run — push, handoff/byte accounting, trigger, planning and
    the executed particle exchange — is one compiled ``shard_map`` over
    the 1-D ``"lb"`` mesh with the particle slabs row-sharded; the only
    host contact is staging the initial slabs in and the final slabs +
    per-step metric series out.  Bit-for-bit the single-device scanned
    driver's ``PICResult`` (including ``final_x/final_y`` restored to
    particle-id order).  See the module docstring for the capacity rule;
    a ``replay_capacity`` below the largest per-shard bucket total
    raises ``ValueError`` after the run (payload is never dropped
    silently).

    ``PICConfig.faults`` injects a ``runtime.resilience.FaultSchedule``
    into the scan (health-masked trigger/planning, forced evacuation
    fires, guarded plan adoption) and ``PICConfig.on_overflow="spill"``
    swaps the exchange for the admission-clamped spill ring (overflow
    particles stay on their source shard and drain on later fires);
    either adds the ``plan_rejected`` / ``deferred`` per-step series to
    the result.  Defaults leave the trace bit-for-bit unchanged."""
    from repro.kernels.histogram.ops import histogram
    from repro.pic import chares as ch
    from repro.pic import driver as pic_driver
    from repro.pic.particles import initialize

    if cfg.strategy != "none":
        strat = core_engine.get_strategy(cfg.strategy)
        if not strat.jittable:
            raise ValueError(
                f"strategy {cfg.strategy!r} is not jittable; the sharded "
                "PIC replay needs a traceable plan_fn (diff-* / none)")
    # the exchange's ring ownership mapping needs num_pes % D == 0 (shard
    # d owns PEs [d*rpd, (d+1)*rpd)) and the particle slabs need n % D
    n = cfg.n_particles
    mesh = _resolve_mesh(None, cfg.replay_shards, (n, cfg.num_pes))
    D = int(np.prod(mesh.devices.shape))
    capacity = n if cfg.replay_capacity is None else int(cfg.replay_capacity)
    if capacity < n // D:
        raise ValueError(
            f"replay_capacity={capacity} cannot even hold the initial "
            f"even split of {n} particles over {D} shards "
            f"({n // D} per shard); raise replay_capacity "
            f"(n_particles={n} is always safe)")
    on_overflow = getattr(cfg, "on_overflow", "strict")
    if on_overflow not in ("strict", "spill"):
        raise ValueError(f"unknown on_overflow mode {on_overflow!r}")

    p = initialize(cfg.mode, cfg.L, n, k=cfg.k, vy0=cfg.vy0,
                   rho=cfg.rho, seed=cfg.seed)
    chare_id = np.asarray(ch.chare_of(p.x, p.y, cfg.L, cfg.cx, cfg.cy))
    assignment = jnp.asarray(
        ch.initial_mapping(cfg.cx, cfg.cy, cfg.num_pes, cfg.mapping),
        jnp.int32)
    n_chares = cfg.cx * cfg.cy

    kw_items = tuple(sorted((cfg.strategy_kwargs or {}).items()))
    trig = pic_driver._resolve_trigger(cfg)
    lb_on = cfg.strategy != "none" and not trig.never
    faults, _ = _resolve_resilience(getattr(cfg, "faults", None), None, D,
                                    cfg.strategy, trig)
    track = (faults is not None) or on_overflow == "spill"
    tel = obs_telemetry.resolve(getattr(cfg, "telemetry", None))
    tel = tel if tel.enabled else None

    # LB planning cost for the CostModel — measured once on the initial
    # snapshot, exactly as the single-device scanned path charges it
    lb_est = 0.0
    if lb_on:
        loads0 = histogram(jnp.asarray(chare_id), jnp.ones(n), C=n_chares,
                           use_kernel=cfg.use_kernel)
        problem0 = ch.build_problem(
            loads0, assignment, L=cfg.L, cx=cfg.cx, cy=cfg.cy,
            num_pes=cfg.num_pes, k=cfg.k, vy0=cfg.vy0,
            lb_period=cfg.lb_every,
            bytes_per_particle=cfg.bytes_per_particle)
        strat = core_engine.get_strategy(cfg.strategy)
        strat.run(problem0, **dict(kw_items))          # warm the compile
        lb_est = strat.run(problem0, **dict(kw_items)).info["plan_seconds"]

    runner = _cached(
        _PIC_CACHE,
        (_mesh_key(mesh), cfg.L, cfg.cx, cfg.cy, cfg.num_pes, cfg.k,
         cfg.vy0, cfg.lb_every, cfg.strategy, kw_items,
         cfg.bytes_per_particle, cfg.use_kernel, cfg.steps, capacity,
         cfg.threads_per_node, trig, faults, on_overflow, tel),
        lambda: _pic_runner(mesh, cfg.L, cfg.cx, cfg.cy, cfg.num_pes,
                            cfg.k, cfg.vy0, cfg.lb_every, cfg.strategy,
                            kw_items, cfg.bytes_per_particle,
                            cfg.use_kernel, cfg.steps, capacity,
                            cfg.threads_per_node, trig, faults,
                            on_overflow, tel))

    slabs = _pad_slabs(
        (p.x, p.y, p.vx, p.vy, p.q, chare_id,
         np.arange(n, dtype=np.int32)), n, D, capacity)
    count0 = jnp.full((D,), n // D, jnp.int32)

    t_start = time.perf_counter()
    out = runner(*slabs, count0, assignment)
    out = jax.device_get(out)
    wall = time.perf_counter() - t_start

    if tel:
        obs_state = obs_telemetry.TelemetryState(*out[-3:])
        out = out[:-3]
    else:
        obs_state = None
    if track:
        (ma, pe_max, ext_b, int_b, mig, mig_bytes, tma, fired, counts_ts,
         rej, deferred, x_out, y_out, perm_out, counts) = out
    else:
        (ma, pe_max, ext_b, int_b, mig, mig_bytes, tma, fired, counts_ts,
         x_out, y_out, perm_out, counts) = out
        rej = deferred = None
    counts_ts = np.asarray(counts_ts)              # (T, D) needed slots
    # spill mode clamps inflow inside the exchange (counts <= capacity
    # by construction, overflow surfaces as the deferred series); strict
    # mode keeps the fail-loud contract
    if on_overflow != "spill" and (counts_ts > capacity).any():
        raise ValueError(
            f"replay_capacity={capacity} overflowed (largest shard "
            f"needed {int(counts_ts.max())} slots at some step); the "
            "exchange would have dropped payload — raise replay_capacity "
            f"(n_particles={n} is always safe) or use "
            "on_overflow='spill'")

    ma, pe_max, ext_b, int_b, mig, mig_bytes, tma, fired = (
        np.asarray(a, np.float64)
        for a in (ma, pe_max, ext_b, int_b, mig, mig_bytes, tma, fired))
    lb_steps = fired > 0
    lb_s_t = np.where(lb_steps, lb_est, 0.0)
    step_s = (
        pe_max * cost.t_particle
        + (ext_b + mig_bytes) * cost.t_byte
        + np.array([cost.lb_seconds(s_, cfg.strategy, cfg.num_pes)
                    for s_ in lb_s_t]) / pic_driver._lb_amort(cfg, trig)
    )
    # concatenate the per-shard valid prefixes (the single-device slot
    # layout), then undo the executed exchanges back to particle-id order
    counts = np.asarray(counts).reshape(-1)
    xs = np.concatenate([np.asarray(x_out)[d * capacity:
                                           d * capacity + counts[d]]
                         for d in range(D)])
    ys_ = np.concatenate([np.asarray(y_out)[d * capacity:
                                            d * capacity + counts[d]]
                          for d in range(D)])
    perm = np.concatenate([np.asarray(perm_out)[d * capacity:
                                                d * capacity + counts[d]]
                           for d in range(D)])
    fx, fy = np.empty_like(xs), np.empty_like(ys_)
    fx[perm], fy[perm] = xs, ys_
    return pic_driver.PICResult(
        ma, ext_b, int_b, mig, mig_bytes,
        float(lb_est * lb_steps.sum()), step_s, fx, fy,
        scanned=True, wall_seconds=wall,
        thread_max_avg=(tma if cfg.threads_per_node else None),
        lb_steps=fired,
        plan_rejected=(None if rej is None
                       else np.asarray(rej, np.float64)),
        deferred=(None if deferred is None
                  else np.asarray(deferred, np.float64)),
        telemetry=(obs_telemetry.snapshot(obs_state, tel)
                   if tel else None))
