"""PIC PRK end-to-end driver with integrated load balancing (paper §VI).

Reproduces the paper's evaluation loop: particles advance each step (Pallas
push kernel), chare loads are measured (histogram kernel), and the
chare→PE assignment is rebalanced by any registered strategy whenever the
online trigger fires (``PICConfig.trigger`` — fixed ``lb_every`` cadence
by default, adaptive threshold/predictive policies via
``runtime.triggers``).  A fired rebalance is **executed**, not just
counted: particle payload is relocated between PE-owned slot regions
(``runtime.migrate`` bucketed gather, device-resident in the scanned
path) and the migration volume is measured from that exchange.  Records
the paper's metrics per step:

  * max/avg particles per PE            (Fig 3, Fig 4)
  * external/internal comm bytes        (particle handoffs crossing PEs)
  * migration volume at LB steps        (measured from the executed
    exchange; ``final_x/final_y`` are restored to particle-id order)
  * modeled step time (compute + comm + LB amortization) for the
    strong-scaling study (Fig 5/6) — see ``CostModel``; wall-clock
    multi-node timing needs real nodes, the model is calibrated per-term
    and reported as such in EXPERIMENTS.md.

Two execution paths:

  * **scanned** (default for jittable strategies) — particles, loads and
    the assignment stay device-resident for the whole run: one ``step_fn``
    is scanned in chunks of ``scan_chunk`` steps, per-step metrics
    accumulate in the scan outputs, and the host sees data only at chunk
    boundaries.  LB planning runs inside the scan via ``lax.cond`` on the
    step index (the fused ``core.engine`` planner).
  * **host loop** — the legacy eager path, used for NumPy-only baseline
    strategies (greedy, metis, ...) or when ``cfg.scan=False``.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import api as core_api
from repro.core import engine as core_engine
from repro.core import hierarchical
from repro.distributed import compat
from repro.obs import telemetry as obs_telemetry
from repro.kernels.histogram.ops import histogram
from repro.kernels.pic_push.ops import pic_push
from repro.pic import chares as ch
from repro.pic.grid import alternating_grid
from repro.pic.particles import initialize
from repro.runtime import migrate as rt_migrate
from repro.runtime import triggers as rt_triggers


@dataclasses.dataclass
class PICConfig:
    L: int = 1000
    n_particles: int = 100_000
    steps: int = 100
    k: int = 2
    rho: float = 0.9
    vy0: float = 1.0
    mode: str = "GEOMETRIC"
    cx: int = 12
    cy: int = 12
    num_pes: int = 4
    mapping: str = "striped"
    lb_every: int = 10
    strategy: str = "diff-comm"
    strategy_kwargs: Optional[Dict] = None
    # online rebalancing policy (runtime.triggers): None resolves to the
    # strategy's registered trigger and then to the legacy fixed
    # ``lb_every`` cadence (bit-for-bit the pre-runtime driver); "every" /
    # "threshold" / "predictive" or a Trigger instance select adaptive
    # policies, decided per step on device from the pre-LB PE loads.
    # Every LB step *executes* the plan: particle payload is relocated
    # between PE-owned slot regions (runtime.migrate) and
    # ``PICResult.migrated_bytes`` is measured from that exchange.
    trigger: Optional[object] = None
    # sweeps per fused diffusion block inside the planner (stage 2); None
    # keeps the engine default.  Plumbed into the diff-* strategies only —
    # the scanned path's lax.cond-gated planning then runs the chunked
    # virtual-LB loop (kernels/diffusion/ops.py).
    sweep_chunk: Optional[int] = None
    # two-level placement (paper §III.D): when set, every step also
    # records max/avg particles per *global PE* ((num_pes × T) threads,
    # chare→thread via the device-resident within-node LPT) in
    # PICResult.thread_max_avg — computed inside the scan, no host trip.
    threads_per_node: Optional[int] = None
    # mesh-sharded replay (distributed/replay_shard.py): the whole run —
    # push, trigger, planning, executed particle exchange — inside ONE
    # shard_map over the 1-D "lb" device mesh, particle slabs
    # row-sharded, bit-for-bit the single-device scanned path.  Needs a
    # jittable strategy; the mesh auto-sizes to the largest device count
    # dividing both n_particles and num_pes (replay_shards overrides).
    # replay_capacity is the static per-shard slot budget for the in-scan
    # ring all-to-all (None = worst-case n_particles, always safe; an
    # undersized budget raises ValueError after the run rather than
    # dropping payload).
    sharded_replay: bool = False
    replay_shards: Optional[int] = None
    replay_capacity: Optional[int] = None
    # resilience (sharded replay only; runtime/resilience.py): `faults`
    # injects a FaultSchedule of die/slow/recover shard events honored
    # inside the scan — health-masked trigger stats and planning, forced
    # evacuation fires, validate_plan-guarded adoption.  `on_overflow`
    # picks the exchange's degradation mode when a fired plan exceeds
    # replay_capacity: "strict" fails loud (the ValueError above),
    # "spill" clamps per-shard inflow, keeps overflow particles on their
    # source shard and retries them at the next fire (PICResult.deferred
    # records the backlog).  Defaults add nothing to the trace.
    faults: Optional[object] = None
    on_overflow: str = "strict"
    # scan-carried StepRecord telemetry (obs/telemetry.py): a
    # TelemetryConfig, a level string, or None.  Off/None adds nothing to
    # the traced program (bit-for-bit the untelemetered driver).
    telemetry: Optional[object] = None
    bytes_per_particle: float = 48.0
    seed: int = 0
    use_kernel: Optional[bool] = None  # None = auto (Pallas on TPU)
    scan: Optional[bool] = None        # None = auto (scan iff jittable)
    scan_chunk: int = 50               # steps per device-resident chunk


@dataclasses.dataclass
class CostModel:
    """Per-term model for simulated strong scaling (Fig 5).

    t_particle — seconds per particle push on one PE;
    t_byte     — seconds per byte crossing a node boundary;
    t_lb       — measured strategy planning time (filled by the driver).
      Diffusion planning is a *distributed* algorithm (O(K·iters) work per
      node); this container executes it serially, so its measured wall
      time is divided by num_pes.  Centralized planners (greedy*, metis*)
      are charged full wall time — matching their Charm++ deployments.
    """
    t_particle: float = 2.0e-8
    # calibrated so comm ≈ compute at the paper's 8-node operating point
    # (Fig 6 shows communication and computation time of the same
    # magnitude): ~50 MB/s effective per-PE boundary bandwidth (many small
    # particle messages on a shared NIC), not the wire peak.
    t_byte: float = 2.0e-8

    def lb_seconds(self, wall: float, strategy: str, num_pes: int) -> float:
        if strategy.startswith("diff"):
            return wall / max(num_pes, 1)
        return wall


@dataclasses.dataclass
class PICResult:
    max_avg: np.ndarray        # (T,) max/avg particles per PE
    ext_bytes: np.ndarray      # (T,) external comm bytes per step
    int_bytes: np.ndarray      # (T,)
    migrations: np.ndarray     # (T,) fraction of chares moved (LB steps)
    migrated_bytes: np.ndarray # (T,) particle bytes moved by LB
    lb_seconds: float
    step_seconds: np.ndarray   # (T,) modeled time per step
    final_x: np.ndarray
    final_y: np.ndarray
    scanned: bool = False
    wall_seconds: float = 0.0  # end-to-end wall time of the replay loop
    # (T,) max/avg load over global PEs under the two-level (node,
    # thread) placement; None unless PICConfig.threads_per_node was set
    thread_max_avg: Optional[np.ndarray] = None
    # (T,) 1.0 where the trigger fired and a rebalance was executed
    lb_steps: Optional[np.ndarray] = None
    # resilient sharded replay only (else None): (T,) 0/1 fired plans
    # rejected by the validate_plan guardrail, and (T,) particles the
    # spill exchange deferred on their source shard at each step
    plan_rejected: Optional[np.ndarray] = None
    deferred: Optional[np.ndarray] = None
    # StepRecord ring snapshot when PICConfig.telemetry was enabled
    telemetry: Optional[obs_telemetry.TelemetrySnapshot] = None

    def summary(self) -> Dict[str, float]:
        # mean ext/int ratio over steps with internal traffic; all-external
        # steps use the finite metrics sentinel, no-comm steps read 0
        from repro.core.metrics import EXT_INT_ALL_EXTERNAL

        ratio = np.where(
            self.int_bytes > 0,
            self.ext_bytes / np.where(self.int_bytes > 0,
                                      self.int_bytes, 1.0),
            np.where(self.ext_bytes > 0, EXT_INT_ALL_EXTERNAL, 0.0))
        return dict(
            mean_max_avg=float(self.max_avg.mean()),
            mean_ext_bytes=float(self.ext_bytes.mean()),
            mean_ext_int=float(ratio.mean()),
            total_migrated_bytes=float(self.migrated_bytes.sum()),
            lb_seconds=float(self.lb_seconds),
            modeled_time=float(self.step_seconds.sum()),
            wall_seconds=float(self.wall_seconds),
        )


def _lb_amort(cfg: PICConfig, trig) -> int:
    """Steps one plan's cost is amortized over in the modeled step time:
    the fixed cadence serves exactly ``lb_every`` steps per plan (the
    legacy accounting); an adaptive trigger's plan serves an interval
    known only after the fact, so its cost is charged where it fires."""
    if isinstance(trig, rt_triggers.EveryTrigger):
        return max(cfg.lb_every, 1)
    return 1


def _resolve_trigger(cfg: PICConfig):
    """Canonical trigger for a config (the strategy's registered policy
    backs ``cfg.trigger=None``; unknown strategies keep the legacy
    cadence)."""
    return rt_triggers.resolve_for_strategy(
        cfg.trigger, lb_every=cfg.lb_every, strategy=cfg.strategy)


def run(cfg: PICConfig, cost: CostModel = CostModel()) -> PICResult:
    if cfg.sweep_chunk is not None and cfg.strategy.startswith("diff"):
        cfg = dataclasses.replace(
            cfg, sweep_chunk=None,
            strategy_kwargs={**(cfg.strategy_kwargs or {}),
                             "sweep_chunk": cfg.sweep_chunk})
    if cfg.sharded_replay:
        if cfg.scan is False:
            raise ValueError(
                "sharded_replay is a scanned path; drop scan=False")
        from repro.distributed import replay_shard

        return replay_shard.run_pic_sharded(cfg, cost)
    if cfg.faults is not None and not getattr(cfg.faults, "empty", False):
        raise ValueError(
            "fault injection (PICConfig.faults) is a sharded-replay "
            "feature; set sharded_replay=True")
    if cfg.on_overflow != "strict":
        raise ValueError(
            "on_overflow='spill' degrades the sharded replay exchange; "
            "set sharded_replay=True (the single-device paths have no "
            "capacity to overflow)")
    use_scan = cfg.scan
    if use_scan and not core_engine.get_strategy(cfg.strategy).jittable:
        raise ValueError(
            f"strategy {cfg.strategy!r} is not jittable; the scanned PIC "
            "driver needs a traceable plan_fn (use scan=False/None or a "
            "diff-* / none strategy)")
    if use_scan is None:
        try:
            use_scan = core_engine.get_strategy(cfg.strategy).jittable
        except KeyError:
            use_scan = False
    tel = obs_telemetry.resolve(cfg.telemetry)
    tel = tel if tel.enabled else None
    if use_scan:
        return _run_scanned(cfg, cost, tel)
    return _run_host(cfg, cost, tel)


# ------------------------------------------------------------ scanned path --


@functools.lru_cache(maxsize=32)
def _chunk_runner(
    L: int, cx: int, cy: int, num_pes: int, k: int, vy0: float,
    lb_every: int, strategy: str, kw_items: tuple, bpp: float,
    use_kernel: Optional[bool], chunk_len: int,
    threads_per_node: Optional[int] = None,
    trig=None, tel=None,
):
    """Compiled ``lax.scan`` over ``chunk_len`` device-resident PIC steps."""
    n_chares = cx * cy
    grid_q = jnp.asarray(alternating_grid(L))
    trig = trig or rt_triggers.resolve(None, lb_every=lb_every)
    lb_on = strategy != "none" and not trig.never
    plan = (core_engine.get_strategy(strategy).bind(**dict(kw_items))
            if lb_on else None)
    tkind = obs_telemetry.trigger_kind(trig) if tel else 0

    def step(carry, t):
        if tel:
            x, y, vx, vy, q, chare_id, assignment, perm, tstate, \
                obs_state = carry
        else:
            x, y, vx, vy, q, chare_id, assignment, perm, tstate = carry
        xn, yn, vxn, vyn = pic_push(grid_q, x, y, vx, vy, q, L=L,
                                    use_kernel=use_kernel)
        with compat.named_scope("replay/handoff"):
            new_chare = ch.chare_of_device(xn, yn, L, cx, cy)
            # particle handoffs: chare changed → bytes move; PE boundary → ext
            moved = new_chare != chare_id
            src_pe = assignment[chare_id]
            dst_pe = assignment[new_chare]
            ext = ((moved & (src_pe != dst_pe)).sum().astype(jnp.float32)
                   * bpp)
            intra = ((moved & (src_pe == dst_pe)).sum()
                     .astype(jnp.float32) * bpp)

        loads = histogram(new_chare, jnp.ones_like(xn), C=n_chares,
                          use_kernel=use_kernel)
        pe_loads = jax.ops.segment_sum(loads, assignment,
                                       num_segments=num_pes)
        pe_max = pe_loads.max()
        ma = pe_max / (pe_loads.mean() + 1e-30)

        if lb_on:
            mx, av, tot = rt_triggers.load_stats(loads, assignment,
                                                 num_pes)
            do, tstate = trig.decide(tstate, t, mx, av, tot)

            def do_plan(args):
                loads_, assignment_ = args
                problem = ch.build_problem(
                    loads_, assignment_, L=L, cx=cx, cy=cy,
                    num_pes=num_pes, k=k, vy0=vy0, lb_period=lb_every,
                    bytes_per_particle=bpp)
                a2, stats = plan(problem)
                return a2, jnp.asarray(stats.diffusion_iters, jnp.float32)

            new_assignment, sweeps = jax.lax.cond(
                do, do_plan,
                lambda a: (a[1].astype(jnp.int32), jnp.float32(0.0)),
                (loads, assignment))
            delta = new_assignment != assignment
            migf = jnp.where(
                do, jnp.mean(delta.astype(jnp.float32)), 0.0)

            # execute the plan: relocate particle payload between the
            # PE-owned slot regions (bucketed gather — runtime.migrate);
            # migrated_bytes is measured from this exchange, not modeled
            with compat.named_scope("replay/owners"):
                owner_old = jnp.take(assignment, new_chare)
                owner_new = jnp.take(new_assignment, new_chare)

            def do_move(args):
                outs, man = rt_migrate.build_and_apply(
                    owner_old, owner_new, args, num_nodes=num_pes)
                return outs, man.moved_count

            (xn, yn, vxn, vyn, q, new_chare, perm), moved_n = jax.lax.cond(
                do, do_move, lambda args: (args, jnp.int32(0)),
                (xn, yn, vxn, vyn, q, new_chare, perm))
            # feed the executed exchange back (measured predictive gate):
            # load units are particles, matching the trigger's load stats
            tstate = trig.observe(tstate, moved_n.astype(jnp.float32), do)
            migb = moved_n.astype(jnp.float32) * bpp
            fired = do.astype(jnp.float32)
            assignment = new_assignment
        else:
            migf = jnp.float32(0.0)
            migb = jnp.float32(0.0)
            fired = jnp.float32(0.0)
            sweeps = jnp.float32(0.0)

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

        ys = (ma, pe_max, ext, intra, migf, migb, tma, fired)
        if tel:
            obs_state = obs_telemetry.record(
                obs_state, tel, t=t,
                node_loads=jax.ops.segment_sum(loads, assignment,
                                               num_segments=num_pes),
                fired=fired, trigger_kind=tkind, sweeps=sweeps,
                moved_items=migb / bpp, moved_bytes=migb)
            return (xn, yn, vxn, vyn, q, new_chare, assignment, perm,
                    tstate, obs_state), ys
        return (xn, yn, vxn, vyn, q, new_chare, assignment, perm,
                tstate), ys

    def run_chunk(carry, ts):
        return jax.lax.scan(step, carry, ts)

    return jax.jit(run_chunk)


def _run_scanned(cfg: PICConfig, cost: CostModel, tel=None) -> PICResult:
    p = initialize(cfg.mode, cfg.L, cfg.n_particles, k=cfg.k, vy0=cfg.vy0,
                   rho=cfg.rho, seed=cfg.seed)
    x, y = jnp.asarray(p.x), jnp.asarray(p.y)
    vx, vy = jnp.asarray(p.vx), jnp.asarray(p.vy)
    q = jnp.asarray(p.q)
    assignment = jnp.asarray(
        ch.initial_mapping(cfg.cx, cfg.cy, cfg.num_pes, cfg.mapping),
        jnp.int32)
    chare_id = ch.chare_of_device(x, y, cfg.L, cfg.cx, cfg.cy)
    n_chares = cfg.cx * cfg.cy

    kw_items = tuple(sorted((cfg.strategy_kwargs or {}).items()))
    trig = _resolve_trigger(cfg)
    lb_on = cfg.strategy != "none" and not trig.never

    # LB planning cost for the CostModel: the scanned path fuses planning
    # into the step executable, so per-call wall time is measured once on
    # the initial snapshot (post-compile) and charged at every LB step —
    # matching the legacy host path's per-call perf_counter semantics.
    lb_est = 0.0
    if lb_on:
        loads0 = histogram(chare_id, jnp.ones_like(x), C=n_chares,
                           use_kernel=cfg.use_kernel)
        problem0 = ch.build_problem(
            loads0, assignment, L=cfg.L, cx=cfg.cx, cy=cfg.cy,
            num_pes=cfg.num_pes, k=cfg.k, vy0=cfg.vy0,
            lb_period=cfg.lb_every,
            bytes_per_particle=cfg.bytes_per_particle)
        strat = core_engine.get_strategy(cfg.strategy)
        strat.run(problem0, **dict(kw_items))          # warm the compile
        lb_est = strat.run(problem0, **dict(kw_items)).info["plan_seconds"]

    T = cfg.steps
    chunk = max(1, min(cfg.scan_chunk, T))
    carry = (x, y, vx, vy, q, chare_id, assignment,
             jnp.arange(cfg.n_particles, dtype=jnp.int32),
             trig.init_state())
    if tel:
        carry = carry + (obs_telemetry.init_state(tel, cfg.num_pes),)
    ys_host = []
    t_start = time.perf_counter()
    for s in range(0, T, chunk):
        n = min(chunk, T - s)
        runner = _chunk_runner(
            cfg.L, cfg.cx, cfg.cy, cfg.num_pes, cfg.k, cfg.vy0,
            cfg.lb_every, cfg.strategy, kw_items, cfg.bytes_per_particle,
            cfg.use_kernel, n, cfg.threads_per_node, trig, tel)
        carry, ys = runner(carry, jnp.arange(s, s + n))
        ys_host.append(jax.device_get(ys))   # host transfer per chunk only
    wall = time.perf_counter() - t_start

    ma, pe_max, ext_b, int_b, mig, mig_bytes, tma, fired = (
        np.concatenate([np.asarray(c[i], np.float64) for c in ys_host])
        for i in range(8))

    lb_steps = fired > 0
    lb_s_t = np.where(lb_steps, lb_est, 0.0)
    step_s = (
        pe_max * cost.t_particle
        + (ext_b + mig_bytes) * cost.t_byte
        + np.array([cost.lb_seconds(s_, cfg.strategy, cfg.num_pes)
                    for s_ in lb_s_t]) / _lb_amort(cfg, trig)
    )
    # the carry holds slot-ordered particles (bucketed by owning PE);
    # report them in original particle-id order, undoing the exchanges
    perm = np.asarray(carry[7])
    xs, ys_ = np.asarray(carry[0]), np.asarray(carry[1])
    fx, fy = np.empty_like(xs), np.empty_like(ys_)
    fx[perm], fy[perm] = xs, ys_
    return PICResult(ma, ext_b, int_b, mig, mig_bytes,
                     float(lb_est * lb_steps.sum()), step_s, fx, fy,
                     scanned=True, wall_seconds=wall,
                     thread_max_avg=(tma if cfg.threads_per_node else None),
                     lb_steps=fired,
                     telemetry=(obs_telemetry.snapshot(carry[9], tel)
                                if tel else None))


# --------------------------------------------------------------- host loop --


def _run_host(cfg: PICConfig, cost: CostModel, tel=None) -> PICResult:
    grid_q = jnp.asarray(alternating_grid(cfg.L))
    p = initialize(cfg.mode, cfg.L, cfg.n_particles, k=cfg.k, vy0=cfg.vy0,
                   rho=cfg.rho, seed=cfg.seed)
    x, y = jnp.asarray(p.x), jnp.asarray(p.y)
    vx, vy = jnp.asarray(p.vx), jnp.asarray(p.vy)
    q = jnp.asarray(p.q)

    n_chares = cfg.cx * cfg.cy
    assignment = ch.initial_mapping(cfg.cx, cfg.cy, cfg.num_pes, cfg.mapping)
    chare_id = np.asarray(ch.chare_of(p.x, p.y, cfg.L, cfg.cx, cfg.cy))
    perm = np.arange(cfg.n_particles, dtype=np.int32)

    trig = _resolve_trigger(cfg)
    lb_on = cfg.strategy != "none" and not trig.never
    tstate = trig.init_state()

    T = cfg.steps
    ma = np.zeros(T)
    ext_b = np.zeros(T)
    int_b = np.zeros(T)
    mig = np.zeros(T)
    mig_bytes = np.zeros(T)
    tma = np.zeros(T)
    step_s = np.zeros(T)
    fired = np.zeros(T)
    lb_seconds = 0.0
    obs_state = (obs_telemetry.init_state(tel, cfg.num_pes)
                 if tel else None)
    tkind = obs_telemetry.trigger_kind(trig) if tel else 0

    t_start = time.perf_counter()
    for t in range(T):
        xn, yn, vx, vy = pic_push(grid_q, x, y, vx, vy, q, L=cfg.L,
                                  use_kernel=cfg.use_kernel)
        new_chare = np.asarray(
            ch.chare_of(np.asarray(xn), np.asarray(yn), cfg.L, cfg.cx, cfg.cy)
        )
        # particle handoffs: chare changed → bytes move; PE boundary → external
        moved = new_chare != chare_id
        src_pe = assignment[chare_id[moved]]
        dst_pe = assignment[new_chare[moved]]
        ext = float((src_pe != dst_pe).sum()) * cfg.bytes_per_particle
        intra = float((src_pe == dst_pe).sum()) * cfg.bytes_per_particle
        x, y, chare_id = xn, yn, new_chare

        loads = np.asarray(
            histogram(jnp.asarray(chare_id), jnp.ones(cfg.n_particles),
                      C=n_chares, use_kernel=cfg.use_kernel)
        )
        pe_loads = np.bincount(assignment, weights=loads,
                               minlength=cfg.num_pes)
        ma[t] = pe_loads.max() / (pe_loads.mean() + 1e-30)
        ext_b[t], int_b[t] = ext, intra

        lb_s = 0.0
        do = False
        if lb_on:
            if isinstance(trig, rt_triggers.EveryTrigger):
                # fixed cadence ignores the stats: legacy predicate,
                # no per-step device trip
                do = t > 0 and t % trig.every == 0
            else:
                # identical expression graph to the scanned path (f32
                # stats + jnp decide), so adaptive triggers fire on the
                # same steps
                mx, av, tot = rt_triggers.load_stats_jit(
                    jnp.asarray(loads, jnp.float32),
                    jnp.asarray(assignment, jnp.int32), cfg.num_pes)
                d, tstate = trig.decide(tstate, jnp.int32(t), mx, av, tot)
                do = bool(d)
        if do:
            problem = ch.build_problem(
                loads, assignment, L=cfg.L, cx=cfg.cx, cy=cfg.cy,
                num_pes=cfg.num_pes, k=cfg.k, vy0=cfg.vy0,
                lb_period=cfg.lb_every,
                bytes_per_particle=cfg.bytes_per_particle,
            )
            t0 = time.perf_counter()
            plan = core_api.STRATEGIES[cfg.strategy](
                problem, **(cfg.strategy_kwargs or {})
            )
            lb_s = time.perf_counter() - t0
            lb_seconds += lb_s
            new_assignment = np.asarray(plan.assignment)
            moved_chares = new_assignment != assignment
            mig[t] = float(moved_chares.mean())
            fired[t] = 1.0

            # execute the plan: bucket particles into PE-owned slot
            # regions; migrated bytes measured from the exchange
            # shared manifest path (runtime.migrate) — the identical
            # permutation code the scanned driver runs, so host and
            # scanned replay share one parity surface
            owner_old = assignment[chare_id]
            owner_new = new_assignment[chare_id].astype(np.int32)
            (x, y, vx, vy, q, ch_j, pm_j), man = rt_migrate.migrate(
                owner_old, owner_new,
                (x, y, vx, vy, q, jnp.asarray(chare_id, jnp.int32),
                 jnp.asarray(perm, jnp.int32)),
                num_nodes=cfg.num_pes)
            moved_n = int(man.moved_count)
            mig_bytes[t] = float(moved_n * cfg.bytes_per_particle)
            chare_id = np.asarray(ch_j)
            perm = np.asarray(pm_j)
            assignment = new_assignment.astype(np.int32)
        if lb_on and not isinstance(trig, rt_triggers.EveryTrigger):
            # measured predictive gate: same f32 particle count the
            # scanned path observes (moved_n for fired steps, else 0)
            tstate = trig.observe(
                tstate,
                jnp.float32(mig_bytes[t] / cfg.bytes_per_particle),
                jnp.asarray(bool(do)))

        if cfg.threads_per_node:
            # same device-resident LPT as the scanned path (f32 parity)
            thr = hierarchical.lpt_threads(
                jnp.asarray(loads, jnp.float32),
                jnp.asarray(assignment, jnp.int32),
                num_nodes=cfg.num_pes,
                threads_per_node=cfg.threads_per_node)
            tl = hierarchical.thread_loads(
                jnp.asarray(loads, jnp.float32),
                jnp.asarray(assignment, jnp.int32), thr,
                num_nodes=cfg.num_pes,
                threads_per_node=cfg.threads_per_node)
            tma[t] = float(tl.max() / (tl.mean() + 1e-30))

        if tel:
            obs_state = obs_telemetry.record(
                obs_state, tel, t=t,
                node_loads=np.bincount(assignment, weights=loads,
                                       minlength=cfg.num_pes),
                fired=fired[t], trigger_kind=tkind,
                moved_items=mig_bytes[t] / cfg.bytes_per_particle,
                moved_bytes=mig_bytes[t])

        # modeled step time: slowest PE compute + boundary traffic + LB
        step_s[t] = (
            pe_loads.max() * cost.t_particle
            + (ext + mig_bytes[t]) * cost.t_byte
            + cost.lb_seconds(lb_s, cfg.strategy, cfg.num_pes)
            / _lb_amort(cfg, trig)
        )

    xs, ys_ = np.asarray(x), np.asarray(y)
    fx, fy = np.empty_like(xs), np.empty_like(ys_)
    fx[perm], fy[perm] = xs, ys_     # undo the executed exchanges
    return PICResult(ma, ext_b, int_b, mig, mig_bytes, lb_seconds, step_s,
                     fx, fy, scanned=False,
                     wall_seconds=time.perf_counter() - t_start,
                     thread_max_avg=(tma if cfg.threads_per_node else None),
                     lb_steps=fired,
                     telemetry=(obs_telemetry.snapshot(obs_state, tel)
                                if tel else None))
