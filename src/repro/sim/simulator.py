"""Load-balancing simulation infrastructure (paper §V).

The paper's simulator takes (loads, coords, comm edges) snapshots from any
Charm++ application and replays strategies at any scale on one process; ours
does the same for ``LBProblem`` instances.  ``compare`` runs a set of
strategies on one snapshot; ``run_series`` replays a time-evolving workload
with periodic rebalancing (used by the PIC driver and Fig 4/5 benchmarks).

``run_series`` has two execution paths:

  * **scanned** — when the strategy is jittable (``engine.Strategy``) and
    ``evolve`` is scan-safe (scenarios from sim/scenarios.py mark theirs
    with ``evolve.jittable = True``), the whole replay compiles to a single
    ``jax.lax.scan``: evolve + ``lax.cond``-gated planning + device-side
    metrics per step, with exactly one host transfer at the end.  Compiled
    runners are cached, so repeated replays (parameter sweeps, many
    scenarios) pay tracing once.
  * **host loop** — the legacy eager path, kept for the NumPy baselines
    (greedy, metis, ...) and for host-side ``evolve`` callables.

``run_series_batch`` is the third path: B independent workloads at a
common shape (e.g. every registered scenario from ``sim/scenarios.py``,
via ``scenarios.batch_instances``) replayed in **one** vmapped scan — a
single compiled call plans and evolves all B lanes per step instead of a
Python loop over scenarios, with the stacked problem buffers donated to
the executable on accelerators.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import api, comm_graph, engine, hierarchical, metrics
from repro.obs import telemetry as obs_telemetry
from repro.runtime import migrate as rt_migrate
from repro.runtime import triggers as rt_triggers


@dataclasses.dataclass
class CompareRow:
    strategy: str
    before: Dict[str, float]
    after: Dict[str, float]
    info: Dict


def compare(
    problem: comm_graph.LBProblem,
    strategies: Sequence[str],
    strategy_kwargs: Optional[Dict[str, Dict]] = None,
) -> List[CompareRow]:
    strategy_kwargs = strategy_kwargs or {}
    before = metrics.evaluate(problem)
    rows = []
    for name in strategies:
        plan = api.run_strategy(name, problem, **strategy_kwargs.get(name, {}))
        after = metrics.evaluate(problem, jnp.asarray(plan.assignment))
        # load volume the plan would migrate — the honest §II metric-3
        # numerator (benchmarks price it via RuntimeCostModel)
        moved = np.asarray(plan.assignment) != np.asarray(problem.assignment)
        plan.info["migrated_load"] = float(
            np.where(moved, np.asarray(problem.loads, np.float32),
                     np.float32(0)).sum())
        rows.append(CompareRow(name, before, after, plan.info))
    return rows


def format_table(rows: List[CompareRow]) -> str:
    """Paper-Table-II-style text table."""
    cols = ["strategy", "max/avg", "ext/int", "%migr", "plan_s"]
    out = ["  ".join(f"{c:>12}" for c in cols)]
    if rows:
        b = rows[0].before
        out.append("  ".join([
            f"{'(initial)':>12}", f"{b['max_avg_load']:>12.3f}",
            f"{b['ext_int_comm']:>12.3f}", f"{'-':>12}", f"{'-':>12}",
        ]))
    for r in rows:
        out.append("  ".join([
            f"{r.strategy:>12}",
            f"{r.after['max_avg_load']:>12.3f}",
            f"{r.after['ext_int_comm']:>12.3f}",
            f"{100*r.after['pct_migrations']:>11.1f}%",
            f"{r.info.get('plan_seconds', float('nan')):>12.3f}",
        ]))
    return "\n".join(out)


@dataclasses.dataclass
class SeriesResult:
    max_avg: np.ndarray        # (T,) per step
    ext_int: np.ndarray        # (T,)
    migrations: np.ndarray     # (T,) fraction moved at that step (0 if no LB)
    plan_seconds: float        # host path: cumulative planning wall time;
                               # scanned path: wall time of the whole replay
    scanned: bool = False
    wall_seconds: float = 0.0  # total replay wall time (both paths)
    # (T,) per-step max/avg load across all P*T global PEs under the
    # two-level (node, thread) placement — only when ``threads_per_node``
    # was requested (None otherwise)
    thread_max_avg: Optional[np.ndarray] = None
    # runtime-era per-step records (None on the batched path): whether the
    # trigger fired, the pre-metrics max node load, and the total load of
    # the objects the rebalance moved — the inputs to
    # ``runtime.cost.series_modeled_seconds``
    lb_fired: Optional[np.ndarray] = None      # (T,) 0/1
    max_load: Optional[np.ndarray] = None      # (T,)
    migrated_load: Optional[np.ndarray] = None  # (T,)
    # (N,) final object→node assignment after the last step (None on the
    # batched path) — the sharded-replay parity contract asserts it
    final_assignment: Optional[np.ndarray] = None
    # (T,) 0/1 — fired plans the validate_plan guardrail rejected (and
    # rolled back); only recorded by the resilient sharded replay paths
    # (``faults`` / ``guard``), None everywhere else
    plan_rejected: Optional[np.ndarray] = None
    # scan-carried StepRecord ring (obs/telemetry.py) — only when the
    # replay was passed an enabled TelemetryConfig, None otherwise
    telemetry: Optional[obs_telemetry.TelemetrySnapshot] = None


def run_series(
    initial: comm_graph.LBProblem,
    evolve: Callable[[comm_graph.LBProblem, int], comm_graph.LBProblem],
    *,
    steps: int,
    lb_every: int,
    strategy: str = "diff-comm",
    strategy_kwargs: Optional[Dict] = None,
    scan: Optional[bool] = None,
    threads_per_node: Optional[int] = None,
    trigger=None,
    telemetry=None,
) -> SeriesResult:
    """Replay ``steps`` of a workload with trigger-policed rebalancing.

    ``evolve(problem, t)`` advances loads/comm one application step while
    preserving the current assignment (the simulator's stand-in for the
    application's own dynamics).  ``scan=None`` auto-selects the scanned
    path when both the strategy and ``evolve`` are jit-traceable.

    ``trigger`` selects the online rebalancing policy
    (``runtime.triggers``): ``None`` falls back to the strategy's
    registered trigger (e.g. ``"diff-comm+threshold"``) and then to the
    legacy fixed period — ``trigger="every"`` (or ``None`` on a plain
    strategy) reproduces the pre-runtime ``lb_every`` replay
    **bit-for-bit** on both paths.  ``"threshold"`` / ``"predictive"``
    (or a configured ``Trigger`` instance) decide per step from the
    pre-LB load statistics, identically on the host and scanned paths.
    Per-step ``lb_fired`` / ``max_load`` / ``migrated_load`` records feed
    ``runtime.cost.series_modeled_seconds``.

    ``threads_per_node`` enables the two-level (node, thread) view (paper
    §III.D): each step additionally records the max/avg load across all
    ``P * T`` global PEs under the within-node LPT placement
    (``hierarchical.lpt_threads`` — computed on device in the scanned
    path) in ``SeriesResult.thread_max_avg``.  The batched replay
    (``run_series_batch``) takes neither knob.

    :func:`run_series_sharded` is the mesh-sharded sibling: the same
    scanned loop (same knobs, bit-for-bit the same ``SeriesResult``)
    executed inside one ``shard_map`` over the 1-D ``"lb"`` device mesh
    with the planner's diffusion stage running as ring halo exchanges.

    ``telemetry`` (a :class:`repro.obs.telemetry.TelemetryConfig`, a level
    string, or ``None``) opts the replay into the scan-carried StepRecord
    ring; ``level="off"`` / ``None`` adds nothing to the traced program
    and is bit-for-bit identical to the pre-telemetry replay."""
    strategy_kwargs = strategy_kwargs or {}
    tel = obs_telemetry.resolve(telemetry)
    tel = tel if tel.enabled else None
    trig = rt_triggers.resolve_for_strategy(trigger, lb_every=lb_every,
                                            strategy=strategy)
    if scan:
        strat = engine.get_strategy(strategy)
        if not strat.jittable:
            raise ValueError(
                f"strategy {strategy!r} is not jittable; the scanned replay "
                "needs a traceable plan_fn (use scan=False or a diff-* / "
                "none strategy)")
    if scan is None:
        try:
            jittable = engine.get_strategy(strategy).jittable
        except KeyError:
            jittable = False
        scan = jittable and getattr(evolve, "jittable", False)
    if scan:
        return _run_series_scanned(
            initial, evolve, steps=steps, lb_every=lb_every,
            strategy=strategy, strategy_kwargs=strategy_kwargs,
            threads_per_node=threads_per_node, trig=trig, tel=tel)
    return _run_series_host(
        initial, evolve, steps=steps, lb_every=lb_every,
        strategy=strategy, strategy_kwargs=strategy_kwargs,
        threads_per_node=threads_per_node, trig=trig, tel=tel)


def run_series_sharded(initial, evolve, **kwargs):
    """Mesh-sharded ``run_series``: the whole replay (evolve → trigger →
    sharded plan → assignment update) inside one ``shard_map`` over the
    1-D ``"lb"`` device mesh, bit-for-bit the scanned single-device
    path.  Thin forwarder to
    :func:`repro.distributed.replay_shard.run_series_sharded` (kept
    lazy so ``sim`` stays importable without the distributed stack)."""
    from repro.distributed import replay_shard

    return replay_shard.run_series_sharded(initial, evolve, **kwargs)


# ------------------------------------------------------------- host loop --


def _compiled_evolve(evolve):
    """``evolve`` compiled as the scan body compiles it, step index
    traced.  Op-by-op dispatch rounds unfused intermediates differently,
    and an ulp in one load can flip a planner tie, so an eager evolve
    would make the host loop replay another workload than the scan."""
    jitted = jax.jit(evolve)
    return lambda p, t: jitted(p, jnp.int32(t))


def _run_series_host(initial, evolve, *, steps, lb_every, strategy,
                     strategy_kwargs, threads_per_node=None,
                     trig=None, tel=None) -> SeriesResult:
    trig = trig or rt_triggers.resolve(None, lb_every=lb_every)
    if getattr(evolve, "jittable", False):
        evolve = _compiled_evolve(evolve)
    t_start = time.perf_counter()
    problem = initial
    ma, ei, mig, tma = [], [], [], []
    fired, mxl, migl = [], [], []
    plan_s = 0.0
    obs_state = (obs_telemetry.init_state(tel, initial.num_nodes)
                 if tel else None)
    tkind = obs_telemetry.trigger_kind(trig) if tel else 0
    lb_on = strategy != "none" and not trig.never
    # the fixed cadence ignores the load stats: keep the legacy pure-
    # Python predicate (bit-identical) instead of a per-step device trip
    is_every = isinstance(trig, rt_triggers.EveryTrigger)
    tstate = trig.init_state()
    for t in range(steps):
        problem = evolve(problem, t)
        do = False
        if lb_on:
            if is_every:
                do = t > 0 and t % trig.every == 0
            else:
                # same jnp expression graph as the scanned path, so
                # adaptive threshold comparisons agree bitwise across
                # paths
                mx, av, tot = rt_triggers.load_stats_jit(
                    jnp.asarray(problem.loads, jnp.float32),
                    jnp.asarray(problem.assignment, jnp.int32),
                    problem.num_nodes)
                d, tstate = trig.decide(tstate, jnp.int32(t), mx, av, tot)
                do = bool(d)
        moved_n = 0.0
        sweeps = 0.0
        if do:
            plan = api.run_strategy(strategy, problem, **strategy_kwargs)
            delta = plan.assignment != np.asarray(problem.assignment)
            moved = float(np.mean(delta))
            moved_n = float(np.sum(delta))
            sweeps = float(plan.info.get("diffusion_iters", 0.0))
            migl.append(float(jnp.where(
                jnp.asarray(delta),
                jnp.asarray(problem.loads, jnp.float32), 0.0).sum()))
            problem = problem.with_assignment(jnp.asarray(plan.assignment))
            plan_s += plan.info.get("plan_seconds", 0.0)
            mig.append(moved)
        else:
            mig.append(0.0)
            migl.append(0.0)
        if lb_on and not is_every:
            # feed the executed exchange volume back (measured predictive
            # gate) — same f32 value the scanned path observes, so the
            # two paths keep firing on identical steps
            tstate = trig.observe(tstate, jnp.float32(migl[-1]),
                                  jnp.asarray(do))
        fired.append(1.0 if do else 0.0)
        m = metrics.evaluate(problem)
        ma.append(m["max_avg_load"])
        ei.append(m["ext_int_comm"])
        mxl.append(m["max_load"])
        if threads_per_node:
            tma.append(float(_thread_max_avg(
                problem.loads, problem.assignment,
                problem.num_nodes, threads_per_node)))
        if tel:
            obs_state = obs_telemetry.record(
                obs_state, tel, t=t,
                node_loads=obs_telemetry.node_loads(
                    problem.loads, problem.assignment, problem.num_nodes),
                fired=fired[-1], trigger_kind=tkind, sweeps=sweeps,
                moved_items=moved_n, moved_bytes=migl[-1])
    return SeriesResult(np.array(ma), np.array(ei), np.array(mig), plan_s,
                        scanned=False,
                        wall_seconds=time.perf_counter() - t_start,
                        thread_max_avg=(np.array(tma) if threads_per_node
                                        else None),
                        lb_fired=np.array(fired), max_load=np.array(mxl),
                        migrated_load=np.array(migl),
                        final_assignment=np.asarray(problem.assignment,
                                                    np.int32),
                        telemetry=(obs_telemetry.snapshot(obs_state, tel)
                                   if tel else None))


# ---------------------------------------------------------- scanned path --


def _thread_max_avg(loads, assignment, num_nodes: int,
                    threads_per_node: int):
    """Traceable max/avg PE load under the two-level LPT placement."""
    thr = hierarchical.lpt_threads(
        jnp.asarray(loads, jnp.float32),
        jnp.asarray(assignment, jnp.int32),
        num_nodes=num_nodes, threads_per_node=threads_per_node)
    tl = hierarchical.thread_loads(
        loads, assignment, thr, num_nodes=num_nodes,
        threads_per_node=threads_per_node)
    return (tl.max() / (tl.mean() + 1e-30)).astype(jnp.float32)


@functools.lru_cache(maxsize=64)
def _scanned_runner(evolve, steps: int, lb_every: int, strategy: str,
                    kw_items: tuple, threads_per_node: Optional[int] = None,
                    trig=None, tel=None):
    """Compile-once scan over the whole replay.

    Cache key: the evolve closure (identity), the static replay shape,
    the strategy binding, the trigger policy and the telemetry config
    (all frozen dataclasses) — re-running the same scenario/strategy/
    trigger reuses the compiled executable.  ``tel=None`` (telemetry off)
    adds nothing to the trace: the carry and every expression below are
    identical to the pre-telemetry runner."""
    strat = engine.get_strategy(strategy)
    plan = strat.bind(**dict(kw_items))
    trig = trig or rt_triggers.resolve(None, lb_every=lb_every)
    do_lb_at_all = strategy != "none" and not trig.never
    tkind = obs_telemetry.trigger_kind(trig) if tel else 0

    def step(carry, t):
        if tel:
            problem, tstate, obs_state = carry
        else:
            problem, tstate = carry
        problem = evolve(problem, t)
        prev = problem.assignment
        sweeps = jnp.float32(0.0)
        moved_n = jnp.float32(0.0)
        if do_lb_at_all:
            mx, av, tot = rt_triggers.load_stats(
                problem.loads, problem.assignment, problem.num_nodes)
            do, tstate = trig.decide(tstate, t, mx, av, tot)
            new_assignment, stats = jax.lax.cond(
                do,
                plan,
                lambda p: (p.assignment.astype(jnp.int32),
                           engine.zero_stats()),
                problem,
            )
            delta = new_assignment != prev
            moved = jnp.where(
                do, jnp.mean(delta.astype(jnp.float32)), 0.0)
            migrated_load = jnp.where(
                do,
                jnp.where(delta,
                          jnp.asarray(problem.loads, jnp.float32),
                          0.0).sum(),
                0.0)
            # executed-exchange feedback for the measured predictive gate
            tstate = trig.observe(tstate, migrated_load, do)
            fired = do.astype(jnp.float32)
            problem = problem.with_assignment(new_assignment)
            if tel:
                sweeps = jnp.asarray(stats.diffusion_iters, jnp.float32)
                moved_n = delta.sum().astype(jnp.float32)
        else:
            moved = jnp.float32(0.0)
            migrated_load = jnp.float32(0.0)
            fired = jnp.float32(0.0)
        m = metrics.evaluate_device(problem)
        if threads_per_node:
            tma = _thread_max_avg(problem.loads, problem.assignment,
                                  problem.num_nodes, threads_per_node)
        else:
            tma = jnp.float32(0.0)
        ys = (m.max_avg_load, m.ext_int_comm, moved,
              tma, fired, m.max_load, migrated_load)
        if tel:
            obs_state = obs_telemetry.record(
                obs_state, tel, t=t,
                node_loads=obs_telemetry.node_loads(
                    problem.loads, problem.assignment, problem.num_nodes),
                fired=fired, trigger_kind=tkind, sweeps=sweeps,
                moved_items=moved_n, moved_bytes=migrated_load)
            return (problem, tstate, obs_state), ys
        return (problem, tstate), ys

    def run(problem):
        carry = (problem, trig.init_state())
        if tel:
            carry = carry + (obs_telemetry.init_state(
                tel, problem.num_nodes),)
        return jax.lax.scan(step, carry, jnp.arange(steps))

    return jax.jit(run)


def _canonical(problem: comm_graph.LBProblem) -> comm_graph.LBProblem:
    """Device arrays with the carry dtypes the scan expects."""
    return dataclasses.replace(
        problem,
        loads=jnp.asarray(problem.loads, jnp.float32),
        assignment=jnp.asarray(problem.assignment, jnp.int32),
        edges_src=jnp.asarray(problem.edges_src, jnp.int32),
        edges_dst=jnp.asarray(problem.edges_dst, jnp.int32),
        edges_bytes=jnp.asarray(problem.edges_bytes, jnp.float32),
        coords=None if problem.coords is None
        else jnp.asarray(problem.coords, jnp.float32),
    )


# ---------------------------------------------------------- batched path --


@dataclasses.dataclass
class BatchSeriesResult:
    """One vmapped replay of B workloads: per-lane series + batch wall."""

    series: List[SeriesResult]   # one per input instance, in order
    wall_seconds: float          # wall time of the whole batched replay
    steps: int

    @property
    def batch(self) -> int:
        return len(self.series)

    @property
    def lane_steps_per_sec(self) -> float:
        """Aggregate throughput: (B × T) scenario-steps per second."""
        return self.batch * self.steps / max(self.wall_seconds, 1e-12)


def _shape_preserving(evolve):
    """Wrap ``evolve`` to keep the batch's padded edge envelope.

    Inside the batched scan each lane's problem carries edge lists padded
    to the batch-wide maximum; an evolve that rebuilds ``edges_bytes`` at
    its native length (the PIC proxy) would otherwise shrink the carry.
    Re-pads with the standard (-1, -1, 0.0) edge padding."""

    def ev(p, t):
        q = evolve(p, t)
        fixes = {}
        for field, fill in (("edges_src", -1), ("edges_dst", -1),
                            ("edges_bytes", 0.0)):
            old, new = getattr(p, field), getattr(q, field)
            if new.shape != old.shape:
                fixes[field] = jnp.pad(
                    jnp.asarray(new, old.dtype),
                    (0, old.shape[0] - new.shape[0]), constant_values=fill)
        return dataclasses.replace(q, **fixes) if fixes else q

    return ev


@functools.lru_cache(maxsize=16)
def _batched_runner(evolves: tuple, lane_branch: tuple, steps: int,
                    lb_every: int, strategy: str, kw_items: tuple):
    """Compile-once vmapped scan over B lanes × ``steps`` steps.

    ``evolves`` are the distinct evolve closures (``lax.switch`` branches);
    ``lane_branch[b]`` maps lane b to its branch.  Cached on the closure
    identities + replay shape, so re-running the same batch reuses the
    executable."""
    strat = engine.get_strategy(strategy)
    plan = strat.bind(**dict(kw_items))
    do_lb_at_all = strategy != "none" and lb_every > 0
    branches = [_shape_preserving(ev) for ev in evolves]

    # lane→evolve is static, so lanes are grouped per distinct evolve and
    # each group vmapped over its slice — a lax.switch on a vmapped index
    # would instead run *every* branch for *every* lane (O(B²) evolve work)
    groups = sorted(
        (b, tuple(l for l, lb in enumerate(lane_branch) if lb == b))
        for b in set(lane_branch))
    order = [l for _, lanes in groups for l in lanes]
    # device-resident O(B) inverse (shared with the migration manifests)
    # instead of a host argsort
    inv_order = rt_migrate.inverse_permutation(
        np.asarray(order, np.int32))
    single = len(groups) == 1

    def evolve_all(ps, t):
        if single:
            return jax.vmap(lambda p: branches[0](p, t))(ps)
        parts = [
            jax.vmap(lambda p, b=b: branches[b](p, t))(
                jax.tree_util.tree_map(
                    lambda a, lanes=lanes: a[jnp.asarray(lanes)], ps))
            for b, lanes in groups
        ]
        merged = jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(xs, axis=0), *parts)
        return jax.tree_util.tree_map(lambda a: a[inv_order], merged)

    def run(problems):
        def step(ps, t):
            ps = evolve_all(ps, t)
            if do_lb_at_all:
                # the LB-period predicate is uniform across lanes, so the
                # cond stays *outside* the vmap — a per-lane cond would
                # batch into a select that runs the planner every step
                do = (t > 0) & (t % lb_every == 0)
                prev = ps.assignment                       # (B, N)
                new_assignment = jax.lax.cond(
                    do,
                    lambda ps: jax.vmap(plan)(ps)[0].astype(jnp.int32),
                    lambda ps: ps.assignment.astype(jnp.int32),
                    ps,
                )
                moved = jnp.where(
                    do,
                    jnp.mean((new_assignment != prev).astype(jnp.float32),
                             axis=1),
                    jnp.zeros(prev.shape[0], jnp.float32))
                ps = ps.with_assignment(new_assignment)
            else:
                moved = jnp.zeros(ps.assignment.shape[0], jnp.float32)
            m = jax.vmap(metrics.evaluate_device)(ps)
            return ps, (m.max_avg_load, m.ext_int_comm, moved)

        return jax.lax.scan(step, problems, jnp.arange(steps))

    # the stacked carry is staged by run_series_batch and never reused —
    # donate it where the backend supports donation (not CPU XLA)
    donate = (0,) if jax.default_backend() != "cpu" else ()
    return jax.jit(run, donate_argnums=donate)


def run_series_batch(
    instances: Sequence,
    *,
    steps: int,
    lb_every: int,
    strategy: str = "diff-comm",
    strategy_kwargs: Optional[Dict] = None,
) -> BatchSeriesResult:
    """Replay B workloads in one vmapped scan (one compiled call).

    ``instances`` is a sequence of ``(problem, evolve)`` pairs — or
    ``(name, problem, evolve)`` triples as produced by
    ``scenarios.batch_instances`` — at a common ``(num_nodes, num_objects)``
    shape (edge lists are padded to the longest).  Every ``evolve`` must be
    scan-safe and the strategy jittable; distinct evolves become
    ``lax.switch`` branches selected per lane."""
    strategy_kwargs = strategy_kwargs or {}
    strat = engine.get_strategy(strategy)
    if not strat.jittable:
        raise ValueError(
            f"strategy {strategy!r} is not jittable; the batched replay "
            "needs a traceable plan_fn (diff-* / none)")
    if strat.trigger is not None:
        # refuse rather than silently downgrade the wrapped strategy's
        # adaptive policy to the fixed cadence (per-lane trigger state in
        # the vmapped carry is a ROADMAP item)
        raise ValueError(
            f"strategy {strategy!r} carries an adaptive trigger; the "
            "batched replay only supports the fixed lb_every cadence — "
            f"use run_series or the base strategy")
    pairs = [inst[-2:] for inst in instances]
    for _, ev in pairs:
        if not getattr(ev, "jittable", False):
            raise ValueError(
                "every evolve in a batched replay must be scan-safe "
                "(scenarios from sim/scenarios.py are)")
    uniq: List = []
    lane_branch = []
    for _, ev in pairs:
        if ev not in uniq:
            uniq.append(ev)
        lane_branch.append(uniq.index(ev))
    runner = _batched_runner(
        tuple(uniq), tuple(lane_branch), steps, lb_every, strategy,
        tuple(sorted(strategy_kwargs.items())))
    stacked = comm_graph.stack_problems(
        [_canonical(p) for p, _ in pairs])
    t_start = time.perf_counter()
    _final, (ma, ei, mig) = runner(stacked)
    ma, ei, mig = jax.device_get((ma, ei, mig))   # (T, B) each
    wall = time.perf_counter() - t_start
    series = [
        SeriesResult(np.asarray(ma[:, b], np.float64),
                     np.asarray(ei[:, b], np.float64),
                     np.asarray(mig[:, b], np.float64),
                     wall, scanned=True, wall_seconds=wall)
        for b in range(len(pairs))
    ]
    return BatchSeriesResult(series, wall, steps)


def _run_series_scanned(initial, evolve, *, steps, lb_every, strategy,
                        strategy_kwargs, threads_per_node=None,
                        trig=None, tel=None) -> SeriesResult:
    runner = _scanned_runner(
        evolve, steps, lb_every, strategy,
        tuple(sorted(strategy_kwargs.items())), threads_per_node, trig, tel)
    t_start = time.perf_counter()
    try:
        final, ys = runner(_canonical(initial))
    except jax.errors.TracerArrayConversionError as e:
        # scan=True forced with a host-NumPy evolve: surface the cause
        # instead of the opaque tracer leak from inside lax.scan
        raise ValueError(
            "the evolve callable is not jit-traceable (it converts traced "
            "arrays to NumPy); use scan=False or a pure-jnp evolve — "
            "scenarios from sim/scenarios.py are scan-safe") from e
    ma, ei, mig, tma, fired, mxl, migl = jax.device_get(ys)
    wall = time.perf_counter() - t_start
    return SeriesResult(np.asarray(ma, np.float64), np.asarray(ei, np.float64),
                        np.asarray(mig, np.float64), wall, scanned=True,
                        wall_seconds=wall,
                        thread_max_avg=(np.asarray(tma, np.float64)
                                        if threads_per_node else None),
                        lb_fired=np.asarray(fired, np.float64),
                        max_load=np.asarray(mxl, np.float64),
                        migrated_load=np.asarray(migl, np.float64),
                        final_assignment=np.asarray(final[0].assignment,
                                                    np.int32),
                        telemetry=(obs_telemetry.snapshot(final[2], tel)
                                   if tel else None))
