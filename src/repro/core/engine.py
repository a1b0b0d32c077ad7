"""Device-resident LB engine: the three planning stages fused into one
shape-stable, jit/scannable ``plan`` function, plus the Strategy protocol.

The paper's balancer (§III) is three stages — neighbor selection, virtual
diffusion, object selection.  ``core/api.py``'s eager path composes them
through host Python with NumPy round-trips per call; that is fine for one
snapshot but dominates wall time when a time-evolving workload is replayed
(Fig 4/5) and makes the planner unusable inside ``jax.lax.scan``.

``LBEngine`` closes over the static configuration ``(variant, K, tol,
iteration caps)`` and exposes

  * ``plan_fn(problem) -> (assignment, PlanStats)`` — pure, traceable,
    shape-stable in the static ``(P, K, C)`` envelope (``P`` nodes, ``K``
    neighbor slots, ``C`` objects; all baked into array shapes), safe to
    call under ``jit`` / ``lax.scan`` / ``lax.cond``;
  * ``plan(problem) -> LBPlan`` — eager host convenience with timing and
    the legacy ``info`` dict;
  * ``plan_batch_fn`` / ``plan_batch`` — the vmapped batch path: B
    independent same-shaped problems (stacked via
    ``comm_graph.stack_problems``) planned in one compiled call, with the
    staged problem buffers donated to the executable on accelerators.

Stage 2 runs the chunked virtual-LB loop (``sweep_chunk`` sweeps per
``while_loop`` body) through ``kernels.diffusion.ops.diffusion_nsweeps``,
which selects the implementation (the XLA-compiled reference chunk on
every backend; see that module for why).

``Strategy`` is the registry protocol replacing the dict-of-lambdas in
``core/api.py`` (a thin mapping view remains there for back-compat):
jittable strategies expose a traceable ``plan_fn(problem, **params)``;
host-only baselines (greedy, metis, ...) keep ``jittable=False`` and are
run eagerly by ``Strategy.run``.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Dict, Mapping, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import baselines, comm_graph, hierarchical
from repro.core import neighbor_selection as ns
from repro.core import object_selection as osel
from repro.core import virtual_lb as vlb
from repro.distributed import compat
from repro.kernels.diffusion import ops as diffusion_ops
from repro.obs import metrics as obs_metrics

# host counters of the eager rebalance request (Strategy.run, eager_plan),
# resolved once so the registry's lock stays off the request path
_PLAN_REQUESTS = obs_metrics.counter("lb.plan.requests")
_PLAN_HOST_READS = obs_metrics.counter("lb.plan.host_reads")
_PLAN_STATS_NS = obs_metrics.counter("lb.plan.stats_ns")


class PlanStats(NamedTuple):
    """Planner statistics as device scalars (scan/cond friendly)."""

    protocol_rounds: jax.Array     # i32 — stage-1 handshake rounds
    mean_degree: jax.Array         # f32 — mean confirmed neighbor count
    diffusion_iters: jax.Array     # i32 — stage-2 sweeps executed
    diffusion_residual: jax.Array  # f32 — final neighborhood imbalance
    unrealized_flow: jax.Array     # f32 — |wanted - shipped| load (stage 3)


def zero_stats() -> PlanStats:
    """Neutral PlanStats — the no-LB branch of a ``lax.cond``."""
    return PlanStats(
        protocol_rounds=jnp.int32(0),
        mean_degree=jnp.float32(0.0),
        diffusion_iters=jnp.int32(0),
        diffusion_residual=jnp.float32(0.0),
        unrealized_flow=jnp.float32(0.0),
    )


class LBEngine:
    """Fused three-stage diffusion planner with static configuration.

    Construction is cheap; the first ``plan`` call per problem shape pays
    XLA compilation.  Instances are cached by :func:`get_engine`.
    """

    def __init__(
        self,
        *,
        variant: str = "comm",          # "comm" (§III) | "coord" (§IV)
        k: int = 4,
        tol: float = 0.02,
        max_iters: int = 512,
        max_rounds: int = 64,
        single_hop: bool = True,
        step_fn: Optional[Callable] = None,
        sweep_chunk: int = 8,
        threads_per_node: Optional[int] = None,
    ):
        if variant not in ("comm", "coord"):
            raise ValueError(f"unknown variant {variant!r}")
        self.variant = variant
        self.k = int(k)
        self.tol = float(tol)
        self.max_iters = int(max_iters)
        self.max_rounds = int(max_rounds)
        self.single_hop = bool(single_hop)
        self.step_fn = step_fn
        self.sweep_chunk = int(sweep_chunk)
        # optional stage 4 (paper §III.D): within-node LPT across T threads
        self.threads_per_node = (None if threads_per_node is None
                                 else int(threads_per_node))
        # production stage-2 path: the S-sweep chunk dispatched by
        # kernels/diffusion/ops.py; an explicit step_fn opts out and runs
        # per-sweep inside the chunk.
        self.chunk_fn = (diffusion_ops.diffusion_nsweeps
                         if step_fn is None else None)
        self._jitted = jax.jit(self.plan_fn)
        self._jitted_batch = jax.jit(self.plan_batch_fn)
        self._jitted_hier = (jax.jit(self.plan_hier_fn)
                             if self.threads_per_node else None)
        # donating variant: only for batches plan_batch stages itself — a
        # caller-owned pre-stacked batch must survive the call.  CPU XLA
        # has no donation.
        self._jitted_batch_donate = jax.jit(
            self.plan_batch_fn,
            donate_argnums=(0,) if jax.default_backend() != "cpu" else (),
        )

    # ------------------------------------------------------- traced path --

    def plan_fn(
        self, problem: comm_graph.LBProblem
    ) -> Tuple[jax.Array, PlanStats]:
        """Neighbor selection → virtual balance → object selection, fused.

        Pure function of the problem arrays; every intermediate keeps the
        static (P, K) / (C,) padding, so the same trace serves every step
        of a scanned replay."""
        return self._plan_stages(problem, None)

    def plan_health_fn(
        self, problem: comm_graph.LBProblem, alive, speed=None
    ) -> Tuple[jax.Array, PlanStats]:
        """Health-masked :meth:`plan_fn` for a degraded mesh.

        ``alive`` is a (P,) bool node mask, ``speed`` an optional (P,)
        f32 per-node speed in (0, 1].  Dead nodes' objects are first
        re-homed onto their strongest alive communication partner
        (``runtime.resilience.rehome_dead``), slowed nodes' loads are
        scaled by the reciprocal speed, and the stage-1 preference
        rows/columns of dead nodes are zeroed — so the same three
        stages re-diffuse the displaced load over the surviving mesh
        and never target a dead node.  ``alive=None`` is exactly
        :meth:`plan_fn`.  Traceable like :meth:`plan_fn`; the resilient
        replay loops call it inside their scans."""
        if alive is None:
            return self._plan_stages(problem, None)
        from repro.runtime import resilience  # local: runtime imports core

        problem = resilience.degrade_problem(problem, alive, speed)
        return self._plan_stages(problem, jnp.asarray(alive, bool))

    def _plan_stages(
        self, problem: comm_graph.LBProblem, alive
    ) -> Tuple[jax.Array, PlanStats]:
        """Shared three-stage body; ``alive=None`` keeps the exact
        unmasked trace (the ``if`` is static, nothing is added).

        Each stage runs under a ``compat.named_scope`` so profiler
        traces and HLO dumps attribute planner time per stage."""
        from repro.distributed import compat

        # -- stage 1: neighbor selection --------------------------------
        with compat.named_scope("lb-plan/stage1-neighbors"):
            if self.variant == "comm":
                node_comm = comm_graph.node_comm_matrix(problem)
                pref = ns.comm_preference(node_comm)
            else:
                assert problem.coords is not None, \
                    "coordinate variant needs coords"
                cent = osel.centroids(
                    problem.coords, problem.assignment, problem.num_nodes
                )
                pref = ns.coordinate_preference(cent)
            if alive is not None:
                # zeroed rows/columns drop dead nodes from the candidate
                # set (select_neighbors candidates are ``preference > 0``)
                pref = jnp.where(alive[:, None] & alive[None, :], pref,
                                 0.0)
            nres = ns.select_neighbors(pref, k=self.k,
                                       max_rounds=self.max_rounds)

        # -- stage 2: virtual load balancing ----------------------------
        with compat.named_scope("lb-plan/stage2-diffusion"):
            nloads = comm_graph.node_loads(problem)
            vres = vlb.virtual_balance(
                nloads, nres.nbr_idx, nres.nbr_mask,
                tol=self.tol, max_iters=self.max_iters,
                single_hop=self.single_hop, step_fn=self.step_fn,
                sweep_chunk=self.sweep_chunk, chunk_fn=self.chunk_fn,
            )

        # -- stage 3: object selection ----------------------------------
        with compat.named_scope("lb-plan/stage3-objects"):
            sres = osel.select_objects(
                problem, nres.nbr_idx, nres.nbr_mask, vres.flows,
                metric="comm" if self.variant == "comm" else "coord",
            )

        stats = PlanStats(
            protocol_rounds=nres.rounds.astype(jnp.int32),
            mean_degree=jnp.mean(nres.degree.astype(jnp.float32)),
            diffusion_iters=vres.iters.astype(jnp.int32),
            diffusion_residual=vres.residual.astype(jnp.float32),
            unrealized_flow=jnp.abs(sres.residual).sum().astype(jnp.float32),
        )
        return sres.assignment.astype(jnp.int32), stats

    # ------------------------------------------------- hierarchical stage --

    def plan_hier_fn(
        self, problem: comm_graph.LBProblem
    ) -> Tuple[jax.Array, jax.Array, PlanStats]:
        """Two-level placement: :meth:`plan_fn` + within-node LPT (§III.D).

        Returns ``(assignment (N,), thread (N,), stats)`` where
        ``thread[o] ∈ [0, threads_per_node)`` and the global PE id is
        ``assignment * T + thread``.  Traceable like :meth:`plan_fn`
        (the LPT is a vectorized device loop — ``hierarchical.lpt_threads``),
        so the scanned replay layers can emit thread placements without
        leaving device.  Requires ``threads_per_node`` to be configured.
        """
        if not self.threads_per_node:
            raise ValueError(
                "plan_hier_fn needs threads_per_node set on the engine "
                "(get_engine(..., threads_per_node=T))")
        assignment, stats = self.plan_fn(problem)
        thread = hierarchical.lpt_threads(
            problem.loads, assignment,
            num_nodes=problem.num_nodes,
            threads_per_node=self.threads_per_node)
        return assignment, thread, stats

    def plan_hier_batch_fn(
        self, problems: comm_graph.LBProblem
    ) -> Tuple[jax.Array, jax.Array, PlanStats]:
        """Vmapped :meth:`plan_hier_fn` over a stacked problem batch."""
        return jax.vmap(self.plan_hier_fn)(problems)

    # ------------------------------------------------------ batched path --

    def plan_batch_fn(
        self, problems: comm_graph.LBProblem
    ) -> Tuple[jax.Array, PlanStats]:
        """Vmapped :meth:`plan_fn` over a stacked problem batch.

        ``problems`` is a batched ``LBProblem`` (every array leaf carries a
        leading B axis — see ``comm_graph.stack_problems``).  Returns
        ``(assignments (B, N), PlanStats of (B,) arrays)``.  One compiled
        call plans all B independent problems; traceable, so the batched
        replay layers scan over it."""
        return jax.vmap(self.plan_fn)(problems)

    def plan_batch(self, problems):
        """Eager batched planning: B problems in one compiled call.

        Accepts a sequence of same-shaped ``LBProblem``s (stacked here,
        with the staged buffers donated to the executable on accelerators)
        or an already-stacked batch (kept intact — no donation).  Returns
        a list of ``LBPlan``s."""
        from repro.core.api import LBPlan  # local import: api imports us

        t0 = time.perf_counter()
        if isinstance(problems, comm_graph.LBProblem):
            jitted = self._jitted_batch
        else:
            problems = comm_graph.stack_problems(problems)
            jitted = self._jitted_batch_donate
        assignments, stats = jitted(problems)
        assignments = np.asarray(jax.device_get(assignments))
        stats = jax.device_get(stats)
        dt = time.perf_counter() - t0
        plans = []
        for b in range(assignments.shape[0]):
            info = dict(
                strategy=f"diff-{self.variant}",
                k=self.k,
                batch_index=b,
                batch_size=assignments.shape[0],
                protocol_rounds=int(stats.protocol_rounds[b]),
                mean_degree=float(stats.mean_degree[b]),
                diffusion_iters=int(stats.diffusion_iters[b]),
                diffusion_residual=float(stats.diffusion_residual[b]),
                unrealized_flow=float(stats.unrealized_flow[b]),
                plan_seconds=dt,      # wall time of the whole batch
            )
            plans.append(LBPlan(assignments[b], info))
        return plans

    # -------------------------------------------------------- host path --

    def plan(self, problem: comm_graph.LBProblem):
        """Eager plan with wall-clock timing and the legacy info dict.

        With ``threads_per_node`` configured, the returned ``info`` also
        carries the two-level placement: ``thread`` ((N,) i32) and
        ``threads_per_node`` (the global PE id of object ``o`` is
        ``assignment[o] * T + thread[o]``)."""
        return eager_plan(self, problem, f"diff-{self.variant}")


def _request_meta(strategy_name: str) -> Dict:
    """Count one eager rebalance request; the metadata of its spans."""
    return dict(request=int(_PLAN_REQUESTS.inc()), strategy=strategy_name)


def _host_tail(meta: Dict, assignment, stats: Optional[PlanStats] = None,
               thread=None):
    """The host end of an eager rebalance request: fetch the assignment
    (and ``thread``) under the span ``lb/plan/fetch``, then read the
    ``PlanStats`` scalars under ``lb/plan/stats`` (``stats=None`` reads
    none), their host ns counted on ``lb.plan.stats_ns``.  Every blocking
    device-to-host read counts on ``lb.plan.host_reads``.  Returns
    ``(assignment, thread, fetched_at, stat_info)``: ``fetched_at`` is
    the ``perf_counter`` time the fetch ended, ``stat_info`` the
    statistics of the legacy info dict."""
    reads = 0
    with compat.trace_annotation("lb/plan/fetch", **meta):
        if thread is not None:
            reads += isinstance(thread, jax.Array)
            thread = np.asarray(jax.device_get(thread))
        reads += isinstance(assignment, jax.Array)
        assignment = np.asarray(jax.device_get(assignment))
    fetched_at = time.perf_counter()
    stat_info = {}
    if stats is not None:
        with compat.trace_annotation("lb/plan/stats", **meta):
            t = time.perf_counter_ns()
            reads += sum(isinstance(v, jax.Array) for v in stats)
            stat_info = dict(
                protocol_rounds=int(stats.protocol_rounds),
                mean_degree=float(stats.mean_degree),
                diffusion_iters=int(stats.diffusion_iters),
                diffusion_residual=float(stats.diffusion_residual),
                unrealized_flow=float(stats.unrealized_flow),
            )
            _PLAN_STATS_NS.inc(time.perf_counter_ns() - t)
    _PLAN_HOST_READS.inc(reads)
    return assignment, thread, fetched_at, stat_info


def eager_plan(eng, problem, strategy_name: str,
               extra_info: Optional[Dict] = None):
    """Shared eager planning body (``LBEngine`` and the mesh-sharded
    ``distributed.lb_shard.ShardedLBEngine``): jitted dispatch — the
    two-level variant when ``threads_per_node`` is configured — one
    device transfer, wall-clock timing, and the legacy info dict.  The
    request is traced as the spans ``lb/plan`` > ``dispatch``, ``fetch``,
    ``stats`` (see :meth:`Strategy.run`)."""
    from repro.core.api import LBPlan  # local import: api imports us

    meta = _request_meta(strategy_name)
    with compat.trace_annotation("lb/plan", **meta):
        t0 = time.perf_counter()
        thread = None
        with compat.trace_annotation("lb/plan/dispatch", **meta):
            if eng.threads_per_node:
                assignment, thread, stats = eng._jitted_hier(problem)
            else:
                assignment, stats = eng._jitted(problem)
        assignment, thread, _, stat_info = _host_tail(
            meta, assignment, stats, thread)
        info = dict(
            strategy=strategy_name,
            k=eng.k,
            **(extra_info or {}),
            **stat_info,
            plan_seconds=time.perf_counter() - t0,
        )
    if thread is not None:
        info.update(thread=thread, threads_per_node=eng.threads_per_node)
    return LBPlan(assignment, info)


_ENGINE_CACHE: Dict[tuple, LBEngine] = {}
_ENGINE_CACHE_MAX = 64


def _engine_key(cfg: Dict) -> tuple:
    """Canonical hashable cache key: values coerced exactly as
    ``LBEngine.__init__`` coerces them, so positional vs keyword spelling
    and int/float spelling of the same configuration share one entry.  An
    unhashable ``step_fn`` is keyed by identity (the cached engine holds a
    strong reference, so the id stays valid for the entry's lifetime)."""
    step_fn = cfg["step_fn"]
    try:
        hash(step_fn)
    except TypeError:
        step_fn = ("step_fn_id", id(step_fn))
    return (
        str(cfg["variant"]), int(cfg["k"]), float(cfg["tol"]),
        int(cfg["max_iters"]), int(cfg["max_rounds"]),
        bool(cfg["single_hop"]), step_fn, int(cfg["sweep_chunk"]),
        None if cfg["threads_per_node"] is None
        else int(cfg["threads_per_node"]),
    )


def get_engine(
    variant: str = "comm",
    k: int = 4,
    tol: float = 0.02,
    max_iters: int = 512,
    max_rounds: int = 64,
    single_hop: bool = True,
    step_fn: Optional[Callable] = None,
    sweep_chunk: int = 8,
    threads_per_node: Optional[int] = None,
) -> LBEngine:
    """Engine cache — one compiled planner per static configuration.

    Python's argument binding canonicalizes positional vs keyword
    spelling, and ``_engine_key`` canonicalizes the values, so — unlike
    the previous ``lru_cache`` — equivalent configurations share one
    entry regardless of call spelling, and an unhashable ``step_fn``
    does not raise."""
    cfg = dict(variant=variant, k=k, tol=tol, max_iters=max_iters,
               max_rounds=max_rounds, single_hop=single_hop,
               step_fn=step_fn, sweep_chunk=sweep_chunk,
               threads_per_node=threads_per_node)
    key = _engine_key(cfg)
    eng = _ENGINE_CACHE.get(key)
    if eng is None:
        eng = _ENGINE_CACHE[key] = LBEngine(**cfg)
        while len(_ENGINE_CACHE) > _ENGINE_CACHE_MAX:  # drop oldest entry
            _ENGINE_CACHE.pop(next(iter(_ENGINE_CACHE)))
    return eng


# ------------------------------------------------------ Strategy protocol --


@dataclasses.dataclass(frozen=True)
class Strategy:
    """A registered load-balancing strategy.

    ``plan_fn(problem, **params) -> (assignment, PlanStats)``.  When
    ``jittable`` the call is traceable for static ``params`` (usable under
    ``jit`` / ``scan`` / ``cond``); otherwise it runs host-side NumPy and
    may only be called eagerly.  ``defaults`` are merged under caller
    params by :meth:`run` and by the scanned replay layers.

    ``trigger`` names the strategy's default online rebalancing policy
    (``runtime.triggers`` — e.g. the ``diff-comm+threshold`` registration
    carries ``trigger="threshold"``).  The replay layers resolve it when
    the caller passes ``trigger=None``; a plain strategy (``trigger is
    None``) keeps the legacy fixed ``lb_every`` cadence.

    ``variant`` names the diffusion-planner variant (``"comm"`` /
    ``"coord"``) behind a diff-* strategy.  The sharded replay runtime
    (``distributed/replay_shard.py``) reads it to instantiate the
    mesh-sharded twin of the same planner configuration; ``None`` marks
    strategies with no diffusion engine behind them (baselines,
    ``"none"``), which the sharded replay cannot distribute.
    """

    name: str
    plan_fn: Callable[..., Tuple[jax.Array, PlanStats]]
    jittable: bool = False
    defaults: Mapping = dataclasses.field(default_factory=dict)
    trigger: Optional[str] = None
    variant: Optional[str] = None

    def params(self, **overrides) -> Dict:
        return {**self.defaults, **overrides}

    def bind(self, **overrides) -> Callable:
        """Traceable closure ``problem -> (assignment, PlanStats)``."""
        p = self.params(**overrides)
        return lambda problem: self.plan_fn(problem, **p)

    def run(self, problem: comm_graph.LBProblem, **overrides):
        """Eager execution returning the legacy ``LBPlan``.

        One rebalance request: counted on ``lb.plan.requests`` and traced
        as the host span ``lb/plan`` enclosing ``lb/plan/dispatch`` (the
        ``plan_fn`` call until it returns), ``lb/plan/fetch`` (the
        assignment's transfer) and ``lb/plan/stats`` (the ``PlanStats``
        reads), all with the metadata ``request`` and ``strategy``."""
        from repro.core.api import LBPlan  # local import: api imports us

        meta = _request_meta(self.name)
        with compat.trace_annotation("lb/plan", **meta):
            t0 = time.perf_counter()
            params = self.params(**overrides)
            with compat.trace_annotation("lb/plan/dispatch", **meta):
                assignment, stats = self.plan_fn(problem, **params)
            diff = self.name.startswith("diff")  # incl. the sharded ones
            assignment, _, fetched_at, stat_info = _host_tail(
                meta, assignment, stats if diff else None)
            info = dict(strategy=self.name,
                        plan_seconds=fetched_at - t0,
                        **{k: v for k, v in params.items()
                           if isinstance(v, (int, float, bool, str))})
            info.update(stat_info)
        return LBPlan(assignment, info)


_REGISTRY: Dict[str, Strategy] = {}


def register(strategy: Strategy) -> Strategy:
    _REGISTRY[strategy.name] = strategy
    return strategy


def get_strategy(name: str) -> Strategy:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown strategy {name!r}; available: {sorted(_REGISTRY)}"
        ) from None


def available() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def registry() -> Mapping[str, Strategy]:
    return dict(_REGISTRY)


# ------------------------------------------------------ built-in strategies --


def _diffusion_plan_fn(variant: str):
    def plan_fn(problem, **params):
        # the jitted entry point: eager callers (Strategy.run / STRATEGIES)
        # get the cached compiled plan; traced callers (scan/cond) inline it
        return get_engine(variant=variant, **params)._jitted(problem)
    return plan_fn


def _none_plan_fn(problem):
    return problem.assignment.astype(jnp.int32), zero_stats()


def _host(fn):
    """Wrap a NumPy baseline as a Strategy plan_fn."""
    def plan_fn(problem, **params):
        return np.asarray(fn(problem, **params), np.int32), zero_stats()
    return plan_fn


register(Strategy("none", _none_plan_fn, jittable=True))
register(Strategy("diff-comm", _diffusion_plan_fn("comm"), jittable=True,
                  variant="comm"))
register(Strategy("diff-coord", _diffusion_plan_fn("coord"), jittable=True,
                  variant="coord"))
register(Strategy("greedy", _host(baselines.greedy)))
register(Strategy("ep-greedy", _host(baselines.greedy_capped),
                  defaults=dict(cap=0)))
register(Strategy("greedy-refine", _host(baselines.greedy_refine)))
register(Strategy("metis", _host(baselines.metis_like)))
register(Strategy("parmetis", _host(baselines.parmetis_like)))

# trigger-wrapped variants: same planner, adaptive rebalance policy — the
# replay layers pick the trigger up when called with ``trigger=None``
# (single snapshots via ``compare``/``run_strategy`` plan identically to
# the base strategy; the wrapping only matters over time)
for _variant in ("comm", "coord"):
    for _trig in ("threshold", "predictive"):
        register(Strategy(f"diff-{_variant}+{_trig}",
                          _diffusion_plan_fn(_variant), jittable=True,
                          trigger=_trig, variant=_variant))
del _variant, _trig
