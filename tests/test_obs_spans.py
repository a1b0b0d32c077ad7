"""The program's own spans and counters, and its device scopes.

  * Every eager rebalance request (``Strategy.run``, ``eager_plan``) is
    one host span ``lb/plan`` enclosing ``lb/plan/dispatch``,
    ``lb/plan/fetch`` and ``lb/plan/stats`` in that order, all with the
    same ``request`` id, and counts on ``lb.plan.requests``,
    ``lb.plan.host_reads`` (one per blocking device-to-host read) and
    ``lb.plan.stats_ns`` (host ns in the ``PlanStats`` reads).
  * A real ``jax.profiler`` trace carries those spans with their
    metadata as event stats, nested as opened.
  * The scanned PIC step names its handoff (``replay/handoff``) and owner
    maps (``replay/owners``) outside every ``cond``; the exchange
    (``exchange/migrate``) lies only inside the fired ``cond`` branch;
    object selection names each phase's ``score`` and ``take``; the
    eager ``migrate()`` keeps its ``exchange/migrate`` ops.
"""
import contextlib
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import engine
from repro.distributed import compat
from repro.pic import chares, driver
from repro.runtime import migrate as rt_migrate
from repro.runtime import triggers
from repro.sim import scenarios

CHILDREN = ["lb/plan/dispatch", "lb/plan/fetch", "lb/plan/stats"]


@pytest.fixture(scope="module")
def problem():
    prob, _ = scenarios.get("stencil-wave").instantiate(grid=8,
                                                        num_nodes=4)
    return prob


@pytest.fixture
def recorded(monkeypatch):
    """Every span the program opens: ("enter"|"exit", name, meta)."""
    events = []

    @contextlib.contextmanager
    def record(name, **meta):
        events.append(("enter", name, meta))
        yield
        events.append(("exit", name, meta))

    monkeypatch.setattr(compat, "trace_annotation", record)
    return events


REQUESTS = {
    "strategy-run": lambda p: engine.get_strategy("diff-comm").run(p, k=2),
    "eager-plan": lambda p: engine.get_engine(k=2).plan(p),
}


@pytest.mark.parametrize("call", sorted(REQUESTS))
def test_request_span_tree(problem, recorded, call):
    REQUESTS[call](problem)
    assert [(kind, name) for kind, name, _ in recorded] == (
        [("enter", "lb/plan")]
        + [(k, c) for c in CHILDREN for k in ("enter", "exit")]
        + [("exit", "lb/plan")])
    metas = {tuple(sorted(m.items())) for _, _, m in recorded}
    assert len(metas) == 1
    meta = dict(metas.pop())
    assert meta["strategy"] == "diff-comm"
    assert meta["request"] == engine._PLAN_REQUESTS.value


def _counts():
    return engine._PLAN_REQUESTS.value, engine._PLAN_HOST_READS.value


def _stats_ns():
    return engine._PLAN_STATS_NS.value


@pytest.mark.parametrize("call,reads", [
    ("strategy-run", 6),           # assignment + five PlanStats scalars
    ("eager-plan", 6),
    ("two-level", 7),              # + the thread placement
    ("host-baseline", 0),          # a NumPy plan: nothing on the device
    ("none", 1),                   # the device assignment, no stats read
])
def test_counters_per_request(problem, call, reads):
    run = dict(REQUESTS, **{
        "two-level": lambda p: engine.get_engine(
            k=2, threads_per_node=2).plan(p),
        "host-baseline": lambda p: engine.get_strategy("greedy").run(p),
        "none": lambda p: engine.get_strategy("none").run(p),
    })[call]
    run(problem)                                   # compile outside
    req0, reads0 = _counts()
    ns0 = _stats_ns()
    plan = run(problem)
    assert _counts() == (req0 + 1, reads0 + reads)
    # only a request that reads PlanStats spends time in the stats reads
    stats_read = call not in ("host-baseline", "none")
    assert (_stats_ns() > ns0) == stats_read
    assert engine._PLAN_REQUESTS.name == "lb.plan.requests"
    assert engine._PLAN_HOST_READS.name == "lb.plan.host_reads"
    assert engine._PLAN_STATS_NS.name == "lb.plan.stats_ns"
    assert plan.info["plan_seconds"] > 0


def test_info_keys_unchanged(problem):
    plan = engine.get_strategy("diff-comm").run(problem, k=2)
    assert list(plan.info) == [
        "strategy", "plan_seconds", "k", "protocol_rounds", "mean_degree",
        "diffusion_iters", "diffusion_residual", "unrealized_flow"]
    plan = engine.get_engine(k=2).plan(problem)
    assert list(plan.info) == [
        "strategy", "k", "protocol_rounds", "mean_degree",
        "diffusion_iters", "diffusion_residual", "unrealized_flow",
        "plan_seconds"]


def test_profiler_trace_carries_the_request_spans(problem, tmp_path):
    from jax.profiler import ProfileData

    strategy = engine.get_strategy("diff-comm")
    strategy.run(problem, k=2)                     # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        strategy.run(problem, k=2)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    spans = [(e.name, e.start_ns, e.duration_ns, dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             for ln in plane.lines for e in ln.events
             if e.name.startswith("lb/")]
    by = {name: (s, d, st) for name, s, d, st in spans}
    assert sorted(by) == ["lb/plan"] + CHILDREN
    s0, d0, st0 = by["lb/plan"]
    assert st0["request"] == engine._PLAN_REQUESTS.value
    assert st0["strategy"] == "diff-comm"
    end = s0
    for child in CHILDREN:
        s, d, st = by[child]
        assert st == st0
        assert s0 <= s and s + d <= s0 + d0 and s >= end
        end = s + d


def _op_names(hlo_text):
    return set(re.findall(r'op_name="([^"]*)"', hlo_text))


@pytest.fixture(scope="module")
def chunk_paths():
    """op_name paths of a tiny scanned PIC chunk with a rebalance."""
    L, cx, cy, P, n = 100, 4, 4, 4, 256
    trig = triggers.resolve_for_strategy(None, lb_every=2,
                                         strategy="diff-comm")
    runner = driver._chunk_runner(L, cx, cy, P, 2, 1.0, 2, "diff-comm", (),
                                  48.0, None, 4, None, trig, None)
    kx, ky = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.uniform(kx, (n,)) * L
    y = jax.random.uniform(ky, (n,)) * L
    ones = jnp.ones(n)
    assignment = jnp.asarray(chares.initial_mapping(cx, cy, P, "striped"),
                             jnp.int32)
    carry = (x, y, 0 * ones, ones, ones,
             chares.chare_of_device(x, y, L, cx, cy), assignment,
             jnp.arange(n, dtype=jnp.int32), trig.init_state())
    return _op_names(runner.lower(carry, jnp.arange(4)).compile()
                     .as_text())


@pytest.mark.parametrize("scope", ["replay/handoff", "replay/owners"])
def test_chunk_names_the_step_body_outside_every_cond(chunk_paths, scope):
    named = [p for p in chunk_paths if f"/{scope}/" in p]
    assert named
    assert not any("/cond/" in p for p in named)


def test_chunk_exchange_lies_only_in_the_fired_branch(chunk_paths):
    migrate = [p for p in chunk_paths if "exchange/migrate" in p]
    assert migrate
    assert all("/cond/branch" in p for p in migrate)
    assert not [p for p in chunk_paths
                if "exchange/" in p and "/cond/branch" not in p]


def test_planner_names_stage3_score_and_take(problem):
    paths = _op_names(engine.get_engine(k=2)._jitted.lower(problem)
                      .compile().as_text())
    stage3 = [p for p in paths if "lb-plan/stage3-objects/" in p]
    assert any("/score/" in p for p in stage3)
    assert any("/take/" in p for p in stage3)
    assert any(re.search(r"/take/.*sort", p) for p in stage3)


def test_eager_migrate_keeps_its_exchange_scope():
    owner = jnp.array([0, 1, 2, 3] * 4, jnp.int32)
    fn = rt_migrate._migrate_exec(4, False, "auto")
    paths = _op_names(fn.lower(owner, owner[::-1], (jnp.arange(16.0),))
                      .compile().as_text())
    assert any("exchange/migrate/" in p for p in paths)
    (moved,), man = rt_migrate.migrate(owner, owner[::-1],
                                       (jnp.arange(16.0),), num_nodes=4)
    assert int(man.moved_count) > 0 and moved.shape == (16,)
