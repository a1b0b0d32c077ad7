"""LBEngine / Strategy protocol / scenario registry / scanned replay.

The load-bearing regression here: the scan-compiled planning pipeline must
produce the *same plan* as the eager ``diffusion_lb`` path bit-for-bit on
a fixed seed — the device-resident engine is a compilation strategy, not a
different algorithm.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import api, comm_graph, engine, metrics
from repro.pic import driver
from repro.sim import scenarios, simulator, stencil, synthetic

LEGACY_NAMES = {"none", "diff-comm", "diff-coord", "greedy",
                "greedy-refine", "metis", "parmetis"}


def _fixture_problem():
    prob = stencil.stencil_2d(12, 12, 9, mapping="tiled")
    return synthetic.hotspot(prob, node=0, factor=6.0)


# ------------------------------------------------------------- registry --


def test_registry_keeps_all_legacy_strategies():
    assert LEGACY_NAMES <= set(engine.available())
    assert LEGACY_NAMES <= set(api.STRATEGIES)          # back-compat view
    for name in LEGACY_NAMES:
        s = engine.get_strategy(name)
        assert s.name == name
        assert isinstance(s.jittable, bool)
    assert engine.get_strategy("diff-comm").jittable
    assert not engine.get_strategy("greedy").jittable


def test_unknown_strategy_raises_with_listing():
    with pytest.raises(KeyError, match="diff-comm"):
        engine.get_strategy("nope")


def test_strategy_run_matches_run_strategy():
    prob = _fixture_problem()
    a1 = engine.get_strategy("diff-comm").run(prob, k=4).assignment
    a2 = api.run_strategy("diff-comm", prob, k=4).assignment
    np.testing.assert_array_equal(a1, a2)


def test_host_baseline_through_protocol():
    prob = _fixture_problem()
    plan = engine.get_strategy("greedy").run(prob)
    assert plan.assignment.shape == (144,)
    after = metrics.evaluate(prob, jnp.asarray(plan.assignment))
    before = metrics.evaluate(prob)
    assert after["max_avg_load"] <= before["max_avg_load"]


# ------------------------------------------------------ engine vs eager --


def test_scanned_plan_matches_eager_diffusion_bit_for_bit():
    prob = _fixture_problem()
    eager = api.diffusion_lb(prob, k=4, variant="comm").assignment

    plan = engine.get_strategy("diff-comm").bind(k=4)

    def scanned(p):
        def body(carry, _):
            a, stats = plan(carry)
            return carry, a
        _, ys = jax.lax.scan(body, p, None, length=3)
        return ys

    ys = np.asarray(jax.jit(scanned)(prob))
    for row in ys:                       # same input => same plan, each step
        np.testing.assert_array_equal(row, eager)


def test_engine_plan_stats_match_eager_info():
    prob = _fixture_problem()
    info = api.diffusion_lb(prob, k=4).info
    _, stats = jax.jit(engine.get_engine(k=4).plan_fn)(prob)
    assert int(stats.protocol_rounds) == info["protocol_rounds"]
    assert int(stats.diffusion_iters) == info["diffusion_iters"]
    assert float(stats.unrealized_flow) == pytest.approx(
        info["unrealized_flow"], rel=1e-6)


def test_zero_stats_dtypes_match_plan_stats():
    prob = _fixture_problem()
    _, stats = engine.get_engine(k=2).plan_fn(prob)
    zero = engine.zero_stats()
    for a, b in zip(stats, zero):
        assert jnp.asarray(a).dtype == jnp.asarray(b).dtype


# --------------------------------------------------------- batched path --


def test_stack_problems_pads_edges_and_stacks_leaves():
    probs = [p for _, p, _ in scenarios.batch_instances(4)]
    stacked = comm_graph.stack_problems(probs)
    E = max(p.num_edges for p in probs)
    assert stacked.loads.shape == (4,) + probs[0].loads.shape
    assert stacked.edges_src.shape == (4, E)
    assert stacked.num_nodes == probs[0].num_nodes
    # padding slots carry the standard (-1, -1, 0.0) convention
    for b, p in enumerate(probs):
        pad = np.asarray(stacked.edges_src[b, p.num_edges:])
        assert (pad == -1).all()
        assert (np.asarray(stacked.edges_bytes[b, p.num_edges:]) == 0).all()


def test_stack_problems_rejects_mixed_shapes():
    a = stencil.stencil_2d(8, 8, 4)
    b = stencil.stencil_2d(12, 12, 4)
    with pytest.raises(ValueError, match="common"):
        comm_graph.stack_problems([a, b])


def test_plan_batch_matches_per_problem_plans():
    probs = [synthetic.hotspot(stencil.stencil_2d(12, 12, 9), node=n,
                               factor=f)
             for n, f in [(0, 6.0), (3, 2.0), (5, 9.0)]]
    eng = engine.get_engine(k=4)
    plans = eng.plan_batch(probs)
    assert len(plans) == 3
    for p, plan in zip(probs, plans):
        single = api.diffusion_lb(p, k=4).assignment
        np.testing.assert_array_equal(plan.assignment, single)
        assert plan.info["batch_size"] == 3


def test_run_series_batch_matches_single_lane_replays():
    inst = scenarios.batch_instances(4, grid=8, num_nodes=4)
    kw = dict(steps=12, lb_every=4, strategy="diff-comm",
              strategy_kwargs=dict(k=2))
    bres = simulator.run_series_batch(inst, **kw)
    assert bres.batch == 4 and bres.steps == 12
    for (_, p, ev), lane in zip(inst, bres.series):
        single = simulator.run_series(p, ev, scan=True, **kw)
        np.testing.assert_allclose(single.max_avg, lane.max_avg, rtol=1e-4)
        np.testing.assert_allclose(single.ext_int, lane.ext_int, rtol=1e-4)
        np.testing.assert_allclose(single.migrations, lane.migrations,
                                   atol=1e-6)


def test_run_series_batch_rejects_host_strategy():
    inst = scenarios.batch_instances(2, grid=8, num_nodes=4)
    with pytest.raises(ValueError, match="jittable"):
        simulator.run_series_batch(inst, steps=4, lb_every=2,
                                   strategy="greedy")


# -------------------------------------------------------- scanned replay --


def test_run_series_scanned_matches_host_loop():
    problem, evolve = scenarios.get("stencil-wave").instantiate(
        grid=12, num_nodes=4)
    kw = dict(steps=24, lb_every=6, strategy="diff-comm",
              strategy_kwargs=dict(k=3))
    host = simulator.run_series(problem, evolve, scan=False, **kw)
    scan = simulator.run_series(problem, evolve, scan=True, **kw)
    assert scan.scanned and not host.scanned
    np.testing.assert_allclose(host.max_avg, scan.max_avg, rtol=1e-4)
    np.testing.assert_allclose(host.ext_int, scan.ext_int, rtol=1e-4)
    np.testing.assert_allclose(host.migrations, scan.migrations, atol=1e-6)


def test_run_series_host_loop_replays_the_scanned_workload():
    """At P=256 an op-by-op (unfused) evolve rounds some loads an ulp
    away from the scan's compiled evolve, which flips a planner tie at
    the first fire; the host loop must compile evolve the same way and
    replay the scanned trajectory exactly."""
    problem, evolve = scenarios.get("stencil-wave").instantiate(
        grid=128, num_nodes=256)
    kw = dict(steps=12, lb_every=10, strategy="diff-comm")
    host = simulator.run_series(problem, evolve, scan=False, **kw)
    scan = simulator.run_series(problem, evolve, scan=True, **kw)
    assert host.lb_fired.sum() == 1
    np.testing.assert_array_equal(host.final_assignment,
                                  scan.final_assignment)
    np.testing.assert_array_equal(host.migrations, scan.migrations)


def test_run_series_none_strategy_scans():
    problem, evolve = scenarios.get("stencil-wave").instantiate(
        grid=8, num_nodes=4)
    res = simulator.run_series(problem, evolve, steps=10, lb_every=3,
                               strategy="none")
    assert res.scanned
    assert (res.migrations == 0).all()


def test_run_series_host_fallback_for_numpy_baseline():
    problem, evolve = scenarios.get("stencil-wave").instantiate(
        grid=8, num_nodes=4)
    res = simulator.run_series(problem, evolve, steps=10, lb_every=3,
                               strategy="greedy-refine")
    assert not res.scanned
    assert np.isfinite(res.max_avg).all()


# ------------------------------------------------------ scenario registry --

SMALL = {
    "stencil-wave": dict(grid=8, num_nodes=4),
    "pic-geometric": dict(cx=6, cy=6, num_pes=4, n_particles=1000.0),
    "adversarial-hotspot": dict(grid=8, num_nodes=4),
    "bimodal-churn": dict(grid=8, num_nodes=4),
}


def test_scenario_registry_has_required_workloads():
    assert {"stencil-wave", "pic-geometric", "adversarial-hotspot",
            "bimodal-churn"} <= set(scenarios.available())


@pytest.mark.parametrize("name", sorted(SMALL))
def test_scenario_evolve_is_scan_safe_and_shape_stable(name):
    problem, evolve = scenarios.get(name).instantiate(**SMALL[name])
    assert getattr(evolve, "jittable", False)
    p1 = jax.jit(lambda p, t: evolve(p, t))(problem, jnp.int32(3))
    assert p1.loads.shape == problem.loads.shape
    assert p1.loads.dtype == jnp.float32
    assert np.isfinite(np.asarray(p1.loads)).all()
    res = simulator.run_series(problem, evolve, steps=12, lb_every=4,
                               strategy="diff-comm",
                               strategy_kwargs=dict(k=2))
    assert res.scanned
    assert np.isfinite(res.max_avg).all()


def test_scenario_evolve_is_deterministic_in_t():
    problem, evolve = scenarios.get("bimodal-churn").instantiate(
        **SMALL["bimodal-churn"])
    a = np.asarray(evolve(problem, 7).loads)
    b = np.asarray(evolve(problem, 7).loads)
    np.testing.assert_array_equal(a, b)


# ------------------------------------------------------------ PIC driver --


def test_pic_scanned_matches_host_loop():
    base = dict(L=100, n_particles=2000, steps=20, k=1, rho=0.9, cx=8,
                cy=8, num_pes=4, mapping="striped", lb_every=6, seed=0,
                strategy="diff-comm", strategy_kwargs=dict(k=2))
    host = driver.run(driver.PICConfig(scan=False, **base))
    scan = driver.run(driver.PICConfig(scan=True, **base))
    assert scan.scanned and not host.scanned
    np.testing.assert_allclose(host.max_avg, scan.max_avg, rtol=1e-5)
    np.testing.assert_allclose(host.ext_bytes, scan.ext_bytes, rtol=1e-5)
    np.testing.assert_allclose(host.migrations, scan.migrations, atol=1e-6)
    np.testing.assert_allclose(host.migrated_bytes, scan.migrated_bytes,
                               rtol=1e-5)
    np.testing.assert_allclose(host.final_x, scan.final_x, atol=1e-3)


def test_pic_sweep_chunk_config_is_result_invariant():
    """PICConfig.sweep_chunk reaches the planner through strategy_kwargs
    and must not change any trajectory (chunking is bit-for-bit)."""
    base = dict(L=100, n_particles=2000, steps=16, k=1, rho=0.9, cx=8,
                cy=8, num_pes=4, mapping="striped", lb_every=5, seed=0,
                strategy="diff-comm", strategy_kwargs=dict(k=2), scan=True)
    r_def = driver.run(driver.PICConfig(**base))
    r_chk = driver.run(driver.PICConfig(sweep_chunk=32, **base))
    np.testing.assert_array_equal(r_def.max_avg, r_chk.max_avg)
    np.testing.assert_array_equal(r_def.migrations, r_chk.migrations)
    np.testing.assert_array_equal(r_def.final_x, r_chk.final_x)


def test_pic_scan_chunking_invariant():
    base = dict(L=100, n_particles=2000, steps=17, k=1, rho=0.9, cx=8,
                cy=8, num_pes=4, mapping="striped", lb_every=5, seed=0,
                strategy="diff-comm", strategy_kwargs=dict(k=2), scan=True)
    r1 = driver.run(driver.PICConfig(scan_chunk=5, **base))
    r2 = driver.run(driver.PICConfig(scan_chunk=50, **base))
    np.testing.assert_allclose(r1.max_avg, r2.max_avg, rtol=1e-6)
    np.testing.assert_allclose(r1.migrations, r2.migrations, atol=1e-7)
    np.testing.assert_allclose(r1.final_x, r2.final_x, atol=1e-4)
