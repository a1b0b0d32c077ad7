"""Sharded replay vs single-device replay: the whole step loop on a mesh.

Replays every registered scenario (sim/scenarios.py) twice — through the
single-device scanned path (``sim.simulator.run_series``) and through the
mesh-sharded replay runtime (``distributed.replay_shard`` — evolve,
trigger, sharded three-stage planning and the assignment update all
inside one ``shard_map``) — plus the PIC driver end-to-end (executed
particle exchange via the in-scan ``ppermute`` ring all-to-all,
``PICConfig(sharded_replay=True)``).  The headline gate is **parity, not
speed**: on an emulated CPU mesh the sharded wall time measures virtual-
device overhead, not distributed planning time (the same caveat
fig5_scaling documents), so wall numbers are reported honestly but not
asserted.  Every scenario must reproduce the single-device trajectory
**bit-for-bit** — per-step metrics, trigger fire steps, migration
counts/loads and final assignments (PIC: final particle order too).

Results are written twice: ``artifacts/bench/replay_shard_bench.json``
(legacy location) and the stable-schema ``BENCH_replay.json`` at the
repo root (schema ``replay-bench/v3``; keys are append-only — v2 added
the ``manifest_method`` the PIC exchange resolved to (sort vs sort-free
counting scatter), v3 adds the ``resilience`` section: a fault-injected
replay (one shard of the mesh dead mid-run) gated on completion,
finiteness, full evacuation and zero particle loss, with the degraded
post-fault peak load reported relative to the healthy run; committed +
CI-uploaded).

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src:. python benchmarks/replay_shard_bench.py

(running the file directly forces the 8-virtual-device mesh itself when
XLA_FLAGS does not already pin a device count)
"""
from __future__ import annotations

import json
import os

SCHEMA = "replay-bench/v4"
REPEATS = 3
BENCH_PATH = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_replay.json")

SERIES_FIELDS = ("max_avg", "ext_int", "migrations", "lb_fired",
                 "max_load", "migrated_load", "final_assignment")
PIC_FIELDS = ("max_avg", "ext_bytes", "int_bytes", "migrations",
              "migrated_bytes", "lb_steps", "final_x", "final_y")


def _parity(ref, got, fields):
    """Per-field bit-for-bit equality (wall-derived fields excluded —
    ``plan_seconds``/``step_seconds`` embed measured wall clock, which
    differs between *any* two runs, sharded or not)."""
    import numpy as np

    return {f: bool(np.array_equal(np.asarray(getattr(ref, f)),
                                   np.asarray(getattr(got, f))))
            for f in fields}


def _bench_scenarios(out, *, steps=200, lb_every=10, k=4):
    import numpy as np

    from benchmarks.common import table, timeit_median
    from repro.distributed import replay_shard
    from repro.sim import scenarios, simulator

    out["scenarios"] = {}
    rows = []
    for name in scenarios.available():
        prob, evolve = scenarios.get(name).instantiate()
        kw = dict(steps=steps, lb_every=lb_every, strategy="diff-comm",
                  strategy_kwargs=dict(k=k))
        single, single_wall = timeit_median(
            lambda: simulator.run_series(prob, evolve, scan=True, **kw),
            repeat=REPEATS)
        mesh = replay_shard._resolve_mesh(None, None, (prob.num_nodes,))
        D = int(np.prod(mesh.devices.shape))
        sharded, sharded_wall = timeit_median(
            lambda: simulator.run_series_sharded(prob, evolve, **kw),
            repeat=REPEATS)
        par = _parity(single, sharded, SERIES_FIELDS)
        out["scenarios"][name] = dict(
            num_nodes=prob.num_nodes,
            num_shards=D,
            rebalances=float(single.lb_fired.sum()),
            migrated_load=float(single.migrated_load.sum()),
            single_wall_seconds=single_wall,
            sharded_wall_seconds=sharded_wall,
            parity=par,
            bit_for_bit=all(par.values()),
        )
        rows.append([name, prob.num_nodes, D, int(single.lb_fired.sum()),
                     f"{single_wall:.3f}", f"{sharded_wall:.3f}",
                     all(par.values())])
        assert all(par.values()), \
            f"sharded replay diverged from single-device on {name}: " \
            f"{ {f: v for f, v in par.items() if not v} }"
    print(f"\nscenario registry replay (diff-comm k={k}, {steps} steps, "
          f"median of {REPEATS})")
    print(table(["scenario", "P", "shards", "rebalances", "single s",
                 "sharded s", "bit-for-bit"], rows))


def _bench_pic(out, *, steps=60, lb_every=10):
    import numpy as np

    from benchmarks.common import table, timeit_median
    from repro.distributed import replay_shard
    from repro.pic import driver

    base = dict(L=200, n_particles=20_000, steps=steps, k=2, rho=0.9,
                cx=10, cy=10, num_pes=8, mapping="striped",
                lb_every=lb_every, strategy="diff-comm",
                strategy_kwargs=dict(k=4))
    single_cfg = driver.PICConfig(scan=True, **base)
    sharded_cfg = driver.PICConfig(sharded_replay=True, **base)
    single, single_wall = timeit_median(
        lambda: driver.run(single_cfg), repeat=REPEATS)
    mesh = replay_shard._resolve_mesh(
        None, None, (base["n_particles"], base["num_pes"]))
    D = int(np.prod(mesh.devices.shape))
    sharded, sharded_wall = timeit_median(
        lambda: driver.run(sharded_cfg), repeat=REPEATS)
    from repro.runtime import migrate as rt_migrate

    par = _parity(single, sharded, PIC_FIELDS)
    conserved = bool(sharded.final_x.shape[0] == base["n_particles"]
                     and np.isfinite(sharded.final_x).all())
    out["pic"] = dict(
        n_particles=base["n_particles"],
        num_pes=base["num_pes"],
        # v2: which manifest build the executed exchange resolved to
        manifest_method=rt_migrate.resolve_method(
            "auto", n=base["n_particles"], num_nodes=base["num_pes"]),
        num_shards=D,
        rebalances=float(single.lb_steps.sum()),
        migrated_bytes=float(single.migrated_bytes.sum()),
        particles_conserved=conserved,
        single_wall_seconds=single_wall,
        sharded_wall_seconds=sharded_wall,
        parity=par,
        bit_for_bit=all(par.values()),
    )
    print(f"\nPIC driver 20k particles, {steps} steps, {D}-shard mesh, "
          f"executed in-scan exchange")
    print(table(
        ["path", "rebalances", "migrated bytes", "wall s", "bit-for-bit"],
        [["single", int(single.lb_steps.sum()),
          f"{single.migrated_bytes.sum():.0f}", f"{single_wall:.3f}", "-"],
         ["sharded", int(sharded.lb_steps.sum()),
          f"{sharded.migrated_bytes.sum():.0f}", f"{sharded_wall:.3f}",
          all(par.values())]]))
    assert conserved, "sharded exchange must conserve particles"
    assert all(par.values()), \
        f"sharded PIC replay diverged: " \
        f"{ {f: v for f, v in par.items() if not v} }"


def _bench_resilience(out, *, steps=120, lb_every=10, k=4):
    """Fault-injected replay: kill one shard mid-run, gate the recovery.

    The healthy and degraded runs share scenario, cadence and strategy;
    the only delta is a ``FaultSchedule`` with one ``die`` event at
    ``steps // 3``.  Gates (asserted, not just reported):

      * the degraded run completes with finite metrics end to end;
      * the final assignment has **zero** objects on the dead shard's
        nodes (full evacuation), and no plan was rejected;
      * the PIC fault run conserves every particle — its final
        particle-id-order positions equal the LB-free reference run
        exactly (the push physics never depended on the assignment);
      * the post-fault peak load stays bounded: with 1 of D shards dead
        the load-per-alive-node floor rises by D/(D-1), so the degraded
        steady-state peak must stay within ``DEGRADE_BOUND`` of the
        healthy post-fault mean peak (measured ~1.3–1.6x on the 8-shard
        CPU mesh; 3.0 leaves headroom without masking an evacuation
        that dumps everything on one node, which measures ~8x).

    Skipped (reported, not failed) on a 1-device mesh — killing the only
    shard has no correct answer."""
    import numpy as np

    from benchmarks.common import table
    from repro.distributed import replay_shard
    from repro.pic import driver
    from repro.runtime import resilience as rz
    from repro.sim import scenarios, simulator

    DEGRADE_BOUND = 3.0

    prob, evolve = scenarios.get("stencil-wave").instantiate()
    mesh = replay_shard._resolve_mesh(None, None, (prob.num_nodes,))
    D = int(np.prod(mesh.devices.shape))
    if D < 2:
        out["resilience"] = dict(skipped=True, num_shards=D,
                                 reason="needs >= 2 shards to kill one")
        print("\nresilience: skipped (1-shard mesh)")
        return

    fault_step = steps // 3
    dead_shard = D // 2
    kw = dict(steps=steps, lb_every=lb_every, strategy="diff-comm",
              strategy_kwargs=dict(k=k))
    healthy = simulator.run_series_sharded(prob, evolve, **kw)
    fs = rz.FaultSchedule(events=((fault_step, dead_shard, "die"),))
    degraded = simulator.run_series_sharded(prob, evolve, faults=fs, **kw)

    rpd = prob.num_nodes // D
    dead_nodes = np.arange(dead_shard * rpd, (dead_shard + 1) * rpd)
    evacuated = not np.isin(degraded.final_assignment, dead_nodes).any()
    finite = bool(np.isfinite(degraded.max_avg).all()
                  and np.isfinite(degraded.max_load).all())
    rejected = float(degraded.plan_rejected.sum())
    post = slice(fault_step + lb_every, None)  # past the evacuation spike
    healthy_peak = float(np.mean(healthy.max_load[post]))
    degraded_peak = float(np.mean(degraded.max_load[post]))
    inflation = degraded_peak / healthy_peak

    pic = dict(L=200, n_particles=20_000, steps=60, k=2, rho=0.9,
               cx=10, cy=10, num_pes=8, mapping="striped",
               lb_every=lb_every, seed=0, sharded_replay=True)
    pic_mesh = replay_shard._resolve_mesh(
        None, None, (pic["n_particles"], pic["num_pes"]))
    pic_D = int(np.prod(pic_mesh.devices.shape))
    pic_fs = rz.FaultSchedule(events=((20, pic_D // 2, "die"),))
    pic_ref = driver.run(driver.PICConfig(strategy="none", **pic))
    pic_dead = driver.run(driver.PICConfig(
        strategy="diff-comm", strategy_kwargs=dict(k=4), faults=pic_fs,
        **pic))
    pic_conserved = bool(
        np.array_equal(pic_dead.final_x, pic_ref.final_x)
        and np.array_equal(pic_dead.final_y, pic_ref.final_y))

    out["resilience"] = dict(
        num_shards=D,
        fault_step=fault_step,
        dead_shard=dead_shard,
        evacuated=evacuated,
        finite=finite,
        plans_rejected=rejected,
        healthy_peak_load=healthy_peak,
        degraded_peak_load=degraded_peak,
        peak_inflation=inflation,
        degrade_bound=DEGRADE_BOUND,
        pic_num_shards=pic_D,
        pic_particles_conserved=pic_conserved,
        pic_deferred_final=float(np.asarray(pic_dead.deferred)[-1])
        if pic_dead.deferred is not None else 0.0,
    )
    print(f"\nresilience: shard {dead_shard}/{D} dies at step "
          f"{fault_step} of {steps}")
    print(table(
        ["gate", "value", "pass"],
        [["evacuated (0 objects on dead nodes)", str(evacuated), evacuated],
         ["finite metrics", str(finite), finite],
         ["plans rejected", f"{rejected:.0f}", rejected == 0.0],
         ["post-fault peak inflation",
          f"{inflation:.2f}x (bound {DEGRADE_BOUND}x)",
          inflation < DEGRADE_BOUND],
         ["PIC particles conserved (dead shard)", str(pic_conserved),
          pic_conserved]]))
    assert finite, "degraded replay produced non-finite metrics"
    assert evacuated, \
        f"dead shard {dead_shard} still owns objects after the run"
    assert rejected == 0.0, \
        f"{rejected:.0f} engine plans failed validate_plan on a live mesh"
    assert inflation < DEGRADE_BOUND, \
        f"post-fault peak load {inflation:.2f}x exceeds {DEGRADE_BOUND}x"
    assert pic_conserved, "PIC fault run lost or corrupted particles"


def write_bench_json(out) -> str:
    """Stable-schema perf-trajectory artifact at the repo root."""
    from benchmarks import common

    return common.write_bench_json(
        BENCH_PATH, schema=SCHEMA,
        generated_by="benchmarks/replay_shard_bench.py", repeats=REPEATS,
        **out)


def run():
    import jax

    from benchmarks.common import save_result

    out = {"devices": len(jax.devices()),
           "backend": jax.default_backend(),
           # wall numbers on a CPU mesh measure virtual-device
           # overhead, not distributed planning time — flagged so the
           # perf trajectory never reads them as a regression
           "emulated_mesh": jax.devices()[0].platform == "cpu"}
    _bench_scenarios(out)
    _bench_pic(out)
    _bench_resilience(out)

    path = save_result("replay_shard_bench", out)
    bench_path = write_bench_json(out)
    print(f"\nsaved {path}\nsaved {bench_path}")
    return out


if __name__ == "__main__":
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8").strip()
    run()
