#!/usr/bin/env python3
"""Smoke test of the main path on a TPU, through the entry points users call.

    python chip_smoke.py             # one chip: phases `pic` and `plan`
    python chip_smoke.py --chips 4   # four chips: phase `sharded` only

Phase ``pic``: the paper's §VI PIC PRK replay (``repro.pic.driver.run``)
at the Fig-4 shape (GEOMETRIC, L=1000, 12×12 chares, k=2, rho=0.9,
striped, lb_every=10) on 8 PEs with 2^23 particles for 100 scanned
steps, ``diff-comm`` and then ``none`` on the same particles.  Checks:
particles conserved on every step, diff-comm's mean max/avg below
none's, one Pallas push matching the jnp oracle, and the Pallas
counting scatter reproducing the stable-argsort layout exactly.

Phase ``plan``: the planner at a multi-node PE count through
``repro.sim.simulator.run_series``: ``stencil-wave`` at 256² objects on
1024 PEs (8 nodes × 128 cores), ``diff-comm``, 100 scanned steps,
checked against the host path over its first 20 steps; every final
assignment must pass ``runtime.resilience.validate_plan``.

Phase ``sharded`` (``--chips 4``): the ``pic`` configuration through the
mesh-sharded replay (``sharded_replay=True``, 4 shards) against the
single-device scanned replay, bit for bit: final particles, per-step PE
loads and migrated bytes.

Each phase prints one JSON line (device, dispatch choices, compile and
steady seconds, peak device bytes, check results); the four-chip phase
makes a single sharded call and reports its wall time, not a steady
time.  The last line is
``{"ok": true, "device": {...}}``, printed only when every check passed.
With no TPU, or any failed check or error, the script exits non-zero
without it.  The persistent compilation cache is
``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch import compile_cache  # noqa: E402

PIC_PARTICLES = 2 ** 23
PIC_PES = 8
STEPS = 100
PLAN_GRID = 256                # 65,536 objects
PLAN_NODES = 1024              # 8 nodes x 128 cores
HOST_STEPS = 20
PUSH_TOL = 1e-4                # tests/test_kernels.py pic_push tolerance
PARITY_TOL = 1e-5              # scanned vs host replay


class CompileClock:
    """Seconds spent in XLA backend compilation (persistent-cache hits
    count only their retrieval), from JAX's own monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration_secs, **_):
        if event == self.EVENT:
            self.seconds += duration_secs


def device_info():
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def peak_bytes():
    stats = [d.memory_stats() or {} for d in jax.devices()]
    return max(s.get("peak_bytes_in_use", 0) for s in stats)


def lowered_kernel(fn, *args):
    """"pallas" when the jitted call lowers to a Mosaic custom call."""
    text = jax.jit(fn).lower(*args).as_text()
    return "pallas" if "tpu_custom_call" in text else "xla"


def report(phase, clock, compile0, steady, dispatch, checks, **extra):
    line = dict(phase=phase, device=device_info(), dispatch=dispatch,
                compile_s=clock.seconds - compile0, steady_s=steady,
                peak_bytes_in_use=peak_bytes(), checks=checks, **extra)
    print(json.dumps(line), flush=True)
    failed = [k for k, ok in checks.items() if not ok]
    if failed:
        raise SystemExit(f"chip_smoke: phase {phase} failed {failed}")


def pic_config(n_particles, steps, **kw):
    from repro.obs.telemetry import TelemetryConfig
    from repro.pic.driver import PICConfig
    from repro.sim import scenarios

    fig4 = dict(scenarios.get("pic-geometric").pic_config)
    fig4.update(num_pes=PIC_PES, n_particles=n_particles, steps=steps,
                telemetry=TelemetryConfig(level="full", ring=steps))
    fig4.update(kw)
    return PICConfig(**fig4)


def conserved(res, n_particles, steps):
    """Every step's PE loads sum to the particle count."""
    loads = res.telemetry.node_loads
    return (loads.shape[0] == steps
            and bool((loads.sum(axis=1) == n_particles).all())
            and bool(np.isfinite(res.final_x).all()))


def phase_pic(clock, n_particles=PIC_PARTICLES, steps=STEPS):
    from repro.kernels.histogram.ops import histogram
    from repro.kernels.migrate import ops as migrate_ops
    from repro.kernels.pic_push.ops import pic_push
    from repro.kernels.pic_push.ref import pic_push_ref
    from repro.pic import chares as ch
    from repro.pic import driver
    from repro.pic.grid import alternating_grid
    from repro.pic.particles import initialize

    c0 = clock.seconds
    cfg = pic_config(n_particles, steps, strategy="diff-comm")
    diff = driver.run(cfg)                      # compiles
    t0 = time.perf_counter()
    diff = driver.run(cfg)                      # steady
    steady = time.perf_counter() - t0
    none = driver.run(dataclasses.replace(cfg, strategy="none"))

    # one push and one counting scatter on the initial particles
    p = initialize(cfg.mode, cfg.L, n_particles, k=cfg.k, vy0=cfg.vy0,
                   rho=cfg.rho, seed=cfg.seed)
    g = jnp.asarray(alternating_grid(cfg.L))
    state = tuple(map(jnp.asarray, (p.x, p.y, p.vx, p.vy, p.q)))
    got = pic_push(g, *state, L=cfg.L)
    want = pic_push_ref(g, *state, L=cfg.L)
    push_ok = all(np.allclose(np.asarray(a), np.asarray(b), rtol=PUSH_TOL,
                              atol=PUSH_TOL) for a, b in zip(got, want))
    chare = ch.chare_of_device(state[0], state[1], cfg.L, cfg.cx, cfg.cy)
    owner = jnp.asarray(ch.initial_mapping(cfg.cx, cfg.cy, cfg.num_pes,
                                           cfg.mapping), jnp.int32)[chare]
    n_chares = cfg.cx * cfg.cy
    scatter_ok = {}
    for name, ids, C in (("pes", owner, cfg.num_pes),
                         ("chares", chare, n_chares)):
        dest, _, _ = migrate_ops.scatter_dest(ids, C=C)
        order = jnp.zeros_like(dest).at[dest].set(
            jnp.arange(n_particles, dtype=dest.dtype))
        scatter_ok[name] = bool(
            (order == jnp.argsort(ids, stable=True)).all())

    ones = jnp.ones((n_particles,), jnp.float32)
    dispatch = dict(
        pic_push=lowered_kernel(
            lambda *a: pic_push(g, *a, L=cfg.L), *state),
        histogram=lowered_kernel(
            lambda i, w: histogram(i, w, C=n_chares), chare, ones),
        scatter_dest=lowered_kernel(
            lambda i: migrate_ops.scatter_dest(i, C=cfg.num_pes), owner),
        scatter_impl=migrate_ops.scatter_impl(n_particles, cfg.num_pes),
        manifest_method=migrate_ops.preferred_method(n_particles,
                                                     cfg.num_pes),
        scanned=bool(diff.scanned and none.scanned))
    d_ma = diff.summary()["mean_max_avg"]
    n_ma = none.summary()["mean_max_avg"]
    checks = dict(
        conserved_diff_comm=conserved(diff, n_particles, steps),
        conserved_none=conserved(none, n_particles, steps),
        diff_comm_beats_none=d_ma < n_ma,
        pic_push_matches_ref=push_ok,
        scatter_dest_pes_is_stable_argsort=scatter_ok["pes"],
        scatter_dest_chares_is_stable_argsort=scatter_ok["chares"])
    report("pic", clock, c0, steady, dispatch, checks,
           n_particles=n_particles, steps=steps,
           mean_max_avg={"diff-comm": d_ma, "none": n_ma},
           lb_fires=int(diff.lb_steps.sum()),
           steady_loop_s=diff.wall_seconds)


def phase_plan(clock, grid=PLAN_GRID, num_nodes=PLAN_NODES, steps=STEPS,
               host_steps=HOST_STEPS):
    from repro.kernels.diffusion.ops import sweep_impl
    from repro.runtime.resilience import validate_plan
    from repro.sim import scenarios
    from repro.sim.simulator import run_series

    c0 = clock.seconds
    problem, evolve = scenarios.get("stencil-wave").instantiate(
        grid=grid, num_nodes=num_nodes)
    kw = dict(lb_every=10, strategy="diff-comm")
    scan = run_series(problem, evolve, steps=steps, scan=True, **kw)
    t0 = time.perf_counter()
    scan = run_series(problem, evolve, steps=steps, scan=True, **kw)
    steady = time.perf_counter() - t0
    host = run_series(problem, evolve, steps=host_steps, scan=False, **kw)

    h = slice(0, host_steps)
    series_ok = all(
        np.allclose(getattr(scan, f)[h], getattr(host, f), rtol=PARITY_TOL,
                    atol=PARITY_TOL)
        for f in ("max_avg", "ext_int", "migrations", "max_load"))

    def valid(r):
        return bool(validate_plan(jnp.asarray(r.final_assignment),
                                  problem.loads, num_nodes=num_nodes))

    from repro.core.engine import get_engine

    K = get_engine(variant="comm").k             # diff-comm's planner
    checks = dict(
        scan_matches_host=series_ok,
        same_fire_steps=bool((scan.lb_fired[h] == host.lb_fired).all()),
        fired=bool(scan.lb_fired.sum() > 0),
        valid_assignment_scan=valid(scan),
        valid_assignment_host=valid(host))
    report("plan", clock, c0, steady,
           dict(sweep_impl=sweep_impl(num_nodes, K), scanned=scan.scanned,
                host_scanned=host.scanned),
           checks, objects=grid * grid, nodes=num_nodes, k=K,
           steps=steps, host_steps=host_steps,
           max_avg_first_last=[float(scan.max_avg[0]),
                               float(scan.max_avg[-1])])


def phase_sharded(clock, n_particles=PIC_PARTICLES, steps=STEPS, shards=4):
    from repro.distributed import replay_shard
    from repro.pic import driver

    c0 = clock.seconds
    cfg = pic_config(n_particles, steps, strategy="diff-comm")
    mesh = replay_shard.resolve_mesh(None, shards, (n_particles, PIC_PES))
    devices = [str(d) for d in mesh.devices.flat]
    single = driver.run(cfg)
    # one sharded call only: four chips cost four times as much per
    # second, so this phase reports its first (compiling) call's wall
    # time and no steady time
    t0 = time.perf_counter()
    sharded = driver.run(dataclasses.replace(
        cfg, sharded_replay=True, replay_shards=shards))
    first_call = time.perf_counter() - t0

    fields = dict(
        final_x=lambda r: r.final_x, final_y=lambda r: r.final_y,
        pe_loads=lambda r: r.telemetry.node_loads,
        migrated_bytes=lambda r: r.migrated_bytes,
        max_avg=lambda r: r.max_avg, lb_steps=lambda r: r.lb_steps)
    checks = {"distinct_devices": len({d.id for d in mesh.devices.flat})
              == shards}
    first_diff = {}
    for name, get in fields.items():
        a, b = np.asarray(get(single)), np.asarray(get(sharded))
        same = a.shape == b.shape and bool((a == b).all())
        checks[f"{name}_bit_for_bit"] = same
        if not same and a.shape == b.shape:
            first_diff[name] = np.argwhere(a != b)[0].tolist()
    report("sharded", clock, c0, None,
           dict(sharded=True, shards=shards, scanned=single.scanned),
           checks, first_call_s=first_call, mesh_devices=devices,
           n_particles=n_particles,
           steps=steps, first_difference=first_diff,
           lb_fires=int(single.lb_steps.sum()))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded replay phase")
    args = ap.parse_args(argv)

    cache = compile_cache.enable()
    info = device_info()
    if info["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (devices: {info})")
    if info["count"] < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} TPU devices, found {info['count']}")
    print(json.dumps({"compile_cache": cache}), flush=True)
    clock = CompileClock()
    if args.chips == 4:
        phase_sharded(clock)
    else:
        phase_pic(clock)
        phase_plan(clock)
    print(json.dumps({"ok": True, "device": device_info()}), flush=True)


if __name__ == "__main__":
    main()
