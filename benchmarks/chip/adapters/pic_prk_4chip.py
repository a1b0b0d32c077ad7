"""Adapter for the PIC PRK replay sharded over a four-chip host
(``repro.distributed.replay_shard.prepare_pic``), with one chip's PEs
drained and restored on a fixed cycle.

Set-up makes the particles on the device from the seed
(``chipbench.particles``), expands the traffic's fault cycle into a
``FaultSchedule`` up to ``fault_horizon_steps`` (a step the run cannot
reach; a call that would cross it raises), stages the replay with
``prepare_pic`` (the particles go into the four shards' slabs on the
device) and warms the one compiled chunk runner with a chunk that
crosses a fire and a drain, thrown away.  A call runs one chunk of
``steps_per_call`` steps on the carried state, step indices rising from
0, and returns once the chunk's per-step outputs are on the host.

The program's ``lb.replay.*`` counters are read at the window's start
and end, so ``counters()`` gives what the window counted.
"""
from __future__ import annotations

import time

from chipbench import particles

YS = ("max_avg", "pe_max", "ext", "int", "migrated_bytes", "fired",
      "forced", "rejected", "assignment", "count")
COUNTERS = ("lb.replay.steps", "lb.replay.fires")


def fault_events(cycle, horizon):
    """``(step, shard, kind)`` of the traffic's die/recover cycle below
    ``horizon``: in cycle ``j`` shard ``j mod rotate`` dies at
    ``j*period + die`` and recovers at ``j*period + recover``."""
    period, rotate = int(cycle["period"]), int(cycle["rotate"])
    return tuple(
        (j * period + int(off), j % rotate, kind)
        for j in range(horizon // period + 1)
        for off, kind in ((cycle["die"], "die"),
                          (cycle["recover"], "recover"))
        if j * period + int(off) < horizon)


class Adapter:
    unit = "steps"

    def __init__(self, config, traffic, seed, *, chips=1):
        self.s = dict(config["system"])
        if chips != int(self.s["replay_shards"]):
            raise ValueError(f"the configuration shards over "
                             f"{self.s['replay_shards']} chips, the cell "
                             f"gives {chips}")
        self.traffic = traffic
        self.seed = seed
        self.chips = chips
        self.per_call = int(traffic["steps_per_call"])
        self.span = int(traffic["span_steps"])
        self.horizon = int(traffic["fault_horizon_steps"])
        self.ys = []
        self.calls_done = 0
        self._snaps = {}

    def setup(self):
        # first: a program without the resumable sharded entry fails here
        from repro.distributed.replay_shard import prepare_pic
        import jax
        from repro.pic import driver
        from repro.runtime import resilience

        s = self.s
        t = time.perf_counter()
        parts = particles.generate(
            self.seed, n=s["n_particles"], L=s["L"], k=s["k"],
            rho=s["rho"], vy0=s["vy0"],
            population_seed=s["population_seed"])
        jax.block_until_ready(parts)
        data_s = time.perf_counter() - t
        t = time.perf_counter()
        faults = resilience.FaultSchedule(events=fault_events(
            self.traffic["fault_cycle"], self.horizon))
        cfg = driver.PICConfig(
            L=s["L"], n_particles=s["n_particles"], steps=self.horizon,
            k=s["k"], rho=s["rho"], vy0=s["vy0"], mode=s["mode"],
            cx=s["cx"], cy=s["cy"], num_pes=s["num_pes"],
            mapping=s["mapping"], lb_every=s["lb_every"],
            strategy=self.traffic["strategy"],
            trigger=self.traffic.get("trigger"),
            bytes_per_particle=s["bytes_per_particle"],
            sharded_replay=True, replay_shards=self.chips,
            replay_capacity=s["replay_capacity"], faults=faults,
            on_overflow=s["on_overflow"])
        self.prep = prepare_pic(cfg, particles=parts)
        del parts
        self.carry = self.prep.initial_carry()
        jax.block_until_ready(self.carry)
        stage_s = time.perf_counter() - t
        t = time.perf_counter()
        # one chunk that crosses a fire and a drain, on the initial carry,
        # thrown away: compiles (or reads from the cache) and runs the
        # one program the window runs
        out = self.prep.run_chunk(self.carry, int(self.traffic["warm_t0"]),
                                  self.per_call)
        del out
        return {"data_s": data_s, "stage_s": stage_s,
                "warm_s": time.perf_counter() - t}

    def _snapshot(self):
        from repro.obs import metrics

        snap = metrics.snapshot()
        return {k: snap[k] for k in COUNTERS if k in snap}

    def prepare(self, i):
        if i == 0:
            self._snaps["start"] = self._snapshot()

    def call(self, i):
        t0 = i * self.per_call
        if t0 + self.per_call > self.horizon:
            raise RuntimeError(
                f"step {t0 + self.per_call} passes the fault schedule's "
                f"horizon {self.horizon}: raise fault_horizon_steps")
        self.carry, ys = self.prep.run_chunk(self.carry, t0, self.per_call)
        self.ys.append(ys)
        self.calls_done = i + 1
        return self.per_call

    def finish_span(self, n_calls):
        self._snaps["end"] = self._snapshot()
        i = n_calls
        while i * self.per_call < self.span:
            self.call(i)
            i += 1

    def answers(self):
        import numpy as np

        p = self.prep.particles(self.carry)
        ys = {k: np.concatenate([np.asarray(c[k]) for c in self.ys])
              for k in YS}
        return dict(p, ys=ys, steps=self.calls_done * self.per_call,
                    steps_per_call=self.per_call)

    def counters(self):
        start = self._snaps.get("start", {})
        end = self._snaps.get("end") or self._snapshot()
        return {k: end[k] - start.get(k, 0.0) for k in end}

    def hlo_texts(self):
        return [self.prep.compiled_text(self.carry, self.per_call)]

    def close(self):
        self.carry = None
        self.prep = None
