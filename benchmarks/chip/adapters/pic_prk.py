"""Adapter for the PIC PRK replay (``repro.pic.driver``), scanned on one
chip.

Set-up builds the initial carry from the seed as ``driver._run_scanned``
does (particles, chare ids, striped assignment, trigger state), with the
particles made on the device by ``chipbench.particles``, and warms the
compiled chunk runner that ``driver.run`` drives.  A call runs one chunk
of ``steps_per_call`` steps on the carried state, step indices rising,
and returns once the chunk's per-step outputs and the assignment are on
the host.  ``driver._chunk_runner`` is a private name: the program has no
public resumable (init / advance) entry yet.
"""
from __future__ import annotations

import time

from chipbench import particles

YS = ("max_avg", "pe_max", "ext", "int", "moved_share", "migrated_bytes",
      "thread_max_avg", "fired")


class Adapter:
    unit = "steps"

    def __init__(self, config, traffic, seed, *, chips=1):
        self.s = dict(config["system"])
        self.traffic = traffic
        self.seed = seed
        self.per_call = int(traffic["steps_per_call"])
        self.span = int(traffic["span_steps"])
        self.ys = []
        self.after = []
        self.calls_done = 0
        self.window_calls = None
        self.dispatch_s = None

    def setup(self):
        import jax
        import jax.numpy as jnp
        from repro.pic import chares, driver
        from repro.runtime import triggers

        s = self.s
        t = time.perf_counter()
        x, y, vx, vy, q = particles.generate(
            self.seed, n=s["n_particles"], L=s["L"], k=s["k"],
            rho=s["rho"], vy0=s["vy0"],
            population_seed=s["population_seed"])
        jax.block_until_ready(q)
        data_s = time.perf_counter() - t
        t = time.perf_counter()
        trig = triggers.resolve_for_strategy(
            self.traffic.get("trigger"), lb_every=s["lb_every"],
            strategy=self.traffic["strategy"])
        self.runner = driver._chunk_runner(
            s["L"], s["cx"], s["cy"], s["num_pes"], s["k"], s["vy0"],
            s["lb_every"], self.traffic["strategy"], (),
            s["bytes_per_particle"], None, self.per_call, None, trig, None)
        assignment = jnp.asarray(chares.initial_mapping(
            s["cx"], s["cy"], s["num_pes"], s["mapping"]), jnp.int32)
        chare = chares.chare_of_device(x, y, s["L"], s["cx"], s["cy"])
        self.carry = (x, y, vx, vy, q, chare, assignment,
                      jnp.arange(s["n_particles"], dtype=jnp.int32),
                      trig.init_state())
        # one chunk that crosses a rebalance, on the initial carry, thrown
        # away: compiles (or reads from the cache) and runs every program
        # the window runs
        warm = self._ts(1)
        out = self.runner(self.carry, warm)
        jax.device_get((out[1], out[0][6]))
        del out
        return {"data_s": data_s, "warm_s": time.perf_counter() - t}

    def _ts(self, i):
        import jax.numpy as jnp

        s0 = i * self.per_call
        return jnp.arange(s0, s0 + self.per_call)

    def prepare(self, i):
        pass

    def call(self, i):
        import jax

        t = time.perf_counter()
        self.carry, ys = self.runner(self.carry, self._ts(i))
        self.dispatch_s = time.perf_counter() - t
        ys, a = jax.device_get((ys, self.carry[6]))
        self.ys.append(ys)
        self.after.append(a)
        self.calls_done = i + 1
        return self.per_call

    def finish_span(self, n_calls):
        self.window_calls = n_calls
        i = n_calls
        while i * self.per_call < self.span:
            self.call(i)
            i += 1

    def answers(self):
        import jax
        import numpy as np

        x, y, vx, vy, q, _, _, perm, _ = jax.device_get(self.carry)
        ys = {k: np.concatenate([np.asarray(c[j]) for c in self.ys])
              for j, k in enumerate(YS)}
        return dict(x=x, y=y, vx=vx, vy=vy, q=q, perm=perm, ys=ys,
                    assignment_after=np.stack(self.after),
                    steps=self.calls_done * self.per_call,
                    steps_per_call=self.per_call)

    def counters(self):
        n = self.window_calls or self.calls_done
        fired = sum(float(c[7].sum()) for c in self.ys[:n])
        return {"window_fires": fired}

    def hlo_texts(self):
        return [self.runner.lower(self.carry, self._ts(0)).compile()
                .as_text()]

    def close(self):
        self.carry = None
        self.runner = None
