"""Adapter for rebalance requests on a stencil-wave deployment
(``repro.core.engine`` strategies on ``repro.sim.scenarios`` problems).

A request is what a runtime issues at a load-balancing sync point: the
application has advanced ``lb_every`` steps (``evolve``, application
time, outside the request's latency) and asks the strategy for a new
assignment from the current one.  Its latency runs from the call to
``Strategy.run`` until the assignment is on the host.  Request ``j`` of a
cycle sees the loads of step ``phase + lb_every * j`` and the answer to
request ``j - 1``; every cycle of ``cycle_requests`` starts again from
the initial mapping, so each cycle is the same fixed work.  The
traffic fixes the hotspot's phase, so every seed balances the same
loads; the seed draws the requests the reference re-plans.
"""
from __future__ import annotations

import time

import numpy as np


class Adapter:
    unit = "requests"

    def __init__(self, config, traffic, seed, *, chips=1):
        self.s = dict(config["system"])
        self.traffic = traffic
        self.cycle = int(traffic["cycle_requests"])
        self.every = int(traffic["lb_every"])
        self.phase = int(traffic["phase"])
        self.out = []
        self.rounds = []
        self.iters = []
        self.window_calls = None

    def setup(self):
        import jax
        from repro.core import engine
        from repro.sim import scenarios

        s = self.s
        t = time.perf_counter()
        self.problem, evolve = scenarios.get(s["scenario"]).instantiate(
            grid=s["grid"], num_nodes=s["num_nodes"], mapping=s["mapping"],
            period=s["period"], amp=s["amp"])
        self.a0 = np.asarray(self.problem.assignment)
        data_s = time.perf_counter() - t
        t = time.perf_counter()
        self.evolve = jax.jit(evolve)
        self.strategy = engine.get_strategy(self.traffic["strategy"])
        self.pending = self._problem(0, self.a0)
        self.strategy.run(self.pending)           # compile and warm
        return {"data_s": data_s, "warm_s": time.perf_counter() - t}

    def _problem(self, j, prev):
        import jax
        import jax.numpy as jnp

        p = self.evolve(self.problem.with_assignment(
            jnp.asarray(prev, jnp.int32)), self.phase + self.every * j)
        return jax.block_until_ready(p)

    def prepare(self, i):
        j = i % self.cycle
        self.pending = self._problem(j, self.a0 if j == 0 else self.out[-1])

    def call(self, i):
        plan = self.strategy.run(self.pending)
        self.out.append(np.asarray(plan.assignment))
        self.rounds.append(plan.info["protocol_rounds"])
        self.iters.append(plan.info["diffusion_iters"])
        return 1

    def finish_span(self, n_calls):
        self.window_calls = n_calls
        for i in range(n_calls, self.cycle):
            self.prepare(i)
            self.call(i)

    def answers(self):
        first = self.out[:self.cycle]
        repeat = sum(int(not np.array_equal(a, first[i % self.cycle]))
                     for i, a in enumerate(self.out) if i >= self.cycle)
        return dict(first_cycle=first, repeats=len(self.out) - len(first),
                    repeat_mismatch=repeat, rounds=self.rounds[:self.cycle],
                    iters=self.iters[:self.cycle])

    def counters(self):
        n = self.window_calls or len(self.out)
        return {"protocol_rounds": self.rounds[:n],
                "diffusion_iters": self.iters[:n]}

    def hlo_texts(self):
        from repro.core import engine

        eng = engine.get_engine(variant=self.strategy.variant)
        return [eng._jitted.lower(self.pending).compile().as_text(),
                self.evolve.lower(self.problem, 0).compile().as_text()]

    def close(self):
        self.pending = None
