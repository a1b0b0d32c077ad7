"""The resumable sharded PIC replay (``replay_shard.prepare_pic``).

  * chunked driving (``run_chunk`` from absolute step indices, chunks of
    3 and of 10) is bit for bit the one-shot ``run_pic_sharded`` under a
    die/recover fault cycle, on a 4-virtual-device mesh;
  * the drains lose no particle: final positions equal the LB-free run;
  * the four-chip benchmark cell's plain reference
    (``benchmarks/chip/references/pic_prk_4chip.py``, loaded by path)
    judges device-made particles driven through the entry correct;
  * the sharded step's scopes are in the compiled HLO's ``op_name``s and
    the ``lb.replay.*`` counters count what the chunks did;
  * in-process (one device): the entry's argument checks, and device
    particles stage the same carry as host ones.

The 4-device cases run in one subprocess (forced host devices), shared
by the tests below through a module fixture.
"""
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed import replay_shard
from repro.pic import driver
from repro.pic.particles import initialize

ROOT = pathlib.Path(__file__).resolve().parents[1]

SCRIPT = r"""
import json
import os
import re
import sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
import numpy as np

ROOT = sys.argv[1]
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "chip"))
from chipbench import harness, particles
from repro.distributed import replay_shard
from repro.obs import metrics
from repro.pic import driver
from repro.runtime import resilience

assert len(jax.devices()) == 4, jax.devices()
out = {}
CYCLE = {"period": 15, "rotate": 4, "die": 5, "recover": 11}
T = 30
cell = harness.Cell.load(
    "pic-prk-fig4-4chip.dierecover",
    config_overrides={"n_particles": 4096, "replay_capacity": 4096},
    traffic_overrides={"fault_cycle": CYCLE, "span_steps": T})
reference = harness.load_module(harness.BENCH_DIR / "references"
                                / "pic_prk_4chip.py")
events = reference.fault_events(CYCLE, T)
s = cell.config["system"]
kw = dict(L=s["L"], n_particles=s["n_particles"], steps=T, k=s["k"],
          rho=s["rho"], vy0=s["vy0"], mode=s["mode"], cx=s["cx"],
          cy=s["cy"], num_pes=s["num_pes"], mapping=s["mapping"],
          lb_every=s["lb_every"], strategy="diff-comm", seed=5,
          sharded_replay=True, replay_shards=4,
          replay_capacity=s["replay_capacity"], on_overflow="strict")
cfg = driver.PICConfig(faults=resilience.FaultSchedule(events=events), **kw)
FIELDS = ("max_avg", "ext_bytes", "int_bytes", "migrations",
          "migrated_bytes", "lb_steps", "final_x", "final_y",
          "plan_rejected", "deferred")

one = driver.run(cfg)
out["one_shot_fires"] = int(one.lb_steps.sum())


def chunked(prep, chunk, carry=None):
    carry = prep.initial_carry() if carry is None else carry
    ys = []
    for t0 in range(0, T, chunk):
        carry, y = prep.run_chunk(carry, t0, min(chunk, T - t0))
        ys.append(y)
    return carry, ys


raw = {}
for chunk in (3, 10):
    prep = replay_shard.prepare_pic(cfg)
    before = metrics.snapshot()
    carry, ys = chunked(prep, chunk)
    after = metrics.snapshot()
    got = prep.package(carry, ys, wall_seconds=0.0, cost=driver.CostModel())
    out[f"chunk{chunk}_equal"] = [
        f for f in FIELDS
        if np.array_equal(np.asarray(getattr(one, f)),
                          np.asarray(getattr(got, f)))]
    raw[chunk] = {k: np.concatenate([y[k] for y in ys]) for k in ys[0]}
    out[f"chunk{chunk}_counters"] = {
        k: after.get(k, 0.0) - before.get(k, 0.0)
        for k in ("lb.replay.steps", "lb.replay.fires")}
    out[f"chunk{chunk}_outputs"] = {
        "fires": float(raw[chunk]["fired"].sum()),
        "forced": float(raw[chunk]["forced"].sum()),
        "rejected": float(raw[chunk]["rejected"].sum())}
out["raw_outputs_equal"] = sorted(
    k for k in raw[3] if np.array_equal(raw[3][k], raw[10][k]))
out["raw_outputs"] = sorted(raw[3])
out["events"] = len(events)

none = driver.run(driver.PICConfig(**{**kw, "strategy": "none"}))
out["zero_loss"] = bool(np.array_equal(none.final_x, one.final_x)
                        and np.array_equal(none.final_y, one.final_y))
out["drained_counts"] = raw[10]["count"][5:11, 0].tolist()

seed = 3000000000123
parts = particles.generate(seed, n=s["n_particles"], L=s["L"], k=s["k"],
                           rho=s["rho"], vy0=s["vy0"],
                           population_seed=s["population_seed"])
prep = replay_shard.prepare_pic(cfg, particles=parts)
carry, ys = chunked(prep, 10)
answers = dict(prep.particles(carry),
               ys={k: np.concatenate([y[k] for y in ys]) for k in ys[0]},
               steps=T, steps_per_call=10)
checks, quality = reference.judge(cell.config, cell.traffic, seed, answers)
out["judge"] = {k: [float(v), float(lim)] for k, (v, lim) in checks.items()}
out["quality"] = quality

out["hlo_op_names"] = sorted({
    m for m in re.findall(r'op_name="([^"]*)"',
                          prep.compiled_text(carry, 10))})
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "-c", SCRIPT, str(ROOT)], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, \
        f"STDOUT:\n{p.stdout[-3000:]}\nSTDERR:\n{p.stderr[-3000:]}"
    line = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


FIELDS = ["max_avg", "ext_bytes", "int_bytes", "migrations",
          "migrated_bytes", "lb_steps", "final_x", "final_y",
          "plan_rejected", "deferred"]


@pytest.mark.parametrize("chunk", [3, 10])
def test_chunked_equals_one_shot_under_die_recover(four_devices, chunk):
    assert four_devices[f"chunk{chunk}_equal"] == FIELDS
    assert four_devices["raw_outputs_equal"] == four_devices["raw_outputs"]
    out = four_devices[f"chunk{chunk}_outputs"]
    assert out["fires"] == four_devices["one_shot_fires"]
    # every die and recover forces its own fire; none is refused
    assert out["forced"] == four_devices["events"] == 4
    assert out["rejected"] == 0


def test_drains_lose_no_particle(four_devices):
    assert four_devices["zero_loss"]
    # shard 0 dies at step 5 and recovers at 11: it holds nothing between
    assert four_devices["drained_counts"] == [0] * 6


def test_cell_reference_judges_the_entry_correct(four_devices):
    judge = four_devices["judge"]
    bad = {k: v for k, (v, lim) in judge.items() if v > lim}
    assert not bad, judge
    for name in ("lost_or_doubled", "dead_owner_chares",
                 "drained_shard_particles", "plans_rejected",
                 "fire_schedule_errors", "forced_fire_errors"):
        assert judge[name] == [0.0, 0.0]
    assert set(four_devices["quality"]) == {"max_avg_load", "ext_int_comm"}


@pytest.mark.parametrize("scope", [
    "replay/psum", "resilience/health", "resilience/guard",
    "resilience/rehome", "replay/handoff", "replay/owners",
    "exchange/ring"])
def test_sharded_step_scopes_in_compiled_hlo(four_devices, scope):
    assert any(f"/{scope}/" in f"/{name}/"
               for name in four_devices["hlo_op_names"])


@pytest.mark.parametrize("chunk", [3, 10])
def test_replay_counters_count_the_chunks(four_devices, chunk):
    c = four_devices[f"chunk{chunk}_counters"]
    assert c["lb.replay.steps"] == 30
    assert c["lb.replay.fires"] == four_devices["one_shot_fires"]


# ------------------------------------------------- in-process, 1 device --


def _cfg(**kw):
    base = dict(L=100, n_particles=2000, steps=10, k=1, rho=0.9, cx=10,
                cy=10, num_pes=4, mapping="striped", lb_every=5,
                strategy="diff-comm", strategy_kwargs=dict(k=2), seed=0,
                sharded_replay=True, replay_shards=1)
    base.update(kw)
    return driver.PICConfig(**base)


def test_prepare_pic_validates_its_arguments():
    cfg = _cfg()
    with pytest.raises(ValueError, match="five"):
        replay_shard.prepare_pic(cfg, particles=(np.zeros(3),) * 5)
    with pytest.raises(ValueError, match="five"):
        replay_shard.prepare_pic(cfg, particles=(np.zeros(2000),) * 4)
    prep = replay_shard.prepare_pic(cfg)
    with pytest.raises(ValueError, match="chunk"):
        prep.run_chunk(prep.initial_carry(), 0, 0)
    with pytest.raises(ValueError, match="t0"):
        prep.run_chunk(prep.initial_carry(), -1, 2)


def test_device_particles_stage_the_same_carry():
    cfg = _cfg()
    p = initialize(cfg.mode, cfg.L, cfg.n_particles, k=cfg.k, vy0=cfg.vy0,
                   rho=cfg.rho, seed=cfg.seed)
    host = replay_shard.prepare_pic(cfg).initial_carry()
    dev = replay_shard.prepare_pic(cfg, particles=tuple(
        jnp.asarray(a, jnp.float32) for a in (p.x, p.y, p.vx, p.vy, p.q))
    ).initial_carry()
    for a, b in zip(jax.tree.leaves(host), jax.tree.leaves(dev)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.asarray(host.count).tolist() == [2000]
    assert np.asarray(host.perm)[:5].tolist() == [0, 1, 2, 3, 4]
