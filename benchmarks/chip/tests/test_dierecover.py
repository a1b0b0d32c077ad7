"""The four-chip die/recover cell end to end on 4 virtual CPU devices at
a small size, through the harness: a sound run is correct, a traced run
runs through, the control is not correct, and an adapter broken underneath the timed path fails by the
check named for its fault."""
import json
import os
import subprocess
import sys

import pytest

from chipbench import harness

SCRIPT = r"""
import json
import os
import sys
import time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, sys.argv[1])
import pathlib
from chipbench import control, harness

harness.CACHE_DIR = pathlib.Path(sys.argv[2])
SEED = 3000000000123
N = 1 << 14
cell = harness.Cell.load(
    "pic-prk-fig4-4chip.dierecover",
    config_overrides={"n_particles": N, "replay_capacity": N})
cycle = cell.traffic["fault_cycle"]
per_call = cell.traffic["steps_per_call"]


def dies_in(i):
    # (shard, step) of the die events in call i's steps
    t0 = i * per_call
    out = []
    first = t0 // cycle["period"]
    for j in range(first, (t0 + per_call) // cycle["period"] + 1):
        step = j * cycle["period"] + cycle["die"]
        if t0 <= step < t0 + per_call:
            out.append((j % cycle["rotate"], step))
    return out


def drop_drained(sut):
    # the dying shard's particles vanish instead of moving
    inner = sut.call

    def call(i):
        for shard, _ in dies_in(i):
            sut.carry = sut.carry._replace(
                count=sut.carry.count.at[shard].set(0))
        return inner(i)
    sut.call = call
    return sut


def keep_on_dead(sut):
    # the adopted plan leaves chare 0 on the drained shard's first PE
    inner = sut.call

    def call(i):
        n = inner(i)
        for shard, step in dies_in(i):
            pe = 2 * shard
            sut.carry = sut.carry._replace(
                assignment=sut.carry.assignment.at[0].set(pe))
            owners = sut.ys[-1]["assignment"].copy()
            owners[step - i * per_call:, 0] = pe
            sut.ys[-1]["assignment"] = owners
        return n
    sut.call = call
    return sut


out = {}
for name, wrap in (("sound", None), ("drop_drained", drop_drained),
                   ("keep_on_dead", keep_on_dead)):
    line = harness.run_cell(cell, seed=SEED, seconds=0.01, trace=False,
                            t_start=time.perf_counter(), require_tpu=False,
                            wrap_adapter=wrap)
    out[name] = {"correct": line["correct"],
                 "metrics": sorted(line["metrics"]),
                 "failed_checks": sorted(k for k, c in line["checks"].items()
                                         if c["value"] > c["limit"])}
line = harness.run_cell(cell, seed=SEED + 7, seconds=0.01, trace=True,
                        t_start=time.perf_counter(), require_tpu=False)
out["traced"] = {"metrics": line["metrics"]}
out["control"] = []
for seed in (SEED, SEED + 1, SEED + 2):
    ok, checks = control.run_control(cell, seed)
    out["control"].append({"correct": ok, "failed_checks": sorted(
        k for k, (v, lim) in checks.items() if v > lim)})
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(harness.ROOT / "src"))
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    p = subprocess.run(
        [sys.executable, "-c", SCRIPT, str(harness.BENCH_DIR),
         str(tmp_path_factory.mktemp("jax_cache"))],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert p.returncode == 0, \
        f"STDOUT:\n{p.stdout[-3000:]}\nSTDERR:\n{p.stderr[-3000:]}"
    line = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def test_sound_small_run_is_correct(runs):
    assert runs["sound"]["correct"], runs["sound"]
    assert runs["sound"]["failed_checks"] == []
    assert {"steps_per_s", "max_avg_load", "ext_int_comm",
            "setup_s"} <= set(runs["sound"]["metrics"])


def test_traced_small_run_completes(runs):
    # a CPU trace holds no device ops, so the trace readers report
    # nothing here (tests/test_chips.py reads synthetic chip traces);
    # the traced path itself, counters included, must run through
    assert all(isinstance(m["value"], float)
               for m in runs["traced"]["metrics"].values())


@pytest.mark.parametrize("fault,check", [
    ("drop_drained", "lost_or_doubled"),
    ("keep_on_dead", "dead_owner_chares"),
])
def test_broken_adapter_fails_by_its_check(runs, fault, check):
    assert not runs[fault]["correct"]
    assert check in runs[fault]["failed_checks"], runs[fault]


def test_control_is_not_correct(runs):
    for r in runs["control"]:
        assert not r["correct"], r
