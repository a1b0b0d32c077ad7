"""The command's contract on a host without a TPU, and whole runs on the
CPU at a small size through the harness, sound and with the timed path
broken underneath: the comparison has to catch each fault."""
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from chipbench import control, harness

ROOT = harness.ROOT
CMD = [sys.executable, "benchmarks/chip/run.py", "--workload",
       "pic-prk-fig4.lb", "--seed", "3000000000123", "--seconds", "1",
       "--trace", "0"]
SMALL = {
    "pic-prk-fig4.lb": ({"n_particles": 1 << 16}, {"span_steps": 20}),
    "stencil-wave-8x128.rebalance": ({"grid": 32, "num_nodes": 16},
                                     {"cycle_requests": 6,
                                      "check_requests": 6}),
}
SEED = 3000000000123


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    return env


def test_no_tpu_exits_nonzero_without_a_result():
    p = subprocess.run(CMD, cwd=ROOT, env=_cpu_env(), capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "benchmarks" / "chip",
                    tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(CMD, cwd=tmp_path, env=_cpu_env(),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.fixture
def small_run(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "CACHE_DIR", tmp_path / "jax_cache")

    def go(cell_name, wrap=None, trace=False):
        co, to = SMALL[cell_name]
        cell = harness.Cell.load(cell_name, config_overrides=co,
                                 traffic_overrides=to)
        return harness.run_cell(cell, seed=SEED, seconds=0.01, trace=trace,
                                t_start=time.perf_counter(),
                                require_tpu=False, wrap_adapter=wrap)
    return go


def wrapping(after_call):
    """An adapter whose call is followed by ``after_call(sut, before)``,
    ``before`` being the state the call started from."""
    def wrap(sut):
        inner = sut.call

        def call(i):
            before = getattr(sut, "carry", None)
            if before is None:
                before = np.asarray(sut.pending.assignment).copy()
            n = inner(i)
            after_call(sut, before)
            return n
        sut.call = call
        return sut
    return wrap


# -- faults of the PIC replay's timed path --------------------------------

def pic_unchanged(sut, before):
    sut.carry = before                      # the step returns its state


def pic_half(sut, before):
    n = before[0].shape[0] // 2             # half the particles not pushed
    sut.carry = tuple(
        a.at[n:].set(b[n:]) if j < 4 else a
        for j, (a, b) in enumerate(zip(sut.carry, before)))


def pic_altered(sut, before):
    x = sut.carry[0]                        # one particle's x altered
    sut.carry = (x.at[7].add(1.0),) + tuple(sut.carry[1:])


# -- faults of the rebalance requests' timed path -------------------------

def st_unchanged(sut, before):
    sut.out[-1] = before                    # the old assignment returned


def st_half(sut, before):
    a = sut.out[-1].copy()                  # half the objects left out
    a[a.shape[0] // 2:] = before[a.shape[0] // 2:]
    sut.out[-1] = a


def st_altered(sut, before):
    a = sut.out[-1].copy()                  # one object's owner altered
    P = sut.s["num_nodes"]
    a[5] = (a[5] + P // 2) % P
    sut.out[-1] = a


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_sound_small_run_is_correct(small_run, cell):
    line = small_run(cell)
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert "setup_s" in line["metrics"]
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("cell,fault", [
    ("pic-prk-fig4.lb", pic_unchanged),
    ("pic-prk-fig4.lb", pic_half),
    ("pic-prk-fig4.lb", pic_altered),
    ("stencil-wave-8x128.rebalance", st_unchanged),
    ("stencil-wave-8x128.rebalance", st_half),
    ("stencil-wave-8x128.rebalance", st_altered),
], ids=lambda x: getattr(x, "__name__", x))
def test_broken_timed_path_is_not_correct(small_run, cell, fault):
    line = small_run(cell, wrap=wrapping(fault))
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_control_is_not_correct(cell):
    co, to = SMALL[cell]
    c = harness.Cell.load(cell, config_overrides=co, traffic_overrides=to)
    for seed in (SEED, SEED + 1, SEED + 2):
        ok, checks = control.run_control(c, seed)
        assert not ok, checks
