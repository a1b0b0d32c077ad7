"""Compile what the TPU dispatch selects on the main path, for a described
(not attached) TPU v5e chip, at the sizes ``chip_smoke.py`` runs.

Nothing runs: these tests show only that the chip's compiler accepts each
program (interpret mode hides Mosaic refusals) and that each Pallas kernel
is really in it (``tpu_custom_call``).  The topology is described inside a
module-scoped fixture, never at import, so every pytest worker collects
the same tests and only the worker that runs this file loads the TPU
compiler.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.histogram.kernel import histogram_pallas
from repro.kernels.migrate import ops as migrate_ops
from repro.kernels.migrate.kernel import scatter_dest_pallas
from repro.kernels.pic_push.kernel import pic_push_pallas

L = 1000                  # PIC PRK grid (paper Fig 4)
N = 2 ** 23               # particles in the smoke's PIC phase
CHARES = 12 * 12          # cx * cy
NUM_PES = 8
PLAN_GRID, PLAN_NODES = 256, 1024   # smoke's planner phase


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_pic_push_compiles_for_v5e(one_chip):
    f = functools.partial(pic_push_pallas, L=L)
    compiled = jax.jit(f).lower(
        _spec(one_chip, (L, L)),
        *[_spec(one_chip, (N,)) for _ in range(5)]).compile()
    _assert_kernel(compiled)


def test_histogram_compiles_for_v5e(one_chip):
    f = functools.partial(histogram_pallas, C=CHARES)
    compiled = jax.jit(f).lower(_spec(one_chip, (N,), jnp.int32),
                                _spec(one_chip, (N,))).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("C", [NUM_PES, CHARES])
def test_scatter_dest_compiles_for_v5e(one_chip, C):
    assert migrate_ops.KERNEL_MAX_N > N
    f = functools.partial(scatter_dest_pallas, C=C,
                          block_n=migrate_ops.kernel_block_n(C))
    compiled = jax.jit(f).lower(_spec(one_chip, (N,), jnp.int32)).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("K", [4, 8])
def test_plan_fn_compiles_for_v5e(one_chip, K):
    """The fused three-stage planner at the smoke's P = 1024 nodes
    (stencil-wave, 256² objects); K = 4 is diff-comm's default."""
    from repro.core.engine import LBEngine
    from repro.sim import scenarios

    problem, _ = scenarios.get("stencil-wave").instantiate(
        grid=PLAN_GRID, num_nodes=PLAN_NODES)
    shapes = jax.tree.map(
        lambda a: _spec(one_chip, a.shape, a.dtype), problem)
    eng = LBEngine(k=K)
    compiled = jax.jit(eng.plan_fn).lower(shapes).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16 * 2 ** 30
