"""Pallas kernels vs pure-jnp oracles, interpret mode, shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tests._hyp import given, settings, st

from repro.core.virtual_lb import (
    neighborhood_residual,
    reference_sweep,
    reverse_slots,
)
from repro.kernels.diffusion import ops as diffusion_ops
from repro.kernels.diffusion.kernel import (
    diffusion_nsweeps_pallas,
    diffusion_sweep_pallas,
)
from repro.kernels.diffusion.ref import diffusion_nsweeps_ref
from repro.kernels.histogram.kernel import histogram_pallas
from repro.kernels.histogram.ref import histogram_ref
from repro.kernels.pic_push.kernel import pic_push_pallas
from repro.kernels.pic_push.ref import pic_push_ref
from repro.pic.grid import alternating_grid
from repro.pic.particles import initialize
from tests.conftest import random_symmetric_graph


# --------------------------------------------------------------- diffusion --


def _graph(P, K, seed):
    """Random symmetric K-regular-ish neighbor table (device arrays)."""
    nbr, mask = random_symmetric_graph(P, K, seed)
    return jnp.asarray(nbr), jnp.asarray(mask)


@pytest.mark.parametrize("P,K,block_p", [
    (16, 2, 8), (64, 4, 32), (100, 4, 64), (257, 8, 128), (512, 3, 512),
])
@pytest.mark.parametrize("single_hop", [True, False])
def test_diffusion_kernel_matches_ref(P, K, block_p, single_hop):
    nbr, mask = _graph(P, K, seed=P + K)
    rev = reverse_slots(nbr, mask)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.random(P).astype(np.float32) * 10)
    own = x * 0.7
    out_k = diffusion_sweep_pallas(x, own, nbr, mask, rev, 0.2, single_hop,
                                   block_p=block_p, interpret=True)
    out_r = reference_sweep(x, own, nbr, mask, rev, jnp.float32(0.2),
                            single_hop)
    for a, b in zip(out_k, out_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(P=st.integers(8, 80), K=st.integers(1, 6), seed=st.integers(0, 99))
def test_property_diffusion_kernel_conserves(P, K, seed):
    nbr, mask = _graph(P, K, seed)
    rev = reverse_slots(nbr, mask)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.random(P).astype(np.float32) * 5)
    xn, own, flow = diffusion_sweep_pallas(
        x, x, nbr, mask, rev, 1.0 / (K + 1), True, interpret=True)
    np.testing.assert_allclose(float(jnp.sum(xn)), float(jnp.sum(x)),
                               rtol=1e-4)
    assert (np.asarray(xn) >= -1e-4).all()


# ------------------------------------------------------- fused multi-sweep --


def _nsweeps_args(P, K, seed=0):
    nbr, mask = _graph(P, K, seed=P + K)
    rev = reverse_slots(nbr, mask)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.random(P).astype(np.float32) * 10)
    own = x * 0.7
    flow = jnp.zeros((P, K), jnp.float32)
    res0 = neighborhood_residual(x, nbr, mask)
    return x, own, flow, res0, nbr, mask, rev


@pytest.mark.parametrize("P,K,S", [
    (16, 2, 1), (16, 2, 4), (64, 4, 8), (100, 4, 3), (257, 8, 6),
])
@pytest.mark.parametrize("single_hop", [True, False])
def test_nsweeps_kernel_bit_for_bit_vs_iterated_reference(P, K, S,
                                                          single_hop):
    """The fused S-sweep kernel must equal S iterated reference sweeps
    *bit-for-bit* (interpret mode): same values, not just close ones —
    the chunked loop is a compilation strategy, not a different scheme.
    tol=-1 keeps every sweep active so all S sweeps actually run.

    The oracle is the *jitted* sweep, as every production path runs it.
    Eager op-by-op dispatch rounds each intermediate to f32, whereas a
    compiled sweep may fuse the single-hop rescale ``push * scale`` into
    the ``push - recv`` flow update as one fused multiply-subtract (one
    rounding): that moves ``flow`` by an ulp between eager and compiled
    execution of the *same* jnp code, so eager dispatch states no
    contract a compiled kernel can hold."""
    x, own, flow, res0, nbr, mask, rev = _nsweeps_args(P, K)
    alpha = 1.0 / (K + 1.0)
    got = diffusion_nsweeps_pallas(
        x, own, flow, jnp.int32(0), res0, jnp.int32(0), nbr, mask, rev,
        alpha, n_sweeps=S, single_hop=single_hop, tol=-1.0,
        max_iters=10 ** 6, interpret=True)
    sweep = jax.jit(reference_sweep, static_argnums=6)
    xs, os_, fl = x, own, flow
    for _ in range(S):
        xs, os_, df = sweep(xs, os_, nbr, mask, rev, jnp.float32(alpha),
                            single_hop)
        fl = fl + df
    for a, b in zip(got[:3], (xs, os_, fl)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(got[3]) == S


@pytest.mark.parametrize("P,K,S,tol", [
    (32, 3, 4, 0.02), (64, 4, 8, 0.1), (100, 4, 16, 0.02),
])
@pytest.mark.parametrize("single_hop", [True, False])
def test_nsweeps_kernel_early_exit_parity(P, K, S, tol, single_hop):
    """With a realistic tol the kernel's device-side early exit must make
    the same per-sweep decisions as the reference chunk: identical carry
    (x/own/flow) *and* identical iteration/stall/residual bookkeeping."""
    x, own, flow, res0, nbr, mask, rev = _nsweeps_args(P, K)
    alpha = jnp.float32(1.0 / (K + 1.0))
    args = (x, own, flow, jnp.int32(0), res0, jnp.int32(0), nbr, mask, rev,
            alpha)
    kw = dict(n_sweeps=S, single_hop=single_hop, tol=tol, max_iters=512)
    got = diffusion_nsweeps_pallas(*args, interpret=True, **kw)
    want = diffusion_nsweeps_ref(*args, **kw)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_nsweeps_kernel_resumes_mid_convergence():
    """Chunk boundaries carry (it, res, stall) through: two chained 4-sweep
    kernel calls equal one 8-sweep call."""
    P, K = 48, 3
    x, own, flow, res0, nbr, mask, rev = _nsweeps_args(P, K)
    alpha = jnp.float32(1.0 / (K + 1.0))
    kw = dict(single_hop=True, tol=0.02, max_iters=512, interpret=True)
    one = diffusion_nsweeps_pallas(
        x, own, flow, jnp.int32(0), res0, jnp.int32(0), nbr, mask, rev,
        alpha, n_sweeps=8, **kw)
    half = diffusion_nsweeps_pallas(
        x, own, flow, jnp.int32(0), res0, jnp.int32(0), nbr, mask, rev,
        alpha, n_sweeps=4, **kw)
    two = diffusion_nsweeps_pallas(
        *half, nbr, mask, rev, alpha, n_sweeps=4, **kw)
    for a, b in zip(one, two):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sweep_impl_selection_rule():
    """Every backend, TPU included, takes the XLA-compiled reference
    chunk at every size: Mosaic lowers neither Pallas diffusion kernel's
    neighbor gather, so no shape selects one."""
    for shape in ((64, 4), (4096, 8), (1024, 8), (1_000_000, 8)):
        assert diffusion_ops.sweep_impl(*shape) == "reference"


# --------------------------------------------------------------- histogram --


@pytest.mark.parametrize("N,C,block_n", [
    (100, 7, 32), (4096, 144, 2048), (5000, 333, 1024), (64, 4, 64),
])
def test_histogram_matches_ref(N, C, block_n):
    rng = np.random.default_rng(N)
    ids = jnp.asarray(rng.integers(-1, C, N), jnp.int32)   # incl. padding ids
    w = jnp.asarray(rng.random(N), jnp.float32)
    got = histogram_pallas(ids, w, C=C, block_n=block_n, interpret=True)
    want = histogram_ref(ids, w, C=C)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4)


def test_histogram_weighted_vs_counts():
    ids = jnp.asarray([0, 0, 1, 2, 2, 2], jnp.int32)
    ones = jnp.ones(6, jnp.float32)
    got = histogram_pallas(ids, ones, C=3, interpret=True)
    np.testing.assert_allclose(np.asarray(got), [2, 1, 3])


# ---------------------------------------------------------------- pic_push --


@pytest.mark.parametrize("L,N,block_n", [(32, 100, 64), (64, 1000, 256),
                                         (128, 333, 512)])
def test_pic_push_matches_ref(L, N, block_n):
    p = initialize("GEOMETRIC", L, N, k=1, seed=L)
    g = jnp.asarray(alternating_grid(L))
    args = tuple(map(jnp.asarray, (p.x, p.y, p.vx, p.vy, p.q)))
    got = pic_push_pallas(g, *args, L=L, block_n=block_n, interpret=True)
    want = pic_push_ref(g, *args, L=L)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("mode", ["GEOMETRIC", "SINUSOIDAL", "LINEAR",
                                  "PATCH"])
def test_pic_push_positions_stay_in_bounds(mode):
    L = 48
    p = initialize(mode, L, 500, k=2, seed=1)
    g = jnp.asarray(alternating_grid(L))
    x, y, vx, vy = map(jnp.asarray, (p.x, p.y, p.vx, p.vy))
    q = jnp.asarray(p.q)
    for _ in range(5):
        x, y, vx, vy = pic_push_ref(g, x, y, vx, vy, q, L=L)
    assert (np.asarray(x) >= 0).all() and (np.asarray(x) < L).all()
    assert (np.asarray(y) >= 0).all() and (np.asarray(y) < L).all()


def test_prk_determinism_displacement():
    """The PRK construction: exactly (2k+1) cells/step horizontally after
    every even step, vy cells vertically."""
    L, k = 64, 3
    p = initialize("GEOMETRIC", L, 400, k=k, seed=5)
    g = jnp.asarray(alternating_grid(L))
    s = tuple(map(jnp.asarray, (p.x, p.y, p.vx, p.vy)))
    q = jnp.asarray(p.q)
    for _ in range(4):
        out = pic_push_ref(g, *s, q, L=L)
        s = out
    dx = (np.asarray(s[0]) - p.x) % L
    dy = (np.asarray(s[1]) - p.y) % L
    np.testing.assert_allclose(dx, (4 * (2 * k + 1)) % L, atol=1e-3)
    np.testing.assert_allclose(dy, 4.0, atol=1e-3)


# --------------------------------------------------------- flash attention --


from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import flash_attention_ref


@pytest.mark.parametrize("B,Sq,T,KV,G,hd,window,prefix,dtype", [
    (2, 64, 64, 2, 3, 16, 0, 0, jnp.float32),
    (1, 128, 128, 1, 4, 32, 0, 0, jnp.float32),
    (2, 64, 64, 2, 2, 16, 24, 0, jnp.float32),
    (1, 48, 48, 2, 2, 16, 0, 16, jnp.float32),
    (2, 96, 96, 3, 1, 16, 0, 0, jnp.bfloat16),
    (1, 40, 72, 2, 2, 8, 0, 0, jnp.float32),   # Sq != T, non-multiple blocks
])
def test_flash_attention_matches_ref(B, Sq, T, KV, G, hd, window, prefix,
                                     dtype):
    rng = np.random.default_rng(Sq + T)
    q = jnp.asarray(rng.normal(size=(B, Sq, KV, G, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(B, T, KV, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(B, T, KV, hd)), dtype)
    qpos = jnp.broadcast_to(jnp.arange(Sq, dtype=jnp.int32) + (T - Sq),
                            (B, Sq))
    kpos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    got = flash_attention_pallas(q, k, v, qpos, kpos, window=window,
                                 prefix_len=prefix, q_block=32, kv_block=32,
                                 interpret=True)
    want = flash_attention_ref(q, k, v, qpos, kpos, window=window,
                               prefix_len=prefix)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_cache_sentinels():
    """Unwritten cache slots (sentinel positions) must not contribute."""
    B, Sq, T, KV, G, hd = 1, 16, 64, 1, 2, 16
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, Sq, KV, G, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, T, KV, hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, T, KV, hd)).astype(np.float32))
    qpos = jnp.broadcast_to(jnp.arange(Sq, dtype=jnp.int32), (B, Sq))
    kpos = jnp.where(jnp.arange(T) < Sq, jnp.arange(T), 2 ** 30)[None, :]
    kpos = jnp.broadcast_to(kpos.astype(jnp.int32), (B, T))
    got = flash_attention_pallas(q, k, v, qpos, kpos, q_block=16,
                                 kv_block=16, interpret=True)
    want = flash_attention_ref(q[:, :Sq], k[:, :Sq], v[:, :Sq], qpos,
                               kpos[:, :Sq])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-3)


# ---------------------------------------------------- migrate (sort-free) --


from repro.kernels.migrate import ops as migrate_ops
from repro.kernels.migrate.kernel import scatter_dest_pallas
from repro.kernels.migrate.ref import bucket_ranks_ref, scatter_dest_ref


def _stable_dest(ids, C):
    """Oracle: item → slot of the stable argsort bucketed layout."""
    ids = np.asarray(ids)
    n = ids.shape[0]
    valid = (ids >= 0) & (ids < C)
    order = np.argsort(np.where(valid, ids, C), kind="stable")
    dest = np.full(n, n, np.int64)
    for slot, i in enumerate(order[: valid.sum()]):
        dest[i] = slot
    return dest


@pytest.mark.parametrize("n,C,block_n", [
    (100, 8, 32), (257, 16, 64), (1024, 8, 256), (96, 1, 32),
    (50, 3, 64), (512, 40, 128),
])
def test_migrate_scatter_kernel_matches_ref(n, C, block_n):
    rng = np.random.default_rng(n * 31 + C)
    ids = rng.integers(0, C, size=n).astype(np.int32)
    ids[::13] = -1                       # padding slots
    ids[::29] = C                        # out-of-range sentinel slots
    dest_k, counts_k = scatter_dest_pallas(
        jnp.asarray(ids), C=C, block_n=block_n, interpret=True)
    dest_r, counts_r = scatter_dest_ref(jnp.asarray(ids), C=C)
    np.testing.assert_array_equal(np.asarray(dest_k), np.asarray(dest_r))
    np.testing.assert_array_equal(np.asarray(counts_k), np.asarray(counts_r))
    np.testing.assert_array_equal(np.asarray(dest_r), _stable_dest(ids, C))


@pytest.mark.parametrize("case", ["duplicate_heavy", "empty_node",
                                  "single_node", "empty_input"])
def test_migrate_scatter_edge_cases_both_paths(case):
    n, C = {"duplicate_heavy": (300, 4), "empty_node": (128, 16),
            "single_node": (64, 1), "empty_input": (0, 8)}[case]
    rng = np.random.default_rng(7)
    if case == "duplicate_heavy":
        ids = np.repeat(rng.integers(0, C, 3), 100).astype(np.int32)
    elif case == "empty_node":
        ids = rng.choice([0, 3, 15], size=n).astype(np.int32)  # 13 empty
    elif case == "single_node":
        ids = np.zeros(n, np.int32)
    else:
        ids = np.zeros(0, np.int32)
    want = _stable_dest(ids, C)
    for use_kernel in (False, True):
        dest, counts, offsets = migrate_ops.scatter_dest(
            jnp.asarray(ids), C=C, use_kernel=use_kernel)
        np.testing.assert_array_equal(np.asarray(dest), want)
        np.testing.assert_array_equal(
            np.asarray(counts), np.bincount(ids, minlength=C))
        assert offsets.shape == (C + 1,) and int(offsets[-1]) == n


@settings(max_examples=10, deadline=None)
@given(n=st.integers(1, 600), C=st.integers(1, 48), seed=st.integers(0, 99))
def test_property_migrate_scatter_equals_stable_argsort(n, C, seed):
    """Sort-free permutation == jnp.argsort(owner, stable=True), both
    implementations, random owner vectors (duplicates guaranteed for
    n > C)."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, C, size=n).astype(np.int32)
    want_order = np.asarray(jnp.argsort(jnp.asarray(ids), stable=True))
    for use_kernel in (False, True):
        dest, _, _ = migrate_ops.scatter_dest(
            jnp.asarray(ids), C=C, use_kernel=use_kernel)
        order = np.empty(n, np.int64)
        order[np.asarray(dest)] = np.arange(n)
        np.testing.assert_array_equal(order, want_order)


@settings(max_examples=8, deadline=None)
@given(n=st.integers(1, 400), C=st.integers(2, 32), seed=st.integers(0, 50))
def test_property_migrate_bucket_ranks(n, C, seed):
    """rank[i] counts earlier same-owner items; padding ranks are -1; both
    dispatch paths agree bit-for-bit."""
    rng = np.random.default_rng(seed + 1000)
    ids = rng.integers(-1, C, size=n).astype(np.int32)
    want = np.full(n, -1, np.int64)
    seen = {}
    for i, v in enumerate(ids):
        if 0 <= v < C:
            want[i] = seen.get(v, 0)
            seen[v] = want[i] + 1
    for use_kernel in (False, True):
        rank, counts = migrate_ops.bucket_ranks(
            jnp.asarray(ids), C=C, use_kernel=use_kernel)
        np.testing.assert_array_equal(np.asarray(rank), want)
        np.testing.assert_array_equal(
            np.asarray(counts), np.bincount(ids[ids >= 0], minlength=C))


def test_migrate_blocked_ref_matches_single_block():
    """The blocked lax.scan reference is exact int arithmetic: forcing
    many small blocks reproduces the one-shot result bit-for-bit."""
    rng = np.random.default_rng(3)
    ids = jnp.asarray(rng.integers(0, 6, size=1000), jnp.int32)
    r1, c1 = bucket_ranks_ref(ids, C=6)
    import repro.kernels.migrate.ref as mref
    orig = mref.BLOCK_ELEMS
    try:
        mref.BLOCK_ELEMS = 6 * 64      # force ~16 blocks
        bucket_ranks_ref.clear_cache()
        r2, c2 = bucket_ranks_ref(ids, C=6)
    finally:
        mref.BLOCK_ELEMS = orig
        bucket_ranks_ref.clear_cache()
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


def test_migrate_impl_selection_rule():
    """Non-TPU backends take the compiled reference; the kernel needs a
    block size within the VMEM budget and the f32-exact n bound; the
    sort-vs-scatter crossover tracks the bucket count on CPU."""
    from repro.kernels import on_tpu

    assert migrate_ops.kernel_block_n(8) is not None
    assert migrate_ops.kernel_block_n(100_000) is None
    if on_tpu():
        assert migrate_ops.scatter_impl(1 << 20, 8) == "kernel"
        assert migrate_ops.preferred_method(1 << 20, 1024) == "scatter"
    else:
        assert migrate_ops.scatter_impl(1 << 20, 8) == "reference"
        assert migrate_ops.preferred_method(1 << 20, 8) == "scatter"
        assert migrate_ops.preferred_method(
            1 << 20, migrate_ops.SORT_CROSSOVER_C + 1) == "sort"
