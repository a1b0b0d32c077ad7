"""Jitted wrappers selecting the counting-scatter implementation.

:func:`scatter_dest` / :func:`bucket_ranks` are the sort-free primitives
behind ``runtime.migrate``'s manifest build, the PIC re-bucketing paths
and ``ring_exchange``'s per-shard placement.  All implementations honor
the same bit-for-bit contract: the destinations reproduce the
stable-argsort bucketed layout exactly (ties keep previous position).

Implementation selection (:func:`scatter_impl`):

  * ``"kernel"``    — TPU, (block, C) working set within
                      :data:`MIGRATE_VMEM_BUDGET` and ``n`` below the
                      f32-exact bound 2^24: the fused two-phase Pallas
                      kernel (histogram → exclusive-scan → scatter on the
                      MXU, see kernel.py).
  * ``"reference"`` — CPU/GPU, or TPU fallbacks: the blocked-scan jnp
                      reference (XLA-compiled; Pallas interpret mode is
                      Python-slow and numerically identical, so it is
                      reserved for the kernel tests).

Whether the *sort-free* pipeline beats a stable argsort at all is a
separate question answered by :func:`preferred_method` — both paths are
O(n·C) in total work, so the counting scatter wins while C is small:
~3× at the replay loops' C = 8, n = 2^20 on CPU XLA, crossing over to
the sort around C ≈ 64 (measured on the bench host; see
benchmarks/kernel_bench.py → BENCH_kernels.json).  The TPU kernel keeps
winning to much larger C because the one-hot work rides the MXU while
the sort network does not.
"""
from __future__ import annotations

from repro.distributed import compat
from repro.kernels import on_tpu
from repro.kernels.migrate.kernel import scatter_dest_pallas
from repro.kernels.migrate.ref import bucket_ranks_ref, scatter_dest_ref

import jax.numpy as jnp

# VMEM working-set budget for the fused kernel (bytes): half of the
# ~16 MB per core, leaving the rest for pipelining and the compiler.
MIGRATE_VMEM_BUDGET = 8 * 1024 * 1024

# f32-exact slot arithmetic on the MXU bounds n (destinations are
# integers carried as f32).
KERNEL_MAX_N = 1 << 24

# CPU crossover: the O(n·C) counting scatter beats XLA's stable sort up
# to about this many buckets (measured at n = 2^20 on the bench host).
SORT_CROSSOVER_C = 64


def kernel_vmem_bytes(block_n: int, C: int) -> int:
    """Fused-kernel VMEM working set for a (block_n, C) phase-1 tile.

    Dominant terms: the (bn, bn) strict-lower-tri rank matrix and two
    (bn, C) one-hot/prefix tiles, all f32; the (C, C) exclusive-scan tri
    lives only at the phase boundary but peaks the same buffer; plus the
    i32 id/dest blocks and three (C,) vectors.
    """
    return 4 * (block_n * block_n + 2 * block_n * C
                + max(C * C, block_n * C) + 2 * block_n + 3 * C)


def kernel_block_n(C: int):
    """Largest supported block size fitting the VMEM budget, else None."""
    for bn in (1024, 512, 256, 128):
        if kernel_vmem_bytes(bn, C) <= MIGRATE_VMEM_BUDGET:
            return bn
    return None


def scatter_impl(n: int, C: int) -> str:
    """Which implementation :func:`scatter_dest` selects for (n, C)."""
    if on_tpu() and n < KERNEL_MAX_N and kernel_block_n(C) is not None:
        return "kernel"
    return "reference"


def preferred_method(n: int, C: int) -> str:
    """``"scatter"`` or ``"sort"`` — what ``method="auto"`` resolves to.

    The TPU kernel always prefers the counting scatter (sort networks
    are MXU-hostile); on CPU/GPU the O(n·C) reference wins only below
    the :data:`SORT_CROSSOVER_C` bucket-count crossover.
    """
    del n
    if on_tpu():
        return "scatter"
    return "scatter" if C <= SORT_CROSSOVER_C else "sort"


def scatter_dest(ids, *, C: int, use_kernel=None):
    """Sort-free bucketed destinations: ``(dest, counts, offsets)``.

    ``dest[i] = offsets[ids[i]] + stable-rank-within-bucket`` — the
    inverse of ``jnp.argsort(ids, stable=True)``'s permutation; padding
    ids (outside [0, C)) get the sentinel ``n``.  ``offsets`` is the
    (C+1,) exclusive scan of ``counts``.  ``use_kernel=None`` dispatches
    per :func:`scatter_impl`.
    """
    n = ids.shape[0]
    if use_kernel is None:
        use_kernel = scatter_impl(n, C) == "kernel"
    with compat.named_scope("kernel/scatter-dest"):
        if use_kernel:
            dest, counts = scatter_dest_pallas(
                ids, C=C, block_n=kernel_block_n(C) or 128,
                interpret=not on_tpu())
        else:
            dest, counts = scatter_dest_ref(ids, C=C)
        offsets = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32),
             jnp.cumsum(counts).astype(jnp.int32)])
        return dest, counts, offsets


def bucket_ranks(ids, *, C: int, use_kernel=None):
    """Stable within-bucket ranks: ``(rank, counts)``; padding rank −1.

    Kernel path derives the rank from the fused destinations
    (``rank = dest − offsets[id]``) — exact int arithmetic, identical to
    the reference.
    """
    n = ids.shape[0]
    if use_kernel is None:
        use_kernel = scatter_impl(n, C) == "kernel"
    if not use_kernel:
        return bucket_ranks_ref(ids, C=C)
    ids = jnp.asarray(ids, jnp.int32)
    dest, counts, offsets = scatter_dest(ids, C=C, use_kernel=True)
    base = jnp.take(offsets, jnp.clip(ids, 0, C - 1))
    valid = (ids >= 0) & (ids < C)
    rank = jnp.where(valid, dest - base, -1).astype(jnp.int32)
    return rank, counts
