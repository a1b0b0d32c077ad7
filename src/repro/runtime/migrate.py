"""Device-resident payload migration: the *apply* half of a rebalance.

Planning produces an old→new assignment pair; this module turns that
pair into per-node send/recv manifests and **executes** them, so the
replay layers stop merely counting migration and actually move payload
(the paper's §II migration-cost term, Demiralp et al.'s dominant
end-to-end cost).  Two execution paths:

  * **single device** — :func:`build_manifest` + :func:`apply_manifest`:
    a bucketed gather that reorders the payload arrays so each node's
    items occupy one contiguous slot region (stable order: by new owner,
    ties by previous position).  Pure and shape-stable, so it runs under
    ``jit`` / ``lax.scan`` / ``lax.cond`` — the scanned PIC driver
    executes it inside the replay scan.  :func:`build_and_apply` fuses
    build + apply in one traced expression (the scanned hot path);
    :func:`migrate` is the eager entry with the payload buffers donated
    to the executable on accelerators (double-buffered exchange: XLA may
    write the relocated arrays over the originals).

**The ``method`` knob** (:func:`build_manifest`, :func:`build_and_apply`,
:func:`migrate`): ``"sort"`` builds the permutation with the historical
stable ``argsort``; ``"scatter"`` builds it sort-free via the fused
counting-scatter kernel package (``kernels.migrate``: histogram →
exclusive-scan offsets → stable within-owner rank, O(n·P) MXU-friendly
work instead of the O(n log n) sort network); ``"auto"`` (default) picks
per backend and node count (:func:`kernels.migrate.preferred_method` —
scatter everywhere on TPU, scatter up to the measured C ≈ 64 crossover
on CPU).  **Bit-for-bit layout contract**: every method produces the
identical ``Manifest`` — ``order`` *is* ``argsort(owner_new,
stable=True)`` whichever way it was computed — so replay trajectories,
parity suites and the sharded exchange are method-independent.
  * **mesh-sharded** — :func:`migrate_sharded`: a ``ppermute`` ring
    all-to-all under ``shard_map`` on a 1-D device mesh.  Each shard
    owns a contiguous node range; the local payload block rotates D-1
    hops around the ring and every shard scatters the items it owns into
    its slot region as they pass.  Destination offsets are computed from
    an all-gathered (D, P) count matrix, so the concatenated per-shard
    regions are **bit-for-bit** the single-device bucketed layout.

Conservation is structural: both paths apply a permutation (plus
padding on the sharded path), so item count, total bytes, and every
per-item payload value are preserved exactly — tests/test_runtime.py
asserts all three on both paths.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P_

from repro.distributed import compat
from repro.kernels import migrate as mig_ops

AXIS = "mig"


class CapacityOverflowError(ValueError):
    """A migration would exceed a per-shard/per-node slot budget.

    Raised by the **eager** entries only (:func:`migrate_sharded` in its
    default ``on_overflow="strict"`` mode, and :func:`migrate` when a
    ``capacity`` bound is passed) — inside a compiled scan a Python
    exception is meaningless, which is exactly why the in-scan exchange
    offers the ``spill`` mode instead (overflow items stay on their
    source shard and retry at the next fired rebalance).

    Structured fields: ``capacity`` (the budget), ``counts`` (per-unit
    inflow item counts), ``offending`` (unit ids over budget), ``unit``
    (``"shard"`` or ``"node"``)."""

    def __init__(self, *, capacity: int, counts, unit: str = "shard"):
        self.capacity = int(capacity)
        self.counts = [int(c) for c in np.asarray(counts).ravel()]
        self.unit = str(unit)
        self.offending = [i for i, c in enumerate(self.counts)
                          if c > self.capacity]
        super().__init__(
            f"per-{self.unit} capacity={self.capacity} overflowed: inflow "
            f"counts per {self.unit} {self.counts} exceed the budget at "
            f"{self.unit} ids {self.offending}; the exchange would have "
            "dropped payload — raise capacity (n is always safe) or use "
            "on_overflow='spill'")


class Manifest(NamedTuple):
    """Executable exchange plan for one old→new ownership pair.

    ``order`` is the bucketed gather permutation (stable sort by new
    owner — identical whichever build method produced it); ``dest`` is
    its inverse (``dest[i]`` = item ``i``'s slot), populated only by the
    sort-free scatter build where it falls out for free; ``offsets[p]:
    offsets[p+1]`` is node ``p``'s slot region in the relocated layout;
    ``send_counts[s, d]`` counts items moving from node ``s`` to node
    ``d`` — the off-diagonal is the executed exchange, the diagonal
    stays put."""

    order: jax.Array        # (n,) i32 gather permutation
    offsets: jax.Array      # (P+1,) i32 slot-region boundaries
    send_counts: jax.Array  # (P, P) i32 per-node send/recv matrix
    moved: jax.Array        # (n,) bool — item changed owner
    dest: Optional[jax.Array] = None  # (n,) i32 scatter permutation

    @property
    def moved_count(self) -> jax.Array:
        """i32 scalar — items actually exchanged (equals the
        off-diagonal ``send_counts`` sum)."""
        return self.moved.sum().astype(jnp.int32)

    def moved_bytes(self, bytes_per_item) -> jax.Array:
        """f32 scalar — executed exchange volume (uniform item size)."""
        return self.moved_count.astype(jnp.float32) * bytes_per_item

    def moved_sum(self, weights, where=None) -> jax.Array:
        """f32 scalar — executed exchange volume with **per-item** sizes.

        ``weights`` is (n,) f32 — e.g. each session's resident KV-cache
        bytes in the serving data plane, where items are far from
        uniform; ``where`` optionally restricts the sum to a live-item
        mask (free fleet slots move for free).  The uniform-size
        :meth:`moved_bytes` is the special case ``weights = const``."""
        w = jnp.where(self.moved, jnp.asarray(weights, jnp.float32), 0.0)
        if where is not None:
            w = jnp.where(jnp.asarray(where, bool), w, 0.0)
        return w.sum()


def resolve_method(method: str, *, n: int, num_nodes: int) -> str:
    """Resolve the ``method`` knob to ``"sort"`` or ``"scatter"``.

    ``"auto"`` consults :func:`kernels.migrate.preferred_method` (backend
    + node-count crossover); explicit values pass through.  Shapes are
    static under tracing, so resolution happens at trace time."""
    if method == "auto":
        return mig_ops.preferred_method(int(n), int(num_nodes))
    if method not in ("sort", "scatter"):
        raise ValueError(f"unknown manifest method {method!r}")
    return method


def build_manifest(owner_old, owner_new, num_nodes: int,
                   method: str = "auto") -> Manifest:
    """Traceable manifest for relocating items between node slot regions.

    ``owner_old``/``owner_new`` are (n,) i32 per-item node ids (for PIC:
    ``assignment[chare_id]`` before/after the plan).  ``method`` selects
    how the bucketed permutation is built — ``"sort"`` (stable argsort),
    ``"scatter"`` (sort-free counting scatter, ``kernels.migrate``) or
    ``"auto"`` (:func:`resolve_method`).  The resulting ``Manifest`` is
    bit-for-bit identical either way; the scatter build additionally
    populates ``dest`` (the inverse permutation it derives the layout
    from)."""
    owner_old = jnp.asarray(owner_old, jnp.int32)
    owner_new = jnp.asarray(owner_new, jnp.int32)
    ones = jnp.ones(owner_new.shape, jnp.int32)
    n = int(owner_new.shape[0])
    if resolve_method(method, n=n, num_nodes=num_nodes) == "scatter":
        dest, counts, offsets = mig_ops.scatter_dest(owner_new, C=num_nodes)
        # one O(n) scatter materializes the gather permutation (dest is a
        # permutation here: every owner id is valid)
        order = (jnp.zeros((n,), jnp.int32)
                 .at[dest].set(jnp.arange(n, dtype=jnp.int32),
                               unique_indices=True, mode="drop"))
    else:
        dest = None
        order = jnp.argsort(owner_new, stable=True).astype(jnp.int32)
        counts = jax.ops.segment_sum(ones, owner_new,
                                     num_segments=num_nodes)
        offsets = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32),
             jnp.cumsum(counts).astype(jnp.int32)])
    pair = owner_old * num_nodes + owner_new
    send = jax.ops.segment_sum(
        ones, pair, num_segments=num_nodes * num_nodes
    ).reshape(num_nodes, num_nodes)
    return Manifest(order=order, offsets=offsets, send_counts=send,
                    moved=owner_old != owner_new, dest=dest)


def apply_manifest(manifest: Manifest, *arrays) -> Tuple[jax.Array, ...]:
    """Gather every payload array into the manifest's bucketed layout."""
    return tuple(jnp.take(jnp.asarray(a), manifest.order, axis=0)
                 for a in arrays)


def build_and_apply(owner_old, owner_new, arrays: Sequence, *,
                    num_nodes: int, method: str = "auto"):
    """Fused build + apply: ``(relocated_arrays, manifest)`` in one trace.

    The scanned replay loops call this inside their step ``jit`` so the
    whole pipeline — counts, offsets, destinations, permutation, payload
    gathers — compiles into a single XLA program with no executable
    boundary between the manifest build and the payload movement.  On
    the scatter path the permutation is materialized exactly once (one
    i32 scatter) and every payload array then moves by gather: per-array
    destination scatters were measured slower than scatter-once + gather
    on CPU XLA (scatters cost ~25× a gather there) and scatters
    serialize on TPU, so the gather form wins for any payload count.
    Layout is bit-for-bit the ``method="sort"`` result."""
    man = build_manifest(owner_old, owner_new, num_nodes, method=method)
    return apply_manifest(man, *arrays), man


def inverse_permutation(order) -> jax.Array:
    """Scatter permutation undoing :func:`apply_manifest`'s gather."""
    order = jnp.asarray(order, jnp.int32)
    return (jnp.zeros(order.shape, jnp.int32)
            .at[order].set(jnp.arange(order.shape[0], dtype=jnp.int32)))


@functools.lru_cache(maxsize=32)
def _migrate_exec(num_nodes: int, donate: bool, method: str):
    def fn(owner_old, owner_new, arrays):
        return build_and_apply(owner_old, owner_new, arrays,
                               num_nodes=num_nodes, method=method)

    return jax.jit(fn, donate_argnums=(2,) if donate else ())


def migrate(owner_old, owner_new, arrays: Sequence, *, num_nodes: int,
            donate: Optional[bool] = None, method: str = "auto",
            capacity: Optional[int] = None):
    """Eager single-device migration: ``(relocated_arrays, manifest)``.

    ``donate=None`` donates the payload buffers wherever the backend
    supports donation (not CPU XLA) — the executed exchange then
    double-buffers in place instead of allocating a second copy.
    ``method`` is the manifest-build knob (see :func:`build_manifest`);
    the relocated layout is identical for every setting.  ``capacity``,
    if given, bounds the per-**node** slot count of the relocated
    layout; exceeding it raises :class:`CapacityOverflowError` with the
    per-node inflow counts and offending node ids (the eager path stays
    strict — spill semantics belong to the in-scan exchanges)."""
    if donate is None:
        donate = jax.default_backend() != "cpu"
    with compat.named_scope("exchange/migrate"):
        out, man = _migrate_exec(int(num_nodes), bool(donate),
                                 str(method))(
            jnp.asarray(owner_old, jnp.int32),
            jnp.asarray(owner_new, jnp.int32), tuple(arrays))
    if capacity is not None:
        counts = np.diff(np.asarray(man.offsets))
        if (counts > int(capacity)).any():
            raise CapacityOverflowError(capacity=capacity, counts=counts,
                                        unit="node")
    return out, man


# ------------------------------------------------- spill (degradation) --


def spill_admissions(flow, occupancy, capacity) -> jax.Array:
    """Feasible admitted-flow matrix under a per-group slot budget.

    ``flow`` is the (G, G) i32 *wanted* move-count matrix between groups
    (nodes or shards; the diagonal — items staying put — is ignored),
    ``occupancy`` the (G,) current item count per group, ``capacity``
    the static slot budget every group must respect after the exchange.
    Returns ``A`` (G, G) with ``0 <= A <= off-diag(flow)`` such that
    every post-exchange count ``occupancy - A.sum(1) + A.sum(0)`` is
    ``<= capacity``, shrinking as little flow as possible per round and
    deferring from the **highest source index first** (a fixed
    deterministic rule, so replay trajectories are reproducible).

    A fixed point exists whenever ``occupancy <= capacity`` (``A = 0``
    is then feasible); each ``lax.while_loop`` round strictly reduces
    the admitted total, so termination is guaranteed.  Groups that are
    over budget *before* any exchange (only possible with a
    caller-violated precondition) exit with ``A = 0`` rather than loop
    forever.  Traceable and scan-safe — this is the solver behind both
    the per-node :func:`spill_owner` and the per-shard spill mode of
    :func:`ring_exchange`."""
    flow = jnp.asarray(flow, jnp.int32)
    G = flow.shape[0]
    occupancy = jnp.asarray(occupancy, jnp.int32)
    capacity = jnp.asarray(capacity, jnp.int32)
    eye = jnp.eye(G, dtype=bool)
    F = jnp.where(eye, 0, flow)

    def post(A):
        return occupancy - A.sum(axis=1) + A.sum(axis=0)

    def cond(A):
        return (post(A) > capacity).any() & (A.sum() > 0)

    def body(A):
        over = jnp.maximum(post(A) - capacity, 0)            # (G,)
        # per column: how much flow arrives from rows *below* each source
        # — cutting top-down means cut[s] covers whatever the rows after
        # it cannot absorb
        below = (jnp.cumsum(A[::-1], axis=0)[::-1] - A)      # (G, G)
        cut = jnp.clip(over[None, :] - below, 0, A)
        return A - cut

    return jax.lax.while_loop(cond, body, F)


def spill_owner(owner_old, owner_new, *, num_nodes: int, capacity):
    """Clamp a plan's per-node inflow to ``capacity`` by deferring moves.

    The single-device counterpart of :func:`ring_exchange`'s spill mode:
    items whose admission would push the destination node over the slot
    budget keep their **old** owner (they stay physically where they
    are) and simply retry at the next fired rebalance, when the next
    plan recomputes ``owner_new`` from the live assignment.  Within each
    (src, dst) flow the *first* items in slab order are admitted —
    deterministic, so replay trajectories are reproducible.

    Returns ``(owner_eff, deferred)``: the effective (n,) owner vector
    to hand to :func:`build_and_apply` / :func:`migrate`, and the (n,)
    bool mask of deferred items (``deferred.sum()`` is the per-step
    ``deferred_count``).  Requires every *current* per-node count to be
    ``<= capacity`` (always true when the previous exchange respected
    the same budget); payload is never dropped either way."""
    P = int(num_nodes)
    oo = jnp.asarray(owner_old, jnp.int32)
    on = jnp.asarray(owner_new, jnp.int32)
    move = on != oo
    ones = jnp.ones(oo.shape, jnp.int32)
    pair = oo * P + on
    F = jax.ops.segment_sum(
        jnp.where(move, 1, 0).astype(jnp.int32), pair,
        num_segments=P * P).reshape(P, P)
    occ = jax.ops.segment_sum(ones, oo, num_segments=P)
    A = spill_admissions(F, occ, capacity)
    # stable within-flow rank: admitted = first A[src, dst] movers of
    # each flow, in slab order (the same counting-scatter primitive the
    # manifest build uses; non-movers rank against the padding sentinel)
    rank, _ = mig_ops.bucket_ranks(jnp.where(move, pair, P * P), C=P * P)
    quota = jnp.take(A.reshape(-1), jnp.clip(pair, 0, P * P - 1))
    admitted = move & (rank < quota)
    deferred = move & ~admitted
    return jnp.where(deferred, oo, on), deferred


# ----------------------------------------------------- sharded exchange --


def ring_exchange(owner_loc, arr_loc: Tuple, *, num_nodes: int, D: int,
                  capacity: int, axis: str, count_loc=None,
                  mode: str = "strict"):
    """Per-shard ring all-to-all core (runs under ``shard_map``).

    Shard ``d`` owns nodes ``[d*rpd, (d+1)*rpd)``.  The local block
    rotates D-1 ``ppermute`` hops; at hop ``s`` shard ``me`` sees the
    block of shard ``(me+s) % D`` and scatters the items it owns into
    its (capacity,) output at exact global-bucket positions, computed
    from the all-gathered (D, P) count matrix plus the sort-free
    within-bucket rank (``kernels.migrate.bucket_ranks`` — the same
    counting-scatter primitive the single-device manifest build uses) —
    so the concatenated per-shard valid prefixes reproduce the
    single-device stable bucketed order bit-for-bit.

    ``count_loc`` (i32 scalar, optional) marks only the first
    ``count_loc`` slots of this shard's slab as live items; the rest are
    padding and are neither counted nor scattered.  ``None`` treats the
    whole slab as live (the :func:`migrate_sharded` entry).  The masked
    form is what lets the **sharded replay loop**
    (``distributed/replay_shard.py``) carry fixed-``capacity`` payload
    slabs through ``lax.scan`` and re-bucket them at every fired
    rebalance without a host trip.

    ``mode`` selects the overflow semantics.  ``"strict"`` (default)
    assumes the plan fits the slot budget — the caller is responsible
    for checking the returned counts (the layout contract above holds).
    ``"spill"`` is the graceful-degradation exchange: per-shard inflow
    is clamped to ``capacity`` by the :func:`spill_admissions` fixed
    point, overflow items **stay on their source shard** (their desired
    owner id is preserved in the owner slab so the next fired rebalance
    retries them), and the extra return value ``deferred`` (replicated
    i32 scalar) counts them.  Spill keeps every item exactly once —
    payload is never dropped — but gives up the bit-for-bit bucketed
    *layout* contract: kept items compact to the slab prefix in slab
    order, admitted inflow appends in (source shard, within-flow rank)
    order.

    Returns ``(out_owner, outs, count_me)`` — the (capacity,) relocated
    owner/payload slabs (valid prefix ``count_me``) for this shard —
    plus ``deferred`` in spill mode.
    """
    if mode not in ("strict", "spill"):
        raise ValueError(f"unknown ring_exchange mode {mode!r}")
    rpd = num_nodes // D
    me = jax.lax.axis_index(axis)
    slots = jnp.arange(owner_loc.shape[0], dtype=jnp.int32)
    live = (jnp.ones(owner_loc.shape, bool) if count_loc is None
            else slots < jnp.asarray(count_loc, jnp.int32))
    # padding slots carry stale owner ids: segment them out of range so
    # they contribute to no bucket
    owner_loc = jnp.where(live, owner_loc, num_nodes)
    cnt_loc = jax.ops.segment_sum(
        jnp.ones(owner_loc.shape, jnp.int32), owner_loc,
        num_segments=num_nodes)
    counts = jax.lax.all_gather(cnt_loc, axis)          # (D, P)
    if mode == "spill":
        return _ring_exchange_spill(
            owner_loc, arr_loc, live=live, counts=counts,
            num_nodes=num_nodes, D=D, capacity=capacity, axis=axis, me=me)
    with compat.named_scope("exchange/ring"):
        bucket = counts.sum(axis=0)                     # (P,) global sizes
        my_sizes = jax.lax.dynamic_slice(bucket, (me * rpd,), (rpd,))
        my_base = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32),
             jnp.cumsum(my_sizes).astype(jnp.int32)])[:rpd]  # (rpd,)

        # payload slabs relocate on the leading axis; trailing axes ride
        # along untouched (expert weight matrices are just bigger rows)
        outs = tuple(jnp.zeros((capacity,) + a.shape[1:], a.dtype)
                     for a in arr_loc)
        out_owner = jnp.zeros((capacity,), jnp.int32)
        buf = (owner_loc,) + tuple(arr_loc)
        for s in range(D):
            src = (me + s) % D
            pe = buf[0]
            accept = (pe // rpd) == me  # padding (pe == P) accepts nowhere
            # items from earlier source shards land first within each
            # bucket (source order == global index order: shards hold
            # contiguous global ranges), preserving the stable tie order
            before = (counts * (jnp.arange(D)[:, None] < src)).sum(0)
            # per-shard placement rides the shared sort-free counting-
            # scatter op: stable within-bucket rank of the accepted items
            # (rejected slots mask to the padding sentinel → rank −1)
            rank, _ = mig_ops.bucket_ranks(
                jnp.where(accept, pe, num_nodes), C=num_nodes)
            r = jnp.clip(pe - me * rpd, 0, rpd - 1)
            pos = jnp.where(
                accept,
                my_base[r] + jnp.take(before, pe, mode="clip") + rank,
                capacity)
            out_owner = out_owner.at[pos].set(pe, mode="drop")
            outs = tuple(o.at[pos].set(v, mode="drop")
                         for o, v in zip(outs, buf[1:]))
            if s + 1 < D:
                buf = tuple(
                    jax.lax.ppermute(
                        b, axis, [(d, (d - 1) % D) for d in range(D)])
                    for b in buf)
        count_me = my_sizes.sum().astype(jnp.int32)
        return out_owner, outs, count_me


def _ring_exchange_spill(owner_loc, arr_loc, *, live, counts,
                         num_nodes: int, D: int, capacity: int, axis: str,
                         me):
    """Spill-mode ring body (see :func:`ring_exchange` ``mode="spill"``).

    Admission is decided **on the source shard** from the replicated
    (D, D) shard-flow matrix, travels with the payload around the ring,
    and the destination scatters admitted items at
    ``kept_prefix + cumulative-admitted-before-source + within-flow
    rank`` — every position is < capacity by the admission fixed
    point's feasibility, so no ``mode="drop"`` scatter ever fires on a
    live item."""
    rpd = num_nodes // D
    # (D, D) wanted shard-level flow (diagonal = stays, solver ignores it)
    flow = counts.reshape(D, D, rpd).sum(-1)
    occ = counts.sum(axis=1)                             # (D,) live counts
    A = spill_admissions(flow, occ, capacity)            # (D, D) admitted
    dshard = jnp.minimum(owner_loc // rpd, D)            # padding → D
    fid = jnp.where(live & (dshard != me), dshard, D)
    # stable within-flow rank among this shard's movers to each dest
    rank, _ = mig_ops.bucket_ranks(fid, C=D)
    quota = jnp.take(A[me], jnp.clip(dshard, 0, D - 1))
    admitted = (fid < D) & (rank < quota)
    keep = live & ~admitted
    kept_me = keep.sum().astype(jnp.int32)
    # kept items (stays + deferred movers, desired owner id preserved)
    # compact to the slab prefix in slab order
    kpos = jnp.where(keep,
                     jnp.cumsum(keep.astype(jnp.int32)) - 1, capacity)
    out_owner = jnp.zeros((capacity,), jnp.int32).at[kpos].set(
        owner_loc, mode="drop")
    outs = tuple(
        jnp.zeros((capacity,) + a.shape[1:], a.dtype).at[kpos].set(
            a, mode="drop")
        for a in arr_loc)
    buf = (owner_loc, admitted.astype(jnp.int32), rank) + tuple(arr_loc)
    shift = [(d, (d - 1) % D) for d in range(D)]
    for s in range(1, D):
        buf = tuple(jax.lax.ppermute(b, axis, shift) for b in buf)
        src = (me + s) % D
        pe_b, adm_b, rank_b = buf[0], buf[1], buf[2]
        accept = (adm_b == 1) & (jnp.minimum(pe_b // rpd, D) == me)
        base = kept_me + (A[:, me] * (jnp.arange(D) < src)).sum()
        pos = jnp.where(accept, base + rank_b, capacity)
        out_owner = out_owner.at[pos].set(pe_b, mode="drop")
        outs = tuple(o.at[pos].set(v, mode="drop")
                     for o, v in zip(outs, buf[3:]))
    count_me = (kept_me + A[:, me].sum()).astype(jnp.int32)
    eye = jnp.eye(D, dtype=bool)
    deferred = (jnp.where(eye, 0, flow).sum() - A.sum()).astype(jnp.int32)
    return out_owner, outs, count_me, deferred


def _sharded_body(owner_loc, *arr_loc, num_nodes: int, D: int,
                  capacity: int, axis: str):
    """``shard_map`` adapter over :func:`ring_exchange` (whole slab live)."""
    out_owner, outs, count_me = ring_exchange(
        owner_loc, tuple(arr_loc), num_nodes=num_nodes, D=D,
        capacity=capacity, axis=axis)
    return (out_owner,) + outs + (count_me[None],)


def _sharded_body_spill(owner_loc, *arr_loc, num_nodes: int, D: int,
                        capacity: int, axis: str):
    """Spill-mode ``shard_map`` adapter (whole slab live)."""
    out_owner, outs, count_me, deferred = ring_exchange(
        owner_loc, tuple(arr_loc), num_nodes=num_nodes, D=D,
        capacity=capacity, axis=axis, mode="spill")
    return (out_owner,) + outs + (count_me[None], deferred[None])


def planned_capacity(owner_new, *, num_nodes: int, num_shards: int) -> int:
    """Static per-shard slot budget planned from an executed plan.

    The exchange's exact space requirement on shard ``d`` is the total
    bucket size of the nodes it owns — the **max inflow bound** the
    planner's flow budget realizes once stage 3 has assigned objects.
    This host-side helper computes that tight bound from ``owner_new``
    (one transfer; the eager :func:`migrate_sharded` entry already
    synchronizes on the result).  Callers that need a trace-time
    constant (the sharded replay loop, which sizes its ``lax.scan``
    payload slabs before any plan exists) must fall back to the
    worst-case ``n``."""
    counts = np.bincount(np.asarray(owner_new), minlength=num_nodes)
    per_shard = counts.reshape(num_shards, num_nodes // num_shards).sum(1)
    return max(1, int(per_shard.max()))


def migrate_sharded(owner_new, arrays: Sequence, *, num_nodes: int,
                    mesh: Optional[Mesh] = None,
                    capacity: Optional[int] = None,
                    on_overflow: str = "strict"):
    """Ring all-to-all payload exchange across a 1-D device mesh.

    ``owner_new`` / ``arrays`` are the *global* (n,) buffers, row-sharded
    over the mesh (n and ``num_nodes`` must divide the shard count; the
    caller pads if not).  ``capacity`` is the static per-shard slot
    budget; ``None`` (the default) derives the tight bound from the
    plan itself — :func:`planned_capacity`, the max per-shard inflow —
    so callers no longer have to pass the worst-case ``n``.  An explicit
    ``capacity`` overrides the planned bound (e.g. to keep one compiled
    executable across calls).

    ``on_overflow`` picks the degradation semantics when the plan wants
    more items on a shard than ``capacity`` allows.  ``"strict"`` (the
    default, and the eager contract) raises
    :class:`CapacityOverflowError` with the per-shard inflow counts and
    offending shard ids — payload is never lost silently.  ``"spill"``
    executes the admissible part of the exchange instead: inflow is
    clamped to ``capacity``, overflow items stay on their source shard
    (keeping their desired owner id, so a later call retries them), and
    a fourth return value ``deferred`` (int) counts them.  Spill gives
    up the bit-for-bit layout contract below (see
    :func:`ring_exchange`).

    Returns ``(owner_out, arrays_out, counts)`` where the outputs are
    (D*capacity,) padded global buffers (shard ``d``'s valid prefix is
    ``[d*capacity, d*capacity + counts[d])``) and ``counts`` is (D,) —
    plus ``deferred`` when ``on_overflow="spill"``.  In strict mode,
    concatenating the valid prefixes equals the single-device
    ``apply_manifest`` layout bit-for-bit."""
    if on_overflow not in ("strict", "spill"):
        raise ValueError(f"unknown on_overflow mode {on_overflow!r}")
    if mesh is None:
        mesh = Mesh(np.asarray(jax.devices()), (AXIS,))
    if len(mesh.axis_names) != 1:
        raise ValueError("migrate_sharded needs a 1-D mesh")
    ax = mesh.axis_names[0]
    D = int(np.prod(mesh.devices.shape))
    owner_new = jnp.asarray(owner_new, jnp.int32)
    n = owner_new.shape[0]
    if n % D or num_nodes % D:
        raise ValueError(
            f"n={n} and num_nodes={num_nodes} must divide the {D}-device "
            "mesh")
    spill = on_overflow == "spill"
    if capacity is None:
        capacity = planned_capacity(owner_new, num_nodes=num_nodes,
                                    num_shards=D)
        if spill:
            # the planned bound always fits; a spill caller wants a
            # *tighter* budget, but never below the current occupancy
            # (the admission fixed point needs occupancy <= capacity)
            capacity = max(capacity, n // D)
    if spill and int(capacity) < n // D:
        raise ValueError(
            f"spill capacity={int(capacity)} is below the per-shard "
            f"occupancy {n // D}; the current slabs must already fit")
    body = functools.partial(
        _sharded_body_spill if spill else _sharded_body,
        num_nodes=int(num_nodes), D=D, capacity=int(capacity), axis=ax)
    arrays = tuple(jnp.asarray(a) for a in arrays)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P_(ax),) * (1 + len(arrays)),
        out_specs=(P_(ax),) * ((3 if spill else 2) + len(arrays)),
        check_vma=False)
    out = fn(owner_new, *arrays)
    if spill:
        deferred = int(np.asarray(out[-1])[0])
        return out[0], out[1:-2], out[-2], deferred
    counts = np.asarray(out[-1])
    if (counts > capacity).any():
        raise CapacityOverflowError(capacity=capacity, counts=counts,
                                    unit="shard")
    return out[0], out[1:-1], out[-1]
