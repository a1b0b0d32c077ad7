"""Stage 3 — object selection (paper §III.C).

Realizes the stage-2 virtual flows with actual objects.  Faithful rules:

  * per destination neighbor ``n``, objects leave in decreasing order of the
    bytes they exchange with ``n`` (communication variant) or increasing
    distance to ``n``'s centroid (coordinate variant §IV);
  * when an object moves, its peers' communication patterns update to point
    at the new residence — honored by recomputing the object→neighbor byte
    table between phases (and centroids, for the coordinate variant);
  * single-hop: an object migrates at most once per LB round.

Vectorization: one *phase* per neighbor slot (K phases, K small).  In each
phase every node works on its largest-remaining-budget neighbor; the
per-node "sort by metric, take while under budget" is a global lexsort +
segmented prefix sum — no data-dependent host loops, so the whole planner
jits and can run inside the training loop (distributed/ep_balance.py).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import comm_graph
from repro.distributed import compat

NEG = jnp.float32(-1e30)


class SelectionResult(NamedTuple):
    assignment: jax.Array     # (N,) new object→node map
    moved: jax.Array          # (N,) bool
    realized: jax.Array       # (P, K) load actually shipped per neighbor slot
    residual: jax.Array       # (P, K) unrealized flow (wanted - shipped)


def _segmented_take_while(
    node: jax.Array,       # (N,) segment id per object (its current node)
    score: jax.Array,      # (N,) ordering metric, higher = leaves first
    loads: jax.Array,      # (N,) object loads
    eligible: jax.Array,   # (N,) bool — participates in this phase
    budget: jax.Array,     # (P,) per-node load budget
) -> jax.Array:
    """Per node: order eligible objects by score desc, select while the
    running load stays under budget (midpoint rule: an object is taken iff
    taking it lands closer to the budget than stopping)."""
    P = budget.shape[0]
    eff_score = jnp.where(eligible, score, NEG)
    order = jnp.lexsort((-eff_score, node))            # by node, then score
    node_s = node[order]
    load_s = jnp.where(eligible, loads, 0.0)[order]
    csum = jnp.cumsum(load_s)
    seg_tot = jax.ops.segment_sum(load_s, node_s, num_segments=P)
    before = jnp.concatenate([jnp.zeros(1), jnp.cumsum(seg_tot)[:-1]])
    within = csum - before[node_s]                     # inclusive in-node csum
    take_s = (within - 0.5 * load_s) <= budget[node_s]
    take_s &= eligible[order] & (load_s > 0)
    take = jnp.zeros_like(take_s).at[order].set(take_s)
    return take


@functools.partial(jax.jit, static_argnames=("metric", "score_psum_axis"))
def select_objects(
    problem: comm_graph.LBProblem,
    nbr_idx: jax.Array,
    nbr_mask: jax.Array,
    flows: jax.Array,
    *,
    metric: str = "comm",
    centroids: Optional[jax.Array] = None,
    score_psum_axis: Optional[str] = None,
) -> SelectionResult:
    """Pick objects realizing ``flows`` (stage-2 output, (P, K) net loads).

    ``score_psum_axis``: mesh axis name for the distributed planner
    (``distributed/lb_shard.py``) — the problem's edge arrays are then the
    *local shard* of an edge-sharded comm graph, and the per-phase comm
    scores are completed with a ``lax.psum`` over that axis (loads /
    assignment stay replicated).  ``None`` (default) is the single-device
    path, unchanged."""
    N = problem.num_objects
    P, K = nbr_idx.shape
    loads = problem.loads
    assignment = problem.assignment
    moved = jnp.zeros((N,), bool)
    send = jnp.where(nbr_mask, jnp.maximum(flows, 0.0), 0.0)   # (P, K)
    realized = jnp.zeros_like(send)
    obj_ids = jnp.arange(N)
    node_ids = jnp.arange(P)

    valid_e = problem.edges_src >= 0
    e_src = jnp.where(valid_e, problem.edges_src, 0)
    e_dst = jnp.where(valid_e, problem.edges_dst, 0)
    e_w = jnp.where(valid_e, problem.edges_bytes, 0.0)

    for _ in range(K):
        # Phase slot: each node's largest remaining budget neighbor.
        slot = jnp.argmax(send, axis=1)                         # (P,)
        budget = send[node_ids, slot]
        target = jnp.where(budget > 0, nbr_idx[node_ids, slot], -1)  # (P,)

        # Ordering metric, per the variant.
        with compat.named_scope("score"):
            if metric == "comm":
                # Bytes each object exchanges with its node's phase
                # target — the active column of
                # comm_graph.object_node_bytes, computed directly (one
                # segment-sum over E per direction instead of the full
                # (N, K) table; the "peers update their patterns" rule is
                # preserved because this reruns on the phase's current
                # assignment).
                tgt_obj = target[assignment]                    # (N,)

                def dir_score(a, b):
                    hit = ((assignment[b] == tgt_obj[a])
                           & (tgt_obj[a] >= 0))
                    return jax.ops.segment_sum(
                        jnp.where(hit, e_w, 0.0), a, num_segments=N)

                score = dir_score(e_src, e_dst) + dir_score(e_dst, e_src)
                if score_psum_axis is not None:
                    score = jax.lax.psum(score, score_psum_axis)
            elif metric == "coord":
                assert problem.coords is not None, \
                    "coordinate variant needs coords"
                cent = _centroids(problem.coords, assignment, P)
                tgt = jnp.where(target >= 0, target, 0)[assignment]
                d2 = jnp.sum((problem.coords - cent[tgt]) ** 2, axis=-1)
                score = -d2                              # closest first
            else:
                raise ValueError(f"unknown metric {metric!r}")

        # Take while under budget, then ship.
        with compat.named_scope("take"):
            eligible = ~moved & (target[assignment] >= 0)
            take = _segmented_take_while(assignment, score, loads,
                                         eligible, budget)

            shipped = jax.ops.segment_sum(
                jnp.where(take, loads, 0.0), assignment, num_segments=P
            )
            new_owner = jnp.where(target >= 0, target, 0)[assignment]
            assignment = jnp.where(take, new_owner, assignment)
            moved = moved | take
            realized = realized.at[node_ids, slot].add(shipped)
            # slot done (shipped or not)
            send = send.at[node_ids, slot].set(0.0)

    residual = jnp.where(nbr_mask, jnp.maximum(flows, 0.0), 0.0) - realized
    return SelectionResult(assignment, moved, realized, residual)


def _centroids(coords: jax.Array, assignment: jax.Array, P: int) -> jax.Array:
    """(P, D) unweighted mean position of each node's objects (paper §IV)."""
    s = jax.ops.segment_sum(coords, assignment, num_segments=P)
    c = jax.ops.segment_sum(jnp.ones(coords.shape[0]), assignment,
                            num_segments=P)
    return s / jnp.maximum(c, 1.0)[:, None]


centroids = _centroids  # public alias
