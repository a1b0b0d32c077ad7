"""Plain NumPy reference of the three-stage diffusion planner.

Written from the paper's description (§III) and the repository's
documented protocol, not from its code path: it imports nothing of the
program.  Every array is a NumPy array of dtype ``dt``: float64 for the
reference, ``ml_dtypes.bfloat16`` for the lower-precision control.

  stage 1  K-neighbour handshake over dense (P, P) preference state;
  stage 2  first-order single-hop diffusion on the neighbour graph until
           every neighbourhood is within ``tol`` of its mean;
  stage 3  per node and per neighbour slot, objects leave in decreasing
           order of the bytes they exchange with the target node, taken
           while the shipped load stays nearest the flow.

Ties break toward the lower index everywhere (a stable order).
"""
from __future__ import annotations

import numpy as np

NEG = -1e30


def _topk_mask(score, k_row, k_max):
    """Row-wise mask of the ``k_row[i]`` best entries of ``score`` (ties to
    the lower column), counting only entries above NEG / 2."""
    P = score.shape[0]
    s = score.copy()
    rows = np.arange(P)
    out = np.zeros(score.shape, bool)
    for j in range(min(int(k_max), score.shape[1])):
        idx = np.argmax(s, axis=1)              # first maximum: lower index
        val = s[rows, idx]
        take = (val > NEG / 2) & (j < k_row)
        out[rows[take], idx[take]] = True
        s[rows, idx] = -np.inf
    return out


def node_comm(assignment, src, dst, w, P, dt):
    m = np.zeros((P, P), dt)
    np.add.at(m, (assignment[src], assignment[dst]), w.astype(dt))
    return m + m.T


def neighbours(pref, k, max_rounds=64):
    """Stage 1: returns (nbr_idx (P, K) with -1 padding, mask, rounds)."""
    P = pref.shape[0]
    eye = np.eye(P, dtype=bool)
    cand = (pref > 0) & ~eye
    pref = np.where(cand, pref, NEG)
    max_possible = np.minimum(cand.sum(1), k)
    edges = np.zeros((P, P), bool)
    tried = np.zeros((P, P), bool)
    rounds = stall = 0
    while (rounds < max_rounds and stall < 4
           and (edges.sum(1) < max_possible).any()):
        prev = edges.sum()
        need = np.maximum(k - edges.sum(1), 0)
        n_req = np.where(need > 0, (need + 1) // 2, 0)
        req = _topk_mask(np.where(tried | edges, NEG, pref), n_req, k)
        mutual = req & req.T
        mut = _topk_mask(np.where(mutual, pref, NEG), need, k)
        edges = edges | (mut & mut.T)
        deg = edges.sum(1)
        req = req & ~mutual
        grant_t = _topk_mask(np.where(req.T, pref, NEG),
                             np.maximum(k - deg, 0), k)      # [j, i]
        granted_out = grant_t.sum(1)
        ack = _topk_mask(np.where(grant_t.T, pref, NEG),
                         np.maximum(k - deg - granted_out, 0), k)
        edges = edges | ack | ack.T
        tried = tried | req
        left = (np.where(tried | edges, NEG, pref) > NEG / 2).sum(1)
        exhausted = (left == 0) & (edges.sum(1) < max_possible)
        tried[exhausted] = False
        stall = 0 if edges.sum() > prev else stall + 1
        rounds += 1
    score = np.where(edges, pref, NEG)
    order = np.argsort(-score, axis=1, kind="stable")[:, :min(k, P)]
    taken = np.take_along_axis(edges, order, axis=1)
    return np.where(taken, order, -1), taken, rounds


def _residual(x, nbr, mask):
    xn = np.where(mask, x[np.where(mask, nbr, 0)], x[:, None])
    allx = np.concatenate([x[:, None], xn], 1)
    m = np.concatenate([np.ones((x.shape[0], 1), bool), mask], 1)
    mean = (allx * m).sum(1) / m.sum(1)
    dev = np.where(m, np.abs(allx - mean[:, None]), 0).max(1)
    return (dev / (x.mean() + x.dtype.type(1e-30))).max()


def diffuse(loads, nbr, mask, dt, tol=0.02, max_iters=512):
    """Stage 2: returns (flows (P, K), sweeps)."""
    P, K = nbr.shape
    one = dt(1.0)
    alpha = dt(1.0 / (K + 1.0))
    safe = np.where(mask, nbr, 0)
    rev = np.zeros((P, K), np.int64)
    for i in range(P):
        for s in range(K):
            if mask[i, s]:
                rev[i, s] = int(np.argmax(nbr[nbr[i, s]] == i))
    x = loads.astype(dt)
    own = x.copy()
    flow = np.zeros((P, K), dt)
    res = _residual(x, nbr, mask)
    it = stall = 0
    while it < max_iters and res > tol and stall < 3:
        xn = np.where(mask, x[safe], x[:, None])
        push = np.maximum(alpha * (x[:, None] - xn), dt(0)) * mask
        tot = push.sum(1)
        scale = np.where(tot > 0, np.minimum(one, own / (tot + dt(1e-30))),
                         one)
        push = push * scale[:, None]
        recv = np.where(mask, push[safe, rev], dt(0))
        x2 = x - push.sum(1) + recv.sum(1)
        own = own - push.sum(1)
        flow = flow + (push - recv)
        moved = np.abs(x2 - x).sum()
        stall = stall + 1 if moved <= 1e-6 * (np.abs(x2).mean() + 1e-30) \
            else 0
        x = x2
        res = _residual(x, nbr, mask)
        it += 1
    return flow, it


def select(loads, assignment, src, dst, w, nbr, mask, flows, dt):
    """Stage 3: returns the new (N,) assignment."""
    N = loads.shape[0]
    P, K = nbr.shape
    loads = loads.astype(dt)
    w = w.astype(dt)
    a = assignment.copy()
    moved = np.zeros(N, bool)
    send = np.where(mask, np.maximum(flows, 0), 0).astype(dt)
    nodes = np.arange(P)
    for _ in range(K):
        slot = np.argmax(send, axis=1)
        budget = send[nodes, slot]
        target = np.where(budget > 0, nbr[nodes, slot], -1)
        tgt = target[a]
        score = np.zeros(N, dt)
        for u, v in ((src, dst), (dst, src)):
            hit = (a[v] == tgt[u]) & (tgt[u] >= 0)
            np.add.at(score, u, np.where(hit, w, dt(0)))
        elig = ~moved & (tgt >= 0)
        eff = np.where(elig, score.astype(np.float64), NEG)
        order = np.lexsort((-eff, a))                 # by node, then score
        node_s = a[order]
        load_s = np.where(elig, loads, dt(0))[order]
        csum = np.cumsum(load_s, dtype=dt)
        start = np.searchsorted(node_s, nodes)        # first row of a node
        first = np.zeros(P, dt)
        has = start < N
        first[has] = csum[start[has]] - load_s[start[has]]
        within = csum - first[node_s]                 # in-node cumsum
        take_s = ((within - dt(0.5) * load_s) <= budget[node_s]) \
            & elig[order] & (load_s > 0)
        take = np.zeros(N, bool)
        take[order] = take_s
        a = np.where(take, np.where(target >= 0, target, 0)[a], a)
        moved |= take
        send[nodes, slot] = 0
    return a


def plan(loads, assignment, src, dst, w, P, *, k=4, dt=np.float64):
    """All three stages; returns (assignment, nbr_idx, nbr_mask, stats)."""
    valid = src >= 0
    src, dst, w = src[valid], dst[valid], w[valid]
    comm = node_comm(assignment, src, dst, w, P, dt)
    eps = dt(1e-6) * (dt(1.0) + comm.max())
    pref = np.where(np.eye(P, dtype=bool), dt(0), comm + eps)
    nbr, mask, rounds = neighbours(pref, k)
    nloads = np.zeros(P, dt)
    np.add.at(nloads, assignment, loads.astype(dt))
    flows, sweeps = diffuse(nloads, nbr, mask, dt)
    new = select(loads, assignment, src, dst, w, nbr, mask, flows, dt)
    return new, nbr, mask, dict(protocol_rounds=rounds, diffusion_iters=sweeps)


def max_avg(loads, assignment, P):
    pe = np.bincount(assignment, weights=loads, minlength=P)
    return pe.max() / pe.mean()


def compare(new, old, loads, src, dst, w, P, k, dt=np.float64):
    """Compare one plan with the reference planner's on the same input.

    Returns a dict: ``diff_pct`` (objects whose owner differs, %),
    ``gap_pct`` (how much worse the plan's max/avg load is than the
    reference plan's, %), ``non_neighbour`` (moves to a node that is not
    a stage-1 neighbour of the object's node in the reference) and
    ``out_of_range`` (owners outside [0, P))."""
    ref, nbr, mask, stats = plan(loads, old, src, dst, w, P,
                                             k=k, dt=dt)
    out = int(((new < 0) | (new >= P)).sum())
    safe = np.clip(new, 0, P - 1)
    allowed = np.zeros((P, P), bool)
    rows = np.repeat(np.arange(P), nbr.shape[1])
    allowed[rows[mask.ravel()], nbr.ravel()[mask.ravel()]] = True
    non_nbr = int(((new != old) & ~allowed[old, safe]).sum())
    ma_ref = max_avg(loads, ref, P)
    gap = 100.0 * (max_avg(loads, safe, P) - ma_ref) / ma_ref
    return dict(diff_pct=100.0 * float((new != ref).mean()),
                gap_pct=float(gap), non_neighbour=non_nbr,
                out_of_range=out, stats=stats)
