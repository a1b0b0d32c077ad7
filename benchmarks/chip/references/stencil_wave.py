"""Plain reference for rebalance requests on a stencil-wave deployment,
and the comparison that decides ``correct``.

It imports nothing of the program.  It builds the deployment itself (a
periodic 5-point stencil, one object per grid point, the tiled initial
mapping, a Gaussian load hotspot orbiting the grid), recomputes each
request's loads in float64, plans a sample of the requests drawn from
the seed with the plain NumPy planner (``chipbench.planner_ref``) from
the same previous assignment the program was given, and compares.
"""
from __future__ import annotations

import math

import numpy as np

from chipbench import planner_ref

LIMITS = {
    "plan_diff_pct": 2.0,      # objects whose owner differs from the ref
    "plan_gap_pct": 1.0,       # max/avg of a plan over the ref plan's, %
}


def _factor2(p):
    a = int(math.isqrt(p))
    while p % a:
        a -= 1
    return a, p // a


def deployment(s):
    """(initial assignment, edge src, edge dst, edge bytes)."""
    G, P = s["grid"], s["num_nodes"]
    i, j = np.meshgrid(np.arange(G), np.arange(G), indexing="ij")
    i, j = i.ravel(), j.ravel()
    if s["mapping"] != "tiled":
        raise ValueError(f"reference knows the tiled mapping only, "
                         f"not {s['mapping']!r}")
    px, py = _factor2(P)
    a0 = (np.minimum(i * px // G, px - 1) * py
          + np.minimum(j * py // G, py - 1))
    src = np.concatenate([i * G + j, i * G + j])
    dst = np.concatenate([((i + 1) % G) * G + j, i * G + (j + 1) % G])
    w = np.full(src.shape[0], float(s["bytes_per_edge"]))
    return a0.astype(np.int64), src, dst, w


def wave_loads(s, t):
    """Object loads at step ``t``: 1 + amp * exp(-d^2 / (2 (G/8)^2)) around
    a centre orbiting at radius G/3 with the given period."""
    G = s["grid"]
    i, j = np.meshgrid(np.arange(G), np.arange(G), indexing="ij")
    ang = 2.0 * math.pi * t / s["period"]
    cx = G / 2.0 + G / 3.0 * math.cos(ang)
    cy = G / 2.0 + G / 3.0 * math.sin(ang)
    d2 = ((i - cx) ** 2 + (j - cy) ** 2).ravel()
    return np.maximum(1.0 + s["amp"] * np.exp(-d2 / (2.0 * (G / 8.0) ** 2)),
                      1e-3)


def judge(config, traffic, seed, answers):
    """``(checks, quality)`` as in ``references/pic_prk.py``."""
    s = config["system"]
    P, K = s["num_nodes"], s["k_neighbours"]
    a0, src, dst, w = deployment(s)
    cyc = [np.asarray(a).astype(np.int64) for a in answers["first_cycle"]]
    phase = int(traffic["phase"])
    every = int(traffic["lb_every"])
    checks = {"repeat_mismatch": (answers["repeat_mismatch"], 0)}
    rng = np.random.default_rng(int(seed))
    n = len(cyc)
    sample = sorted(rng.choice(n, size=min(int(traffic["check_requests"]),
                                           n), replace=False))
    diff = gap = 0.0
    non_nbr = out = rounds_bad = 0
    for j in sample:
        prev = a0 if j == 0 else cyc[j - 1]
        r = planner_ref.compare(cyc[j], prev, wave_loads(s, phase + every * j),
                                src, dst, w, P, K)
        diff, gap = max(diff, r["diff_pct"]), max(gap, r["gap_pct"])
        non_nbr += r["non_neighbour"]
        out += r["out_of_range"]
        rounds_bad += int(answers["rounds"][j]
                          != r["stats"]["protocol_rounds"])
    checks["plan_out_of_range"] = (out, 0)
    checks["plan_non_neighbour_moves"] = (non_nbr, 0)
    checks["protocol_rounds_mismatch"] = (rounds_bad, 0)
    checks["plan_diff_pct"] = (diff, LIMITS["plan_diff_pct"])
    checks["plan_gap_pct"] = (gap, LIMITS["plan_gap_pct"])
    quality = {}
    if n == int(traffic["cycle_requests"]):
        ma, ext, intra = [], 0.0, 0.0
        for j, a in enumerate(cyc):
            ma.append(planner_ref.max_avg(
                wave_loads(s, phase + every * j), a, P))
            cross = a[src] != a[dst]
            ext += float(w[cross].sum())
            intra += float(w[~cross].sum())
        quality = {"max_avg_load": float(np.mean(ma)),
                   "ext_int_comm": ext / intra}
    return checks, quality


def control_answers(cell, seed):
    """The control: one cycle of rebalance requests planned by the
    reference planner in bfloat16, the precision below the
    configuration's float32, each from the previous answer.  Returns
    answers shaped as the adapter's."""
    import ml_dtypes

    s = cell.config["system"]
    b16 = ml_dtypes.bfloat16
    requests = int(cell.traffic["cycle_requests"])
    a0, src, dst, w = deployment(s)
    phase = int(cell.traffic["phase"])
    every = int(cell.traffic["lb_every"])
    out, rounds, prev = [], [], a0
    for j in range(requests):
        loads = wave_loads(s, phase + every * j).astype(b16)
        new, _, _, st = planner_ref.plan(loads, prev, src, dst,
                                         w.astype(b16), s["num_nodes"],
                                         k=s["k_neighbours"], dt=b16)
        out.append(new)
        rounds.append(st["protocol_rounds"])
        prev = new.astype(np.int64)
    return dict(first_cycle=out, repeats=0, repeat_mismatch=0,
                rounds=rounds, iters=[])
