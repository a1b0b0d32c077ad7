"""Plain reference for the PIC PRK replay, and the comparison that
decides ``correct``.

It imports nothing of the program.  The PRK's own verification is
analytic: on the alternating-column charge grid every particle moves
exactly ``2k+1`` cells east and ``vy0`` cells north per step, and its
horizontal velocity is ``2(2k+1)`` after an odd number of steps and 0
after an even one.  So after ``T`` steps the reference knows

  * every particle's position and velocity (push, corner-charge gathers);
  * every chare's particle count at every step, from the initial cell
    histogram shifted rigidly (histogram), hence each step's PE loads,
    max/avg, handoff bytes and, at each fired rebalance, the particles
    the exchange has to move;
  * each fired plan's answer, by the plain NumPy planner
    (``chipbench.planner_ref``) run on the same chare problem.

The program's answers are what the timed path left: the per-call scan
outputs, the assignment after each call, and the final slot-ordered
particles with their id permutation (the executed exchange).
"""
from __future__ import annotations


import numpy as np

from chipbench import particles, planner_ref

# Limits, each set between the readings of sound runs and of the
# control (PERF.md, "How correct is decided", gives the readings).
LIMITS = {
    "pos_err_cells": 0.05,     # worst torus distance from the PRK path
    "vel_err": 0.05,           # worst |v - v_PRK|, cells/step
    "plan_gap_pct": 1.0,       # max/avg of a plan over the ref plan's, %
}


def _bands(L, n):
    """Band of each cell centre for ``n`` equal bands over ``L`` cells."""
    w = np.float32(L / n)
    centres = np.arange(L, dtype=np.float32) + np.float32(0.5)
    return np.minimum(np.floor_divide(centres, w), n - 1).astype(np.int64)


class PRKPath:
    """Chare counts of the analytic PRK trajectory at any step."""

    def __init__(self, x0, y0, sysc):
        self.L, self.cx, self.cy = sysc["L"], sysc["cx"], sysc["cy"]
        self.sx = 2 * sysc["k"] + 1
        self.sy = int(round(sysc["vy0"]))
        L = self.L
        c0 = (np.asarray(x0) - 0.5).astype(np.int64)
        r0 = (np.asarray(y0) - 0.5).astype(np.int64)
        self.H0 = np.bincount(c0 * L + r0, minlength=L * L).astype(np.float64)
        self.bx, self.by = _bands(L, self.cx), _bands(L, self.cy)
        cells = np.arange(L * L)
        self.c, self.r = cells // L, cells % L

    def chare_of_cells(self, t):
        """Chare of every initial cell's particles after ``t`` steps."""
        L = self.L
        return (self.bx[(self.c + self.sx * t) % L] * self.cy
                + self.by[(self.r + self.sy * t) % L])

    def counts(self, ch):
        return np.bincount(ch, weights=self.H0,
                           minlength=self.cx * self.cy)


def chare_problem(counts, assignment, sysc):
    """The chare comm graph of one fired plan (paper §VI): east and north
    torus edges carrying the particles expected to cross in one period."""
    L, cx, cy = sysc["L"], sysc["cx"], sysc["cy"]
    bpp = sysc["bytes_per_particle"]
    n = cx * cy
    ci, cj = np.arange(n) // cy, np.arange(n) % cy
    east = ((ci + 1) % cx) * cy + cj
    north = ci * cy + (cj + 1) % cy
    src = np.concatenate([np.arange(n), np.arange(n)])
    dst = np.concatenate([east, north])
    fx = min(1.0, (2 * sysc["k"] + 1) * sysc["lb_every"] / (L / cx))
    fy = min(1.0, abs(sysc["vy0"]) * sysc["lb_every"] / (L / cy))
    eps = 1e-3 * bpp
    w = np.concatenate([counts * fx * bpp + eps, counts * fy * bpp + eps])
    return np.maximum(counts, 1e-3), src, dst, w


def initial_assignment(sysc):
    n = sysc["cx"] * sysc["cy"]
    return (np.arange(n) * sysc["num_pes"] // n).astype(np.int64)


def judge(config, traffic, seed, answers):
    """Compare the program's answers with the reference.

    Returns ``(checks, quality)``: ``checks`` maps a short name to
    ``(value, limit)`` where the value may not exceed the limit, and
    ``quality`` holds ``max_avg_load`` and ``ext_int_comm`` over the
    traffic's fixed span, computed here from the reference's loads."""
    s = config["system"]
    L, P, N = s["L"], s["num_pes"], s["n_particles"]
    bpp = np.float32(s["bytes_per_particle"])
    x0, y0, _, _, q0 = (np.asarray(a) for a in particles.generate(
        seed, n=N, L=L, k=s["k"], rho=s["rho"], vy0=s["vy0"],
        population_seed=s["population_seed"]))
    T = answers["steps"]
    checks = {}

    # -- the executed exchange: every particle once, payload intact
    perm = np.asarray(answers["perm"]).astype(np.int64)
    inside = (perm >= 0) & (perm < N)
    once = np.bincount(perm[inside], minlength=N)
    lost = int((~inside).sum()) + int((once != 1).sum())
    checks["lost_or_doubled"] = (lost, 0)
    if lost:
        return checks, {}

    def by_id(a):
        out = np.empty_like(a)
        out[perm] = a
        return out
    fx, fy, fvx, fvy, fq = (by_id(np.asarray(answers[f])) for f in
                            ("x", "y", "vx", "vy", "q"))
    checks["charge_changed"] = (int((fq != q0).sum()), 0)

    # -- push: positions and velocities against the PRK's path
    sx = 2 * s["k"] + 1
    xr = np.mod(x0.astype(np.float64) + sx * T, L)
    yr = np.mod(y0.astype(np.float64) + s["vy0"] * T, L)
    def torus(a, b):
        d = np.abs(a.astype(np.float64) - b) % L
        return float(np.minimum(d, L - d).max())
    checks["pos_err_cells"] = (max(torus(fx, xr), torus(fy, yr)),
                               LIMITS["pos_err_cells"])
    vxr = 2.0 * sx if T % 2 else 0.0
    checks["vel_err"] = (max(float(np.abs(fvx - vxr).max()),
                             float(np.abs(fvy - s["vy0"]).max())),
                         LIMITS["vel_err"])

    # -- loads, handoffs, fires, exchange volume and plans, step by step
    path = PRKPath(x0, y0, s)
    ys = answers["ys"]                   # dict of (T,) per-step outputs
    after = answers["assignment_after"]  # (calls, C) owner after each call
    per_call = answers["steps_per_call"]
    every = traffic.get("trigger") in (None, "every")
    A = initial_assignment(s)
    prev_ch = path.chare_of_cells(0)
    load_bad = hand_bad = fire_bad = mig_bad = out_range = 0
    plan_gap = 0.0
    skipped = 0
    ma_ref, ext_ref, int_ref = [], [], []
    for c in range(T // per_call):
        a_end = after[c].astype(np.int64)
        steps = range(c * per_call, (c + 1) * per_call)
        fired = [t for t in steps if ys["fired"][t] > 0]
        for t in steps:
            ch = path.chare_of_cells(t + 1)
            cnt = path.counts(ch)
            if fired and fired[0] < t <= fired[-1] and len(fired) > 1:
                skipped += 1            # owner between two fires unknown
                A_t = None
            else:
                A_t = A if (not fired or t <= fired[0]) else a_end
            if A_t is not None:
                pe = np.bincount(A_t, weights=cnt, minlength=P)
                ma = pe.max() / pe.mean()
                load_bad += int(np.float32(pe.max()) != ys["pe_max"][t])
                load_bad += int(abs(ys["max_avg"][t] - ma) > 1e-5 * ma)
                moved = ch != prev_ch
                cross = A_t[prev_ch] != A_t[ch]
                e = np.float32(path.H0[moved & cross].sum()) * bpp
                i = np.float32(path.H0[moved & ~cross].sum()) * bpp
                hand_bad += int(e != ys["ext"][t]) + int(i != ys["int"][t])
                ma_ref.append(ma)
                ext_ref.append(float(e))
                int_ref.append(float(i))
            want_fire = t > 0 and t % s["lb_every"] == 0
            if every:
                fire_bad += int(bool(ys["fired"][t] > 0) != want_fire)
            if ys["fired"][t] > 0 and len(fired) == 1:
                loads, src, dst, w = chare_problem(cnt, A, s)
                r = planner_ref.compare(a_end, A, loads, src, dst, w, P,
                               s["k_neighbours"])
                plan_gap = max(plan_gap, r["gap_pct"])
                out_range += r["out_of_range"]
                change = A != a_end
                mig = np.float32(cnt[change].sum()) * bpp
                mig_bad += int(mig != ys["migrated_bytes"][t])
            prev_ch = ch
        A = a_end
    checks["load_mismatches"] = (load_bad, 0)
    checks["handoff_mismatches"] = (hand_bad, 0)
    checks["fire_schedule_errors"] = (fire_bad, 0)
    checks["moved_bytes_mismatch"] = (mig_bad, 0)
    checks["plan_out_of_range"] = (out_range, 0)
    checks["plan_gap_pct"] = (plan_gap, LIMITS["plan_gap_pct"])
    checks["steps_unchecked"] = (skipped, 0)
    span = int(traffic["span_steps"])
    quality = {}
    if len(ma_ref) >= span and skipped == 0:
        quality["max_avg_load"] = float(np.mean(ma_ref[:span]))
        quality["ext_int_comm"] = (float(np.sum(ext_ref[:span]))
                                   / float(np.sum(int_ref[:span])))
    return checks, quality


def control_answers(cell, seed):
    """The control: this reference put in the program's place and
    computed in bfloat16, the precision below the configuration's
    float32.  The PRK push (corner charges from the column parity),
    chare loads, handoffs and, at each fire, the reference planner, over
    the traffic's fixed span.  Particles stay in id order (no
    exchange).  Returns answers shaped as the adapter's."""
    import jax
    import jax.numpy as jnp
    import ml_dtypes

    s = cell.config["system"]
    L, cx, cy, P = s["L"], s["cx"], s["cy"], s["num_pes"]
    C = cx * cy
    bf = jnp.bfloat16
    x, y, vx, vy, q = (a.astype(bf) for a in particles.generate(
        seed, n=s["n_particles"], L=L, k=s["k"], rho=s["rho"],
        vy0=s["vy0"], population_seed=s["population_seed"]))

    def chare(x, y):
        i = jnp.minimum(jnp.floor(x / bf(L / cx)), cx - 1)
        j = jnp.minimum(jnp.floor(y / bf(L / cy)), cy - 1)
        return i.astype(jnp.int32) * cy + j.astype(jnp.int32)

    @jax.jit
    def step(x, y, vx, vy, ch):
        i0, j0 = jnp.floor(x), jnp.floor(y)
        fx = jnp.zeros_like(x)
        fy = jnp.zeros_like(y)
        for di in (0, 1):
            qc = jnp.where(jnp.mod(i0 + di, 2) == 0, bf(1), bf(-1))
            for dj in (0, 1):
                dx, dy = x - (i0 + di), y - (j0 + dj)
                r2 = dx * dx + dy * dy
                f = q * qc / jnp.maximum(r2, bf(1e-12))
                r = jnp.maximum(jnp.sqrt(r2), bf(1e-6))
                fx = fx + f * dx / r
                fy = fy + f * dy / r
        xn = jnp.mod(x + vx + bf(0.5) * fx, bf(L))
        yn = jnp.mod(y + vy + bf(0.5) * fy, bf(L))
        chn = chare(xn, yn)
        trans = jnp.bincount(ch * C + chn, length=C * C).reshape(C, C)
        return xn, yn, vx + fx, vy + fy, chn, trans

    b16 = ml_dtypes.bfloat16
    bpp = b16(s["bytes_per_particle"])
    per_call = int(cell.traffic["steps_per_call"])
    steps = int(cell.traffic["span_steps"])
    A = initial_assignment(s)
    ch = chare(x, y)
    ys = {k: [] for k in ("max_avg", "pe_max", "ext", "int",
                          "migrated_bytes", "fired")}
    after = []
    for t in range(steps):
        x, y, vx, vy, ch, trans = step(x, y, vx, vy, ch)
        trans = np.asarray(trans).astype(np.int64)
        counts = trans.sum(0)
        loads = counts.astype(b16)
        pe = np.zeros(P, b16)
        np.add.at(pe, A, loads)
        ys["pe_max"].append(float(pe.max()))
        ys["max_avg"].append(float(pe.max() / pe.mean()))
        moved = ~np.eye(C, dtype=bool)
        cross = A[:, None] != A[None, :]
        ys["ext"].append(float(b16(trans[moved & cross].sum()) * bpp))
        ys["int"].append(float(b16(trans[moved & ~cross].sum()) * bpp))
        fire = t > 0 and t % s["lb_every"] == 0
        mig = 0.0
        if fire:
            w_loads, src, dst, w = chare_problem(
                loads.astype(np.float64), A, s)
            new, _, _, _ = planner_ref.plan(
                w_loads.astype(b16), A, src, dst, w.astype(b16), P,
                k=s["k_neighbours"], dt=b16)
            mig = float(b16(counts[A != new].sum()) * bpp)
            A = new.astype(np.int64)
        ys["migrated_bytes"].append(mig)
        ys["fired"].append(1.0 if fire else 0.0)
        if (t + 1) % per_call == 0:
            after.append(A.copy())
    out = {k: np.asarray(v, np.float32) for k, v in ys.items()}
    f32 = [np.asarray(a).astype(np.float32) for a in (x, y, vx, vy, q)]
    n = s["n_particles"]
    return dict(x=f32[0], y=f32[1], vx=f32[2], vy=f32[3], q=f32[4],
                perm=np.arange(n), ys=out,
                assignment_after=np.stack(after), steps=steps,
                steps_per_call=per_call)
