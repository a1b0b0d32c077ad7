"""Plain reference for the PIC PRK replay sharded over a four-chip host
with one chip's PEs drained and restored on a fixed cycle, and the
comparison that decides ``correct``.

It imports nothing of the program.  It builds on the one-chip cell's
reference (``references/pic_prk.py``: the PRK's analytic path, which
gives every chare's particle count at every step, and the chare comm
graph of a fired plan) and on the plain planner
(``chipbench/planner_ref.py``).  What the drain adds, written out here:

  * the shard health at every step, from the traffic's fault cycle
    (:func:`fault_events`, :func:`shard_alive`);
  * the plan of a degraded mesh (:func:`plan_alive`): a dead PE's chares
    are first re-homed onto the alive PE they exchange most bytes with
    (the least-loaded alive PE where they exchange none with any), dead
    PEs are then taken out of every neighbourhood, and the three stages
    run on what is left.

The program's answers are what the timed path left: the per-step
outputs of every call (loads, handoffs, fires, forced fires, rejected
plans, the assignment and each shard's live count after the step) and
the final live particles of the four shards with their ids.
"""
from __future__ import annotations

import importlib.util
import pathlib

import numpy as np

from chipbench import particles, planner_ref


def _one_chip():
    path = pathlib.Path(__file__).with_name("pic_prk.py")
    spec = importlib.util.spec_from_file_location("chipbench_ref_pic_prk",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_one = _one_chip()

# Limits: the one-chip cell's (positions and velocities on the PRK's
# path, a plan's max/avg within 1 % of the float64 plan's); every other
# check is exact.  At this cell's 2^23 particles the program reads 0 on
# all three and the bfloat16 control 499.5 cells, 14529 cells/step and
# 340 % (PERF.md, section 4).
LIMITS = dict(_one.LIMITS)


def fault_events(cycle, horizon):
    """``(step, shard, kind)`` of the die/recover cycle below ``horizon``:
    in cycle ``j`` shard ``j mod rotate`` dies at ``j*period + die`` and
    recovers at ``j*period + recover``."""
    period, rotate = int(cycle["period"]), int(cycle["rotate"])
    out = []
    for j in range(horizon // period + 1):
        for off, kind in ((cycle["die"], "die"),
                          (cycle["recover"], "recover")):
            step = j * period + int(off)
            if step < horizon:
                out.append((step, j % rotate, kind))
    return tuple(out)


def shard_alive(events, t, D):
    """(D,) bool: a shard is alive at step ``t`` unless its latest ``die``
    at or before ``t`` is later than its latest ``recover`` there."""
    last = {"die": np.full(D, -1), "recover": np.full(D, -1)}
    for step, shard, kind in events:
        if step <= t:
            last[kind][shard] = max(last[kind][shard], step)
    return last["die"] <= last["recover"]


def rehome(loads, A, src, dst, w, P, alive, dt):
    """Owners after re-homing the chares of dead PEs: each goes to the
    alive PE it sends and receives the most bytes with under ``A``
    (ties to the lower PE), or, where that is none, to the alive PE with
    the least load under ``A``."""
    N = A.shape[0]
    byts = np.zeros((N, P), dt)
    np.add.at(byts, (src, A[dst]), w.astype(dt))
    np.add.at(byts, (dst, A[src]), w.astype(dt))
    score = np.where(alive[None, :], byts, dt(-1))
    best = np.argmax(score, axis=1)
    has_comm = score.max(axis=1) > 0
    pe_load = np.zeros(P, dt)
    np.add.at(pe_load, A, loads.astype(dt))
    fallback = int(np.argmin(np.where(alive, pe_load, np.inf)))
    target = np.where(has_comm, best, fallback)
    dead = ~alive[A]
    return np.where(dead & alive.any(), target, A)


def plan_alive(loads, A, src, dst, w, P, alive, *, k, dt=np.float64):
    """``planner_ref.plan`` on a degraded mesh.  Departures from it, each
    the drain's: the owners are re-homed first (:func:`rehome`) and the
    rest runs on them; the stage-1 preference of a pair with a dead PE
    is 0, so a dead PE has no neighbour, takes no flow and no chare.
    Returns the new owners."""
    a = rehome(loads, A, src, dst, w, P, alive, dt)
    comm = planner_ref.node_comm(a, src, dst, w, P, dt)
    eps = dt(1e-6) * (dt(1.0) + comm.max())
    pref = np.where(np.eye(P, dtype=bool), dt(0), comm + eps)
    pref = np.where(alive[:, None] & alive[None, :], pref, dt(0))
    nbr, mask, _ = planner_ref.neighbours(pref, k)
    nloads = np.zeros(P, dt)
    np.add.at(nloads, a, loads.astype(dt))
    flows, _ = planner_ref.diffuse(nloads, nbr, mask, dt)
    return planner_ref.select(loads, a, src, dst, w, nbr, mask, flows, dt)


def alive_max_avg(loads, A, P, alive):
    pe = np.bincount(A, weights=loads, minlength=P)[alive]
    return pe.max() / pe.mean()


def judge(config, traffic, seed, answers):
    """Compare the program's answers with the reference.

    Returns ``(checks, quality)`` as ``references/pic_prk.judge`` does;
    ``max_avg_load`` is taken over the alive PEs."""
    s = config["system"]
    L, P, N = s["L"], s["num_pes"], s["n_particles"]
    D = int(s["replay_shards"])
    rpd = P // D
    bpp = np.float32(s["bytes_per_particle"])
    x0, y0, _, _, q0 = (np.asarray(a) for a in particles.generate(
        seed, n=N, L=L, k=s["k"], rho=s["rho"], vy0=s["vy0"],
        population_seed=s["population_seed"]))
    T = int(answers["steps"])
    checks = {}

    # -- the executed exchanges: every particle once across the shards'
    # live prefixes, payload intact
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

    # -- every step: loads and handoffs under the owner before the step,
    # fires, forced fires, moved bytes, plans, dead owners, shard counts
    ys = answers["ys"]
    owners = np.asarray(ys["assignment"]).astype(np.int64)   # (T, C)
    counts = np.asarray(ys["count"]).astype(np.int64)        # (T, D)
    events = fault_events(traffic["fault_cycle"], T)
    path = _one.PRKPath(x0, y0, s)
    A = _one.initial_assignment(s)
    prev_ch = path.chare_of_cells(0)
    alive_sh_prev = np.ones(D, bool)
    bad = dict(load=0, hand=0, fire=0, forced=0, mig=0, quiet=0, rng=0,
               dead=0)
    plan_gap = 0.0
    ma_ref, ext_ref, int_ref = [], [], []
    for t in range(T):
        A_new = owners[t]
        alive_sh = shard_alive(events, t, D)
        alive = np.repeat(alive_sh, rpd)
        ch = path.chare_of_cells(t + 1)
        cnt = path.counts(ch)
        pe = np.bincount(A, weights=cnt, minlength=P)
        ma = pe.max() / pe.mean()
        bad["load"] += int(np.float32(pe.max()) != ys["pe_max"][t])
        bad["load"] += int(abs(ys["max_avg"][t] - ma) > 1e-5 * ma)
        moved = ch != prev_ch
        cross = A[prev_ch] != A[ch]
        e = np.float32(path.H0[moved & cross].sum()) * bpp
        i = np.float32(path.H0[moved & ~cross].sum()) * bpp
        bad["hand"] += int(e != ys["ext"][t]) + int(i != ys["int"][t])
        ma_ref.append(alive_max_avg(cnt, A, P, alive))
        ext_ref.append(float(e))
        int_ref.append(float(i))

        transition = bool((alive_sh != alive_sh_prev).any())
        stranded = bool((~alive[A]).any())
        want_forced = transition or stranded
        want_fire = (t > 0 and t % s["lb_every"] == 0) or want_forced
        fired = ys["fired"][t] > 0
        bad["fire"] += int(fired != want_fire)
        bad["forced"] += int((ys["forced"][t] > 0) != want_forced)
        mig = np.float32(cnt[A != A_new].sum()) * bpp
        bad["mig"] += int(mig != ys["migrated_bytes"][t])
        if fired:
            loads, src, dst, w = _one.chare_problem(cnt, A, s)
            ref = plan_alive(loads, A, src, dst, w, P, alive,
                             k=s["k_neighbours"])
            ok = (A_new >= 0) & (A_new < P)
            bad["rng"] += int((~ok).sum())
            safe = np.clip(A_new, 0, P - 1)
            ma_ref_plan = alive_max_avg(loads, ref, P, alive)
            gap = 100.0 * (alive_max_avg(loads, safe, P, alive)
                           - ma_ref_plan) / ma_ref_plan
            # a plan that leaves the alive PEs no load reads NaN: a fault
            plan_gap = max(plan_gap, gap if np.isfinite(gap) else np.inf)
        else:
            bad["quiet"] += int((A_new != A).any())
        bad["dead"] += int((~alive[np.clip(A_new, 0, P - 1)]).sum())
        prev_ch = ch
        alive_sh_prev = alive_sh
        A = A_new
    checks["load_mismatches"] = (bad["load"], 0)
    checks["handoff_mismatches"] = (bad["hand"], 0)
    checks["fire_schedule_errors"] = (bad["fire"], 0)
    checks["forced_fire_errors"] = (bad["forced"], 0)
    checks["moved_bytes_mismatch"] = (bad["mig"], 0)
    checks["owner_change_without_fire"] = (bad["quiet"], 0)
    checks["plan_out_of_range"] = (bad["rng"], 0)
    checks["plan_gap_pct"] = (plan_gap, LIMITS["plan_gap_pct"])
    checks["dead_owner_chares"] = (bad["dead"], 0)
    checks["plans_rejected"] = (int(np.asarray(ys["rejected"]).sum()), 0)

    # -- shards: every step conserves the particles within the slabs, and
    # a drained shard holds none from its forced fire until the first
    # fire after it recovers
    capacity = int(s["replay_capacity"])
    checks["count_not_conserved"] = (int((counts.sum(1) != N).sum()), 0)
    checks["slab_overflow"] = (int((counts > capacity).sum()), 0)
    fired_steps = np.flatnonzero(np.asarray(ys["fired"]) > 0)
    drained = 0
    for step, shard, kind in events:
        if kind != "die":
            continue
        back = min([r for r, d, k in events
                    if k == "recover" and d == shard and r > step],
                   default=T)
        after = fired_steps[fired_steps >= back]
        end = int(after[0]) if after.size else T
        drained += int(counts[step:end, shard].sum())
    checks["drained_shard_particles"] = (drained, 0)

    span = int(traffic["span_steps"])
    quality = {}
    if T >= span:
        quality["max_avg_load"] = float(np.mean(ma_ref[:span]))
        quality["ext_int_comm"] = (float(np.sum(ext_ref[:span]))
                                   / float(np.sum(int_ref[:span])))
    return checks, quality


def control_answers(cell, seed):
    """The control: this reference put in the program's place and
    computed in bfloat16, the precision below the configuration's
    float32.  The PRK push (corner charges from the column parity),
    chare loads, handoffs and, at each fire, the degraded-mesh planner,
    over the traffic's fixed span.  Particles stay in id order (no
    exchange); a shard's count after a fire is the particles its PEs
    own.  Returns answers shaped as the adapter's."""
    import jax
    import jax.numpy as jnp
    import ml_dtypes

    s = cell.config["system"]
    L, cx, cy, P = s["L"], s["cx"], s["cy"], s["num_pes"]
    D = int(s["replay_shards"])
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
    events = fault_events(cell.traffic["fault_cycle"], steps)
    A = _one.initial_assignment(s)
    ch = chare(x, y)
    n = s["n_particles"]
    shard_count = np.full(D, n // D, np.int64)
    ys = {k: [] for k in ("max_avg", "pe_max", "ext", "int",
                          "migrated_bytes", "fired", "forced", "rejected",
                          "assignment", "count")}
    prev_alive = np.ones(D, bool)
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
        alive_sh = shard_alive(events, t, D)
        alive = np.repeat(alive_sh, P // D)
        forced = bool((alive_sh != prev_alive).any() or (~alive[A]).any())
        fire = (t > 0 and t % s["lb_every"] == 0) or forced
        mig = 0.0
        if fire:
            w_loads, src, dst, w = _one.chare_problem(
                loads.astype(np.float64), A, s)
            new = plan_alive(w_loads.astype(b16), A, src, dst,
                             w.astype(b16), P, alive, k=s["k_neighbours"],
                             dt=b16)
            mig = float(b16(counts[A != new].sum()) * bpp)
            A = new.astype(np.int64)
            shard_count = np.bincount(A // (P // D), weights=counts,
                                      minlength=D).astype(np.int64)
        ys["migrated_bytes"].append(mig)
        ys["fired"].append(1.0 if fire else 0.0)
        ys["forced"].append(1.0 if forced else 0.0)
        ys["rejected"].append(0.0)
        ys["assignment"].append(A.copy())
        ys["count"].append(shard_count.copy())
        prev_alive = alive_sh
    out = {k: (np.stack(v) if k in ("assignment", "count")
               else np.asarray(v, np.float32)) for k, v in ys.items()}
    f32 = [np.asarray(a).astype(np.float32) for a in (x, y, vx, vy, q)]
    return dict(x=f32[0], y=f32[1], vx=f32[2], vy=f32[3], q=f32[4],
                perm=np.arange(n), ys=out, steps=steps,
                steps_per_call=per_call)
