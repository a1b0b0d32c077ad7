"""Readers of the cell that shards one replay over four chips
(``chipbench.chips``): device ms under each scope of the sharded step,
the mean over the chips, per step or per fire by the program's
``lb.replay.*`` counters."""
import pytest

from chipbench import harness, trace

PRE = "jit(run_chunk)/while/body/closed_call/"
BRANCH = PRE + "cond/branch_1_fun/"
OPS = {
    "push": PRE + "kernel/pic-push/pallas_call",
    "hist": PRE + "kernel/histogram/scatter-add",
    "handoff": PRE + "replay/handoff/gather",
    "psum": PRE + "replay/psum/all-reduce",
    "health": PRE + "resilience/health/reduce",
    "guard": PRE + "resilience/guard/compare",
    "owners": PRE + "replay/owners/jit(_take)/gather",
    "body": PRE + "add",
    "rehome": BRANCH + "resilience/rehome/argmax",
    "plan": BRANCH + "lb-plan/stage2-diffusion/gather",
    "ring": BRANCH + "exchange/ring/collective-permute",
}
# own ns of each op in a step; the branch ops run in fired steps only
NS = dict(push=50, hist=8, handoff=12, psum=3, health=2, guard=1,
          owners=20, body=4, rehome=5, plan=7, ring=300)
BRANCH_OPS = ("rehome", "plan", "ring")
STEPS, FIRES, CHIPS = 10, 2, 4


def run_of(counters, *, skip=()):
    ops, k = [], 0
    for dev in range(CHIPS):
        t = 0
        for step in range(STEPS):
            for name, path in OPS.items():
                if name in skip or (name in BRANCH_OPS and step >= FIRES):
                    continue
                # chip d takes (1 + d/10) times as long, so a mean over the
                # chips differs from any one chip's time
                ns = NS[name] * (1 + dev / 10)
                ops.append(trace.DeviceOp(f"/device:TPU:{dev}",
                                          "jit_run_chunk", f"op.{k}", path,
                                          t, ns, ns))
                t += ns
                k += 1
    r = trace.Reduction(ops, (0.0, 1e5),
                        {f"/device:TPU:{d}": 1e5 for d in range(CHIPS)}, [])
    cell = harness.Cell("c", CHIPS, {"system": {"replay_capacity": 1 << 23}},
                        {}, [], [])
    return harness.Run(cell, 0, "steps", units=STEPS, trace=r,
                       counters=dict(counters), device_kind="TPU v5 lite")


COUNTERS = {"lb.replay.steps": STEPS, "lb.replay.fires": FIRES}
MEAN = sum(1 + d / 10 for d in range(CHIPS)) / CHIPS   # chip time factor


def reader(name):
    return harness.load_module(harness.BENCH_DIR / "metrics"
                               / f"{name}.py")


def per_step_ms(*names):
    return 1e-6 * MEAN * sum(NS[n] for n in names)


@pytest.mark.parametrize("metric,want", [
    ("push_ms.4chip", per_step_ms("push")),
    ("histogram_ms.4chip", per_step_ms("hist")),
    ("handoff_ms.4chip", per_step_ms("handoff")),
    ("owner_gather_ms.4chip", per_step_ms("owners")),
    ("psum_ms", per_step_ms("psum")),
    ("resilience_ms", per_step_ms("health", "guard")
     + per_step_ms("rehome") * FIRES / STEPS),
    ("step_body_ms.4chip", per_step_ms("handoff", "owners", "body")),
    ("ring_exchange_ms", per_step_ms("ring")),
])
def test_per_chip_readers(metric, want):
    assert reader(metric).read(run_of(COUNTERS)) == pytest.approx(want)


def test_per_chip_readers_cover_the_step():
    # every op of the window lies in exactly one metric's share, once the
    # planner (no metric in this cell) is set aside
    run = run_of(COUNTERS)
    total = sum(o.self_ns for o in run.trace.ops) * 1e-6 / CHIPS / STEPS
    parts = sum(reader(m).read(run) for m in (
        "push_ms.4chip", "histogram_ms.4chip", "psum_ms", "resilience_ms",
        "step_body_ms.4chip"))
    parts += reader("ring_exchange_ms").read(run) * FIRES / STEPS
    parts += per_step_ms("plan") * FIRES / STEPS
    assert parts == pytest.approx(total)


def test_push_roofline_reads_the_slab_per_chip():
    from chipbench import roofline

    run = run_of(COUNTERS)
    least, _ = roofline.least_time(*roofline.push_work(1 << 23),
                                   "TPU v5 lite")
    assert reader("push_roofline.4chip").read(run) == pytest.approx(
        100.0 * least / (per_step_ms("push") * 1e-3))


@pytest.mark.parametrize("metric", [
    "push_ms.4chip", "push_roofline.4chip", "histogram_ms.4chip",
    "handoff_ms.4chip", "owner_gather_ms.4chip", "psum_ms",
    "resilience_ms", "step_body_ms.4chip", "ring_exchange_ms"])
def test_per_chip_readers_are_absent_without_counters_or_trace(metric):
    # a program without the lb.replay counters (the parent of the
    # resumable entry), and an untraced run, give nothing
    assert reader(metric).read(run_of({})) is None
    run = run_of(COUNTERS)
    run.trace = None
    assert reader(metric).read(run) is None


@pytest.mark.parametrize("metric,scope", [
    ("psum_ms", "psum"), ("ring_exchange_ms", "ring"),
    ("handoff_ms.4chip", "handoff"), ("owner_gather_ms.4chip", "owners")])
def test_scope_readers_are_absent_without_their_scope(metric, scope):
    assert reader(metric).read(run_of(COUNTERS, skip=(scope,))) is None


def test_step_body_is_absent_without_the_replay_module():
    run = run_of(COUNTERS)
    run.trace = trace.Reduction([], (0.0, 1e5), {}, [])
    assert reader("step_body_ms.4chip").read(run) is None
