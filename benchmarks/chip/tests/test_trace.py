"""The trace reduction, on hand-made planes and on a small trace recorded
on a v5e chip (``data/toy.xplane.pb``: three calls of a jitted function
with ops under ``kernel/toy-a`` and ``lb-plan/stage1-toy``, each call
inside a ``bench/toy-call`` host span), and the roofline arithmetic."""
import pathlib
import types

import pytest

from chipbench import harness, layers, readers, roofline, trace

DATA = pathlib.Path(__file__).resolve().parent / "data"

HLO = """HloModule jit_step, entry_computation_layout={()->()}

%body (p: f32[8]) -> f32[8] {
  %mul.1 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(step)/while/body/kernel/pic-push/mul"}
  %fusion.2 = f32[8]{0} fusion(%mul.1), kind=kLoop, calls=%fused.2
}

%fused.2 (q: f32[8]) -> f32[8] {
  %add.3 = f32[8]{0} add(%q, %q), metadata={op_name="jit(step)/while/body/kernel/histogram/add"}
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %while.1 = f32[8]{0} while(%x), body=%body, metadata={op_name="jit(step)/while"}
  %copy.9 = f32[8]{0} copy(%while.1)
}
"""


def ev(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=float(start),
                                 duration_ns=float(dur), stats=[])


def line(name, events):
    return types.SimpleNamespace(name=name, events=events)


def plane(name, lines):
    return types.SimpleNamespace(name=name, lines=lines)


def toy_planes():
    dev = plane("/device:TPU:0", [
        line("XLA Modules", [ev("jit_step(123)", 100, 900)]),
        line("XLA Ops", [
            ev("%while.1 = f32[8] while(...)", 100, 600),
            ev("%mul.1 = f32[8] multiply(...)", 150, 200),
            ev("%fusion.2 = f32[8] fusion(...)", 400, 250),
            ev("%fusion.7 = f32[8] fusion(...)", 660, 20),   # no metadata
            ev("%copy.9 = f32[8] copy(...)", 800, 100),
        ]),
    ])
    host = plane("/host:CPU", [line("python", [
        ev("bench/window", 0, 1000),
        ev("bench/call", 50, 900),
        ev("PjitFunction(step)", 60, 30),
        ev("$array.py _value", 700, 100),
    ])])
    return [dev, host]


def test_self_time_nesting_paths_and_idle_share():
    r = trace.reduce_planes(toy_planes(), [HLO], min_gap_ns=0)
    by = {o.name: o for o in r.ops}
    assert by["while.1"].self_ns == 600 - 200 - 250 - 20
    assert by["mul.1"].path.endswith("kernel/pic-push/mul")
    # a fusion without metadata takes its fused computation's path
    assert by["fusion.2"].path.endswith("kernel/histogram/add")
    # an op the trace nests in the while with no metadata takes the while's
    assert by["fusion.7"].path == "jit(step)/while"
    assert by["copy.9"].path == ""
    assert r.self_s(lambda p: layers.PUSH in p) == pytest.approx(200e-9)
    assert r.self_s(lambda p: layers.HISTOGRAM in p) == pytest.approx(250e-9)
    assert r.window_s == pytest.approx(1000e-9)
    assert r.busy_s == pytest.approx(700e-9)      # [100,700) + [800,900)
    # idle [0,100) while the call dispatched, [700,800) while the host
    # read a value, [900,1000) after the call returned
    assert dict(r.idle_gaps()) == pytest.approx({
        "bench/call": 100e-9,
        "bench/call > $array.py _value": 100e-9,
        "bench/window": 100e-9})


def test_gaps_shorter_than_the_floor_are_not_listed():
    r = trace.reduce_planes(toy_planes(), [HLO], min_gap_ns=150)
    assert r.gaps == []


def test_missing_window_span_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_planes(toy_planes(), [HLO], window_span="bench/none")


def test_recorded_chip_trace():
    from jax.profiler import ProfileData

    planes = ProfileData.from_file(str(DATA / "toy.xplane.pb")).planes
    r = trace.reduce_planes(planes, [(DATA / "toy_hlo.txt").read_text()],
                            window_span="bench/toy-call")
    assert {o.device for o in r.ops} == {"/device:TPU:0"}
    assert {o.module for o in r.ops} == {"jit_toy"}
    top_path, top_s = r.device_ops(1)[0]
    assert "jit(toy)/gather" in top_path and top_s > 0.014
    assert r.self_s(lambda p: "kernel/toy-a" in p) > 0
    assert r.self_s(lambda p: "lb-plan/stage1-toy" in p) > 0
    assert 0 < r.busy_s < r.window_s
    assert sum(s for _, s in r.idle_gaps()) <= r.window_s - r.busy_s + 1e-9


def test_window_from_the_host_clock():
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(str(DATA / "toy.xplane.pb")).planes)
    by_span = trace.reduce_planes(planes, [], window_span="bench/toy-call")
    start = 1792275095238286825          # the trace's profile_start_time
    w = (start + int(by_span.window[0]), start + int(by_span.window[1]))
    by_clock = trace.reduce_planes(planes, [], window_ns=w,
                                   window_span="absent")
    assert by_clock.window == pytest.approx(by_span.window)
    assert by_clock.busy_s == pytest.approx(by_span.busy_s)


def test_push_roofline_arithmetic():
    n = 1 << 23
    flops, nbytes = roofline.push_work(n)
    assert (flops, nbytes) == (96.0 * n, 52.0 * n)
    t, bound = roofline.least_time(flops, nbytes, "TPU v5 lite")
    assert bound == "memory"
    assert t == pytest.approx(52.0 * n / 819e9)
    t, bound = roofline.least_time(197e12, 1.0, "TPU v5 lite")
    assert (t, bound) == (pytest.approx(1.0), "compute")
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def test_push_roofline_reader():
    cell = harness.Cell("c", 1, {"system": {"n_particles": 1 << 20}},
                        {}, [], [])
    r = trace.reduce_planes(toy_planes(), [HLO])
    run = harness.Run(cell, 0, "steps", units=2, trace=r,
                      device_kind="TPU v5 lite")
    reader = harness.load_module(harness.BENCH_DIR / "metrics"
                                 / "push_roofline.py")
    push_s = 200e-9 / 2
    want = 100.0 * (52.0 * (1 << 20) / 819e9) / push_s
    assert reader.read(run) == pytest.approx(want)
    run.trace = None
    assert reader.read(run) is None


BRANCH = "jit(run_chunk)/while/body/closed_call/cond/branch_1_fun/gather"
PLAN = ("jit(run_chunk)/while/body/closed_call/cond/branch_1_fun/"
        "jit(plan_fn)/lb-plan/stage2-diffusion/gather")
PUSHP = "jit(run_chunk)/while/body/kernel/pic-push/pallas_call"
BODY = "jit(run_chunk)/while/body/closed_call/gather"


def steps_run(ops, fires):
    """A traced PIC run whose device ops are (path, start, self ns)."""
    dev = [trace.DeviceOp("/device:TPU:0", "jit_run_chunk", f"op.{k}", p,
                          s, d, d) for k, (p, s, d) in enumerate(ops)]
    r = trace.Reduction(dev, (0.0, 1e3), {"/device:TPU:0": 1e3}, [])
    cell = harness.Cell("c", 1, {"system": {}}, {}, [], [])
    return harness.Run(cell, 0, "steps", units=3, trace=r,
                       counters={"window_fires": fires})


# three steps: push and body each step, a plan and an exchange in the first
FIRED = [(PUSHP, 0, 10), (BODY, 10, 5), (PLAN, 15, 2), (BRANCH, 17, 40),
         (BRANCH, 57, 3), (PUSHP, 60, 10), (BODY, 70, 5), (PUSHP, 80, 10),
         (BODY, 90, 5)]


@pytest.mark.parametrize("ops,fires,stretches,attributed", [
    (FIRED, 1, 1, True),
    # a cond branch outside the planner in an unfired step as well
    (FIRED + [(BRANCH, 75, 1)], 1, 2, False),
    # the exchange run every step by a select: no branch time at all
    ([o for o in FIRED if o[0] != BRANCH], 1, 0, False),
], ids=["one-per-fire", "branch-in-unfired-step", "no-branch"])
def test_exchange_is_told_apart_only_one_stretch_per_fire(
        ops, fires, stretches, attributed, capsys):
    run = steps_run(ops, fires)
    assert readers.exchange_stretches(run.trace) == {
        "/device:TPU:0": stretches}
    ex = harness.load_module(harness.BENCH_DIR / "metrics"
                             / "exchange_ms.py").read(run)
    body = harness.load_module(harness.BENCH_DIR / "metrics"
                               / "step_body_ms.py").read(run)
    if attributed:
        assert ex == pytest.approx(1e3 * 43e-9 / fires)
        assert body == pytest.approx(1e3 * 15e-9 / 3)
    else:
        assert ex is None and body is None
        assert "exchange_unattributed" in capsys.readouterr().err
