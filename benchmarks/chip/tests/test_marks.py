"""The program's own marks and the readers of ``metrics/`` built on them
(``chipbench.marks``): the device scopes below the named layers, read
from the trace's op paths, and the host counters of the program's
registry.  The program's ``lb/`` host spans leave the reduction's
numbers and labels as they were."""
import time

import pytest

from chipbench import harness, trace

from test_trace import HLO, ev, line, plane, steps_run, toy_planes

SCORE = ("jit(plan_fn)/lb-plan/stage3-objects/jit(select_objects)/"
         "score/add")
TAKE = ("jit(plan_fn)/lb-plan/stage3-objects/jit(select_objects)/"
        "take/sort")

PLAN_HLO = f"""HloModule jit_plan_fn, entry_computation_layout={{()->()}}

ENTRY %main (x: f32[8]) -> f32[8] {{
  %add.1 = f32[8]{{0}} add(%x, %x), metadata={{op_name="{SCORE}"}}
  %sort.2 = f32[8]{{0}} sort(%add.1), metadata={{op_name="{TAKE}"}}
}}
"""


def plan_planes():
    """Two rebalance requests, each one device op inside its call."""
    dev = plane("/device:TPU:0", [
        line("XLA Modules", [ev("jit_plan_fn(1)", 150, 200),
                             ev("jit_plan_fn(1)", 500, 200)]),
        line("XLA Ops", [ev("%add.1 = f32[8] add(...)", 150, 200),
                         ev("%sort.2 = f32[8] sort(...)", 500, 200)]),
    ])
    host = plane("/host:CPU", [line("python", [
        ev("bench/window", 0, 1000),
        ev("bench/call", 50, 410),
        ev("bench/call", 455, 490),
    ])])
    return [dev, host]


def requests_run(planes, hlo=PLAN_HLO, units=2):
    cell = harness.Cell("c", 1, {"system": {}}, {}, [], [])
    r = trace.reduce_planes(planes, [hlo], min_gap_ns=0)
    return harness.Run(cell, 0, "requests", units=units, trace=r)


def reader(name):
    return harness.load_module(harness.BENCH_DIR / "metrics"
                               / f"{name}.py")


def test_scope_readers_of_object_selection():
    run = requests_run(plan_planes())
    assert reader("stage3_score_ms").read(run) == pytest.approx(1e-4)
    assert reader("stage3_take_ms").read(run) == pytest.approx(1e-4)
    assert (reader("stage3_score_ms").read(run)
            + reader("stage3_take_ms").read(run)
            == pytest.approx(reader("stage3_ms").read(run)))


def test_scope_readers_are_absent_without_their_scopes():
    bare = PLAN_HLO.replace("score/", "").replace("take/", "")
    run = requests_run(plan_planes(), hlo=bare)
    assert reader("stage3_ms").read(run) is not None
    assert reader("stage3_score_ms").read(run) is None
    assert reader("stage3_take_ms").read(run) is None
    run.trace = None
    assert reader("stage3_score_ms").read(run) is None
    assert reader("stage3_take_ms").read(run) is None


HANDOFF = "jit(run_chunk)/while/body/closed_call/replay/handoff/gather"
OWNERS = "jit(run_chunk)/while/body/closed_call/replay/owners/jit(_take)"
MIGRATE = ("jit(run_chunk)/while/body/closed_call/cond/branch_1_fun/"
           "exchange/migrate/jit(_take)/gather")
PUSHP = "jit(run_chunk)/while/body/kernel/pic-push/pallas_call"


# three steps: push, handoff and owner maps each step, the exchange in the
# first
STEPS = [(PUSHP, 0, 10), (HANDOFF, 10, 4), (OWNERS, 14, 2),
         (MIGRATE, 16, 40), (PUSHP, 60, 10), (HANDOFF, 70, 4),
         (OWNERS, 74, 2), (PUSHP, 80, 10), (HANDOFF, 90, 4),
         (OWNERS, 94, 2)]


def test_replay_scope_readers():
    run = steps_run(STEPS, fires=1)
    assert reader("handoff_ms").read(run) == pytest.approx(1e3 * 12e-9 / 3)
    assert reader("owner_gather_ms").read(run) == pytest.approx(
        1e3 * 6e-9 / 3)
    assert reader("exchange_migrate_ms").read(run) == pytest.approx(
        1e3 * 40e-9)
    # the scoped exchange is the whole of what exchange_ms reads, and the
    # two replay scopes the whole of the step body
    assert reader("exchange_migrate_ms").read(run) == pytest.approx(
        reader("exchange_ms").read(run))
    assert (reader("handoff_ms").read(run)
            + reader("owner_gather_ms").read(run)
            == pytest.approx(reader("step_body_ms").read(run)))


@pytest.mark.parametrize("name", ["handoff_ms", "owner_gather_ms",
                                  "exchange_migrate_ms"])
def test_replay_scope_readers_are_absent_without_their_scopes(name):
    bare = [(PUSHP, 0, 10), (PUSHP, 60, 10)]
    assert reader(name).read(steps_run(bare, fires=1)) is None
    run = steps_run(STEPS, fires=1)
    run.trace = None
    assert reader(name).read(run) is None


def test_exchange_migrate_is_absent_without_fires():
    assert reader("exchange_migrate_ms").read(
        steps_run(STEPS, fires=0)) is None


@pytest.mark.parametrize("name,value", [
    ("plan_host_reads", 6.0),
    ("plan_stats_ms", 2.5),            # 10 ms of stats reads in 4 requests
])
def test_host_path_readers_from_the_program_counters(monkeypatch, name,
                                                     value):
    from repro.obs import metrics

    run = requests_run(plan_planes())
    snap = {"lb.plan.requests": 4.0, "lb.plan.host_reads": 24.0,
            "lb.plan.stats_ns": 10e6}
    monkeypatch.setattr(metrics, "snapshot", lambda: dict(snap))
    assert reader(name).read(run) == pytest.approx(value)
    # a program without the counters, and a run of steps, give nothing
    snap.clear()
    assert reader(name).read(run) is None
    snap.update({"lb.plan.requests": 4.0, "lb.plan.host_reads": 24.0,
                 "lb.plan.stats_ns": 10e6})
    run.unit = "steps"
    assert reader(name).read(run) is None


def _summary(r):
    return (r.window, r.busy_ns, len(r.ops), r.idle_gaps(10),
            r.device_ops(10), r.gaps)


def test_program_spans_leave_the_toy_reduction_as_it_was():
    plain = trace.reduce_planes(toy_planes(), [HLO], min_gap_ns=0)
    marked = toy_planes()
    host = marked[1].lines[0]
    # the request's spans enclose the Python frames they run, as the
    # program opens them: every gap keeps its label
    host.events = host.events + [ev("lb/plan", 55, 880),
                                 ev("lb/plan/dispatch", 55, 40),
                                 ev("lb/plan/stats", 690, 120)]
    r = trace.reduce_planes(marked, [HLO], min_gap_ns=0)
    assert _summary(r) == _summary(plain)
    assert dict(r.idle_gaps())["bench/call > $array.py _value"] == (
        pytest.approx(100e-9))


def test_traced_small_rebalance_run_reads_the_host_path(tmp_path,
                                                        monkeypatch):
    """A traced run of the rebalance cell on the CPU at a small size: the
    program's counters reach their readers (a CPU trace has no device
    plane, so the device readings stay out)."""
    monkeypatch.setattr(harness, "CACHE_DIR", tmp_path / "jax_cache")
    cell = harness.Cell.load("stencil-wave-8x128.rebalance",
                             config_overrides={"grid": 32,
                                               "num_nodes": 16},
                             traffic_overrides={"cycle_requests": 6,
                                                "check_requests": 6,
                                                "trace_calls": 3})
    line = harness.run_cell(cell, seed=3000000000123, seconds=0.01,
                            trace=True, t_start=time.perf_counter(),
                            require_tpu=False)
    m = line["metrics"]
    assert m["plan_host_reads"]["value"] == 6.0
    assert m["plan_stats_ms"]["value"] > 0
    assert "stage3_score_ms" not in m
