"""BENCHMARK.json and the files it names: everything loads by name and
keeps to the benchmark's contract."""
import json
import re

import pytest

from chipbench import harness

SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["benchmarks/chip"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) < 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads_by_name(cell):
    c = harness.Cell.load(cell, SPEC)
    assert (harness.BENCH_DIR / "adapters"
            / f"{c.config['adapter']}.py").is_file()
    ref = harness.load_module(harness.BENCH_DIR / "references"
                              / f"{c.config['reference']}.py")
    # each reference judges the program and makes its own control
    assert callable(ref.judge) and callable(ref.control_answers)
    assert {"loop", "strategy", "trace_calls"} <= set(c.traffic)
    assert c.chips in (1, 4)


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file(cfg):
    data = json.loads((harness.ROOT / cfg["file"]).read_text())
    assert cfg["file"].startswith("benchmarks/chip/configs/")
    assert data["name"] == cfg["name"] and data["source"] == cfg["source"]
    assert data["reduced"] == cfg["reduced"]
    for key in cfg["reduced"]:
        assert key in data["system"]
        assert not re.search(r"(_dim|_rank|hidden|width|size)$", key)
    assert any(w["config"] == cfg["name"] for w in SPEC["workloads"])


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metric_reader_loads_by_name(m):
    mod = harness.load_module(harness.BENCH_DIR / "metrics"
                              / f"{m['name']}.py")
    assert callable(mod.read)


def test_names_and_units():
    names = ([c["name"] for c in SPEC["configs"]] + CELLS
             + [m["name"] for m in METRICS]
             + [w["traffic"] for w in SPEC["workloads"]]
             + [k for c in SPEC["configs"] for k in c["reduced"]])
    for n in names:
        assert NAME.match(n), n
    for group in (SPEC["configs"], SPEC["workloads"], METRICS):
        assert len({x["name"] for x in group}) == len(group)
    for m in METRICS:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_end_to_end_bounds_and_sources():
    names = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in names
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert "bound" not in m


def _reports(metric, cell):
    return cell in metric.get("workloads", CELLS)


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    for cell in CELLS:
        e2e = [m["name"] for m in SPEC["end_to_end"] if _reports(m, cell)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(_reports(m, cell) for m in SPEC["per_layer"])


def test_moves_is_reported_where_the_layer_metric_is():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    layers = {}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        assert "workloads" in m
        for cell in m["workloads"]:
            assert cell in CELLS
            assert _reports(e2e[m["moves"]], cell), (m["name"], cell)
        layers.setdefault(m["layer"], set()).add(m["layer"])
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200


def test_at_most_half_the_cells_take_four_chips():
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(CELLS) // 2)


def test_command_stays_inside_paths():
    cmd = SPEC["command"]
    assert len(cmd) <= 32
    for word in cmd:
        assert not word.startswith("/") and ".." not in word
        if "/" in word:
            assert word.startswith("benchmarks/chip/")


def test_check_budget_fits():
    n = 24
    runs = 2 + 14 * n
    assert (runs * (SPEC["run_seconds"] + 60) + n * 2 * 90 + 1200
            <= 43200)
