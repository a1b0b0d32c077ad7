"""Run one cell of the on-chip benchmark once and print its result line.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything a cell is made of is found by name from ``BENCHMARK.json`` at
the checkout's root: the configuration ``configs/<config>.json`` (which
names its adapter ``adapters/<adapter>.py`` and its plain reference
``references/<reference>.py``), the traffic ``traffic/<traffic>.json``
and one reader ``metrics/<metric>.py`` per metric.  The harness is the
one generator that drives them: a closed loop of calls into the
adapter, timed on the host clock.

A run is: set-up (import, device check, data from the seed, compile and
warm-up of every shape the window uses), the measured window of
``--seconds`` (or, with ``--trace 1``, a traced window of the traffic's
``trace_calls`` calls), the untimed rest of the traffic's fixed quality
span, the peak device memory, and then the reference's comparison.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]     # benchmarks/chip
ROOT = BENCH_DIR.parents[1]                                  # the checkout
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(SystemExit):
    """No accelerator, or fewer chips than the cell asks for."""


def load_json(path: pathlib.Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @classmethod
    def load(cls, name: str, spec: Optional[Dict[str, Any]] = None,
             config_overrides=None, traffic_overrides=None) -> "Cell":
        spec = spec or load_json(ROOT / "BENCHMARK.json")
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; "
                             f"known: {sorted(cells)}")
        w = cells[name]
        cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
        config = load_json(ROOT / cfg_entry["file"])
        traffic = load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json")
        if config_overrides:
            config = {**config, "system": {**config["system"],
                                           **config_overrides}}
        if traffic_overrides:
            traffic = {**traffic, **traffic_overrides}

        def mine(m):
            return name in m.get("workloads", [name])

        return cls(name, int(w["chips"]), config, traffic,
                   [m for m in spec["end_to_end"] if mine(m)],
                   [m for m in spec["per_layer"] if mine(m)])


@dataclasses.dataclass
class Run:
    """What one run measured; the metric readers read it."""

    cell: Cell
    seed: int
    unit: str                       # "steps" | "requests"
    window_s: float = 0.0
    units: int = 0                  # units completed in the window
    latencies_s: List[float] = dataclasses.field(default_factory=list)
    setup_s: float = 0.0
    quality: Dict[str, float] = dataclasses.field(default_factory=dict)
    counters: Dict[str, Any] = dataclasses.field(default_factory=dict)
    trace: Any = None               # chipbench.trace.Reduction
    device_kind: str = ""


def closed_loop(sut, *, seconds: Optional[float] = None,
                calls: Optional[int] = None, annotate=None, diag=None):
    """Call the adapter back to back until ``seconds`` have passed (the
    last call started in time runs to its end) or ``calls`` are done.

    ``sut.prepare(i)`` is the application's time between two calls: it
    lies inside the window and outside each call's latency.
    ``sut.call(i)`` returns once its answer is on the host, with the
    units it completed.  ``diag``, a ``HostDiag``, notes what the host
    did around each call."""
    lat, units, i = [], 0, 0
    ann = annotate or (lambda name: contextlib.nullcontext())
    t0 = time.perf_counter()
    while True:
        with ann("bench/prepare"):
            sut.prepare(i)
        if diag:
            diag.before()
        t = time.perf_counter()
        with ann("bench/call"):
            units += sut.call(i)
        lat.append(time.perf_counter() - t)
        if diag:
            diag.after(sut)
        i += 1
        if calls is not None and i >= calls:
            break
        if seconds is not None and time.perf_counter() - t0 >= seconds:
            break
    return time.perf_counter() - t0, units, lat


class HostDiag:
    """What the host did during each call, to tell a slow call's cause:
    CPU time the hypervisor stole from this machine (``/proc/stat``,
    all CPUs), the process's involuntary context switches, Python's
    garbage-collection pauses, and, where the adapter notes it
    (``sut.dispatch_s``), the seconds spent dispatching before the wait
    for the answer."""

    def __init__(self):
        import gc

        self.calls: List[Dict[str, float]] = []
        self._gc_s = 0.0
        self._gc_t = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t = time.perf_counter()
        elif self._gc_t is not None:
            self._gc_s += time.perf_counter() - self._gc_t
            self._gc_t = None

    @staticmethod
    def _steal_s() -> float:
        try:
            with open("/proc/stat") as f:
                fields = f.readline().split()
            return int(fields[8]) / os.sysconf("SC_CLK_TCK")
        except (OSError, IndexError, ValueError):
            return 0.0

    def _now(self):
        import resource

        return (self._steal_s(),
                resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw,
                self._gc_s)

    def before(self):
        self._t0 = self._now()

    def after(self, sut):
        (s0, c0, g0), (s1, c1, g1) = self._t0, self._now()
        d = {"steal_ms": 1e3 * (s1 - s0), "nivcsw": c1 - c0,
             "gc_ms": 1e3 * (g1 - g0)}
        if getattr(sut, "dispatch_s", None) is not None:
            d["dispatch_ms"] = 1e3 * sut.dispatch_s
        self.calls.append(d)

    def report(self, latencies_s, slow=1.05, top=10):
        """Totals over the window, and the ``top`` slowest calls over
        ``slow`` times the median with what the host did in them."""
        import gc

        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        med = sorted(latencies_s)[len(latencies_s) // 2]
        worst = sorted(range(len(latencies_s)),
                       key=lambda i: -latencies_s[i])[:top]
        slow_calls = [dict(i=i, ms=1e3 * latencies_s[i], **self.calls[i])
                      for i in sorted(worst)
                      if latencies_s[i] > slow * med]
        total = {k: sum(c[k] for c in self.calls)
                 for k in ("steal_ms", "nivcsw", "gc_ms")}
        return {"host_in_calls": total, "slow_calls": slow_calls}


class CompileClock:
    """Compilations and their seconds, from JAX's own monitoring events
    (a persistent-cache hit counts only its retrieval)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration_secs, **_):
        if event == self.EVENT:
            self.seconds += duration_secs
            self.count += 1


def device_info(jax, n: int) -> Dict[str, Any]:
    d = jax.devices()[:n]
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def peak_bytes(jax, n: int) -> int:
    stats = [d.memory_stats() or {} for d in jax.devices()[:n]]
    return int(max(s.get("peak_bytes_in_use", 0) for s in stats))


def _log(msg: Dict[str, Any]):
    print(json.dumps(msg), file=sys.stderr, flush=True)


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True,
             wrap_adapter: Optional[Callable] = None) -> Dict[str, Any]:
    """One run of ``cell``; returns the result line as a dict.

    ``require_tpu=False`` and ``wrap_adapter`` exist for the tests under
    ``benchmarks/chip/tests``: they run the rest of a run on the CPU at
    a small size, with the timed path broken underneath."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU found (devices: {[str(d) for d in devs]})")
    if require_tpu and len(devs) < cell.chips:
        raise NoChip(f"{cell.name} needs {cell.chips} TPU chips, "
                     f"found {len(devs)}")
    t_init = time.perf_counter()
    clock = CompileClock()

    adapter_mod = load_module(
        BENCH_DIR / "adapters" / f"{cell.config['adapter']}.py")
    reference = load_module(
        BENCH_DIR / "references" / f"{cell.config['reference']}.py")
    sut = adapter_mod.Adapter(cell.config, cell.traffic, seed,
                              chips=cell.chips)
    if wrap_adapter is not None:
        sut = wrap_adapter(sut)
    run = Run(cell, seed, sut.unit, device_kind=devs[0].device_kind)
    phases = {"import_and_device_init_s": t_init - t_start}
    phases.update(sut.setup())
    run.setup_s = time.perf_counter() - t_start
    phases["compile_s"] = clock.seconds
    phases["compiles"] = clock.count
    _log({"setup": phases, "setup_s": run.setup_s})

    compiles0 = clock.count
    if trace:
        log_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        hlo = sut.hlo_texts()
        jax.profiler.start_trace(log_dir)
        try:
            with jax.profiler.TraceAnnotation("bench/window"):
                t0_ns = time.time_ns()
                run.window_s, run.units, run.latencies_s = closed_loop(
                    sut, calls=int(cell.traffic["trace_calls"]),
                    annotate=jax.profiler.TraceAnnotation)
                window_ns = (t0_ns, time.time_ns())
        finally:
            jax.profiler.stop_trace()
    else:
        diag = HostDiag()
        run.window_s, run.units, run.latencies_s = closed_loop(
            sut, seconds=seconds, diag=diag)
        _log(diag.report(run.latencies_s))
    in_window = clock.count - compiles0
    n_calls = len(run.latencies_s)
    _log({"window_s": run.window_s, "units": run.units, "calls": n_calls,
          "compiles_in_window": in_window})
    if not trace:
        t = time.perf_counter()
        sut.finish_span(n_calls)
        _log({"span_finish_s": time.perf_counter() - t})
    device = device_info(jax, cell.chips)
    device["memory_peak_bytes"] = peak_bytes(jax, cell.chips)
    answers = sut.answers()
    run.counters = sut.counters()
    sut.close()
    del sut

    t = time.perf_counter()
    checks, run.quality = reference.judge(cell.config, cell.traffic, seed,
                                          answers)
    del answers
    _log({"reference_s": time.perf_counter() - t})
    if trace:
        from chipbench import trace as trace_mod

        t = time.perf_counter()
        run.trace = trace_mod.reduce_file(log_dir, hlo,
                                          window_ns=window_ns)
        shutil.rmtree(log_dir, ignore_errors=True)
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        _log({"trace_reduce_s": time.perf_counter() - t,
              "trace_ops": len(run.trace.ops)})

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        reader = load_module(BENCH_DIR / "metrics" / f"{m['name']}.py")
        value = reader.read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    if not trace and n_calls > 1:
        lat = sorted(run.latencies_s)
        _log({"call_latency_ms": {"median": 1e3 * lat[len(lat) // 2],
                                  "max": 1e3 * lat[-1],
                                  "count": len(lat)}})
        if run.unit == "steps":
            _log({"call_ms": [round(1e3 * x, 3) for x in run.latencies_s]})
    ok = all(v <= lim for v, lim in checks.values()) and bool(checks)
    line = {"correct": ok, "attempted": n_calls, "failed": 0,
            "metrics": metrics, "device": device}
    if trace:
        line["breakdown"] = {"device_ops": run.trace.device_ops(10),
                             "idle_gaps": run.trace.idle_gaps(10)}
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"check {k}: {v} (limit {lim})", file=sys.stderr)
    return line


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = Cell.load(args.workload)
    try:
        line = run_cell(cell, seed=args.seed, seconds=args.seconds,
                        trace=bool(args.trace), t_start=t_start)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(line), flush=True)
    return 0
