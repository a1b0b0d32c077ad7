"""Serving launcher: batched requests against a small model.

``python -m repro.launch.serve --arch smollm-135m --requests 8`` spins up a
ServeEngine on the reduced config, feeds it a batch of prompts through the
diffusion scheduler (multi-replica placement simulated at host scale), and
reports throughput + scheduling metrics.

``--fleet-replay N`` skips the model entirely and drives ``N`` synthetic
bursty multi-turn sessions through the scan-compiled serving replay
(``serve/replay.py`` — trigger decision and executed KV-slab exchange
inside one ``lax.scan``), reporting the balance/KV-traffic summary the
serving benchmark gates on.

Observability: every reported number flows through the
``repro.obs.metrics`` registry (``snapshot()`` is the single source the
log lines print from), ``--telemetry counters|full`` threads the
scan-carried StepRecord ring through the replay, ``--trace-out f.json``
exports the recorded run as a Chrome/Perfetto trace, and
``--profile-dir d`` wraps the run in ``jax.profiler.trace``.
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def fleet_replay(args) -> None:
    from repro.distributed import compat
    from repro.obs import metrics, trace_export
    from repro.serve import replay as sr

    w = sr.ServeWorkload(num_sessions=args.fleet_replay,
                         num_replicas=args.replicas)
    t0 = time.time()
    with compat.profiler_trace(args.profile_dir):
        r = sr.run_serve_replay(w, steps=args.ticks, lb_every=10,
                                strategy=args.strategy,
                                telemetry=args.telemetry)
    metrics.gauge("serve/replay_seconds").set(time.time() - t0)
    metrics.counter("serve/sessions").inc(w.num_sessions)
    metrics.counter("serve/ticks").inc(args.ticks)
    metrics.counter("serve/rebalances").inc(int(r.lb_fired.sum()))
    metrics.counter("serve/moved_kv_bytes").inc(float(r.total_moved_kv))
    metrics.gauge("serve/p95_max_avg").set(
        float(np.percentile(r.max_avg, 95)))
    metrics.gauge("serve/prefix_local").set(float(r.prefix_local.mean()))
    s = metrics.snapshot()
    print(f"replayed {int(s['serve/sessions'])} sessions x "
          f"{int(s['serve/ticks'])} ticks on "
          f"{w.num_replicas} replicas in {s['serve/replay_seconds']:.2f}s "
          f"({'scanned' if r.scanned else 'host'} path)")
    print(f"  rebalances {int(s['serve/rebalances'])}, moved KV "
          f"{s['serve/moved_kv_bytes']:.0f} bytes, p95 max/avg "
          f"{s['serve/p95_max_avg']:.3f}, prefix-local "
          f"{s['serve/prefix_local']:.3f}")
    if r.telemetry is not None and args.trace_out:
        trace_export.export_chrome_trace(r.telemetry, path=args.trace_out,
                                         label="serve-replay")
        print(f"  wrote Chrome trace to {args.trace_out} "
              f"({len(r.telemetry.records)} steps recorded, "
              f"{r.telemetry.dropped} dropped)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--fleet-replay", type=int, default=0,
                    help="replay N synthetic sessions through "
                         "serve.replay instead of serving a model")
    ap.add_argument("--ticks", type=int, default=60)
    ap.add_argument("--strategy", default="diff-comm+predictive")
    ap.add_argument("--telemetry", default="off",
                    choices=("off", "counters", "full"),
                    help="scan-carried StepRecord telemetry level "
                         "(fleet replay)")
    ap.add_argument("--trace-out", default=None,
                    help="write the recorded run as a Chrome/Perfetto "
                         "trace JSON (needs --telemetry full)")
    ap.add_argument("--profile-dir", default=None,
                    help="wrap the run in jax.profiler.trace(DIR)")
    args = ap.parse_args()

    from repro.launch import compile_cache

    compile_cache.enable()
    if args.fleet_replay > 0:
        fleet_replay(args)
        return

    from repro.configs import get_arch
    from repro.distributed import compat
    from repro.models import transformer
    from repro.models.params import init_params
    from repro.obs import metrics
    from repro.serve.engine import Request, ServeConfig, ServeEngine
    from repro.serve.scheduler import DiffusionScheduler, Session

    spec = get_arch(args.arch)
    cfg = spec.reduced
    params = init_params(transformer.model_specs(cfg), 0)

    sched = DiffusionScheduler(args.replicas)
    engines = [ServeEngine(cfg, params, ServeConfig(num_slots=args.slots))
               for _ in range(args.replicas)]

    rng = np.random.default_rng(0)
    t0 = time.time()
    with compat.profiler_trace(args.profile_dir):
        for i in range(args.requests):
            prompt = rng.integers(1, cfg.vocab_size,
                                  size=rng.integers(4, 12))
            sess = Session(uid=i, replica=0, tokens_per_s=1.0,
                           prefix_group=i % max(args.requests // 4, 1))
            r = sched.place_new(sess)
            engines[r].submit(Request(uid=i, prompt=prompt,
                                      max_new_tokens=args.max_new))
        info = sched.rebalance()
        done = []
        for e in engines:
            done += e.run_until_drained()
    metrics.gauge("serve/seconds").set(time.time() - t0)
    metrics.counter("serve/requests").inc(len(done))
    metrics.counter("serve/tokens").inc(sum(len(r.out) for r in done))
    metrics.gauge("serve/max_avg_load").set(info.get("max_avg_load", 1))
    metrics.gauge("serve/ext_int_comm").set(info.get("ext_int_comm", 0))
    metrics.counter("serve/moved_kv_bytes").inc(
        float(info.get("moved_kv_bytes", 0)))
    s = metrics.snapshot()
    dt, toks = s["serve/seconds"], s["serve/tokens"]
    print(f"served {int(s['serve/requests'])} requests, {int(toks)} "
          f"tokens in {dt:.2f}s ({toks/dt:.1f} tok/s)")
    print(f"scheduler: max/avg load {s['serve/max_avg_load']:.3f}, "
          f"ext/int {s['serve/ext_int_comm']:.3f}, moved KV "
          f"{s['serve/moved_kv_bytes']:.0f} bytes")
    for r in done[:4]:
        print(f"  req {r.uid}: {len(r.out)} tokens {r.out[:8]}...")


if __name__ == "__main__":
    main()
