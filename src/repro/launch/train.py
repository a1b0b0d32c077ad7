"""Training launcher: end-to-end driver wiring every subsystem together.

``python -m repro.launch.train --arch smollm-135m --steps 100 ...`` runs a
real (small-scale) training job on the available devices: data pipeline →
jitted train step (donated state) → periodic checkpointing → fault-tolerant
supervision → optional diffusion balancers (EP placement for MoE archs,
straggler-driven data re-sharding).

At production scale the same module is the per-host entry point: the mesh
comes from ``make_production_mesh`` and jax.distributed handles cross-host
init (not available in this container; the multi-pod configuration is
exercised by launch/dryrun.py instead).
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time
from typing import Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.distributed import compat
from repro.launch import compile_cache
from repro.models import transformer
from repro.models.params import init_params
from repro.obs import metrics as obs_metrics
from repro.train import checkpoint as ckpt
from repro.train import data as data_mod
from repro.train import ep_runtime
from repro.train import optimizer as opt_mod
from repro.train import train_step as ts_mod


@dataclasses.dataclass
class RunConfig:
    arch: str = "smollm-135m"
    reduced: bool = True            # full configs need real accelerators
    steps: int = 50
    seq_len: int = 128
    global_batch: int = 8
    lr: float = 3e-4
    warmup: int = 10
    save_every: int = 20
    ckpt_dir: Optional[str] = None
    resume: bool = True
    remat: str = "none"
    ep_balance_every: int = 0       # MoE expert rebalance cadence (0 = off)
    ep_strategy: str = "diff-comm"  # any registered strategy (+ "greedy")
    ep_trigger: Optional[str] = None  # None → strategy default / cadence
    ep_num_ranks: int = 0           # EP ranks (0 = min(4, E) at host scale)
    seed: int = 0
    log_every: int = 10
    profile_dir: Optional[str] = None  # jax.profiler.trace around the loop


def build(cfg: RunConfig):
    spec = get_arch(cfg.arch)
    mcfg = spec.reduced if cfg.reduced else spec.config
    specs = transformer.model_specs(mcfg)
    params = init_params(specs, cfg.seed)
    ocfg = opt_mod.OptConfig(lr=cfg.lr, warmup_steps=cfg.warmup,
                             total_steps=cfg.steps)
    opt_state = opt_mod.init(params)
    collect = bool(cfg.ep_balance_every) and mcfg.moe is not None
    step_fn = jax.jit(ts_mod.make_train_step(mcfg, ocfg, remat=cfg.remat,
                                             collect_router_stats=collect),
                      donate_argnums=(0, 1))
    dcfg = data_mod.DataConfig(vocab_size=mcfg.vocab_size,
                               seq_len=cfg.seq_len,
                               global_batch=cfg.global_batch,
                               seed=cfg.seed)
    pipe = data_mod.DataPipeline(dcfg, num_ranks=1)
    return mcfg, params, opt_state, step_fn, pipe


def train(cfg: RunConfig) -> Dict:
    mcfg, params, opt_state, step_fn, pipe = build(cfg)
    start = 0
    if cfg.ckpt_dir and cfg.resume and ckpt.latest_step(cfg.ckpt_dir) is not None:
        params, opt_state, start, ds = ckpt.restore(
            cfg.ckpt_dir, params, opt_state)
        if ds:
            pipe.state = data_mod.PipelineState.from_dict(ds)
        print(f"resumed from step {start}")

    rebalancer = None
    if cfg.ep_balance_every and mcfg.moe is not None:
        E = mcfg.moe.num_experts
        # EP ranks at host scale: a few virtual ranks (the planning logic
        # is rank-count agnostic; at production scale this is the
        # model-axis size).
        R = cfg.ep_num_ranks or min(4, E)
        rebalancer = ep_runtime.EPRebalancer(
            E, R, strategy=cfg.ep_strategy, trigger=cfg.ep_trigger,
            lb_every=cfg.ep_balance_every)

    hist = []
    t0 = time.time()
    with compat.profiler_trace(cfg.profile_dir):
        for step in range(start, cfg.steps):
            batch = {k: jnp.asarray(v)
                     for k, v in pipe.next_batch().items()}
            params, opt_state, m = step_fn(params, opt_state, batch)
            loss = float(m["loss"])
            hist.append(loss)
            # registry first, log lines from the snapshot — one source
            obs_metrics.counter("train/steps").inc()
            obs_metrics.gauge("train/loss").set(loss)
            obs_metrics.gauge("train/grad_norm").set(float(m["grad_norm"]))
            obs_metrics.gauge("train/lr").set(float(m["lr"]))
            obs_metrics.gauge("train/seconds").set(time.time() - t0)
            if cfg.log_every and step % cfg.log_every == 0:
                s = obs_metrics.snapshot()
                print(f"step {step:5d} loss {s['train/loss']:.4f} "
                      f"gnorm {s['train/grad_norm']:.3f} "
                      f"lr {s['train/lr']:.2e} "
                      f"({s['train/seconds']:.1f}s)", flush=True)
            if (cfg.ckpt_dir and cfg.save_every
                    and (step + 1) % cfg.save_every == 0):
                ckpt.save(cfg.ckpt_dir, step + 1, params, opt_state,
                          data_state=pipe.state.to_dict())
                obs_metrics.counter("train/checkpoints").inc()
            if rebalancer is not None:
                params, info = _rebalance_experts(params, rebalancer, m,
                                                  step)
                if info.get("fired"):
                    obs_metrics.counter("train/ep_fires").inc()
                    obs_metrics.counter("train/ep_moved_experts").inc(
                        int(info["moved_experts"]))
                    obs_metrics.counter("train/ep_moved_bytes").inc(
                        float(info["moved_bytes"]))
                    obs_metrics.gauge("train/ep_last_moved").set(
                        int(info["moved_experts"]))
                    obs_metrics.gauge("train/ep_last_bytes").set(
                        float(info["moved_bytes"]))
                    obs_metrics.gauge("train/ep_max_avg").set(
                        float(info["max_avg"]))
                    if cfg.log_every:
                        s = obs_metrics.snapshot()
                        print(f"  [ep-balance] moved "
                              f"{int(s['train/ep_last_moved'])} "
                              f"experts ({s['train/ep_last_bytes']:.0f} "
                              f"B), max/avg {s['train/ep_max_avg']:.3f}",
                              flush=True)
    if cfg.ckpt_dir:
        ckpt.save(cfg.ckpt_dir, cfg.steps, params, opt_state,
                  data_state=pipe.state.to_dict())
    return dict(losses=hist, final_loss=hist[-1] if hist else float("nan"),
                seconds=time.time() - t0, params=params,
                opt_state=opt_state)


def _moe_blocks(params) -> list:
    """(section, index) of every block param dict holding a MoE layer."""
    out = []
    for sect in ("unit", "prefix", "suffix"):
        for i, blk in enumerate(params.get(sect, ())):
            if isinstance(blk, dict) and "moe" in blk:
                out.append((sect, i))
    return out


def _rebalance_experts(params, rebalancer: "ep_runtime.EPRebalancer",
                       metrics: Dict, step: int):
    """One live-rebalancing tick on the real parameter tree.

    The train step already accumulated the router statistics on device
    (``router_counts``/``router_coact`` in its metrics); the rebalancer
    decides, plans, and — on fire — relocates every MoE layer's expert
    weights through the executed exchange, reporting the measured moved
    bytes back to its trigger."""
    where = _moe_blocks(params)
    layers = [params[s][i]["moe"] for s, i in where]
    layers, info = rebalancer.step(
        step, np.asarray(metrics["router_counts"]),
        np.asarray(metrics["router_coact"]), layers)
    if info.get("fired"):
        for (s, i), moe in zip(where, layers):
            params[s][i] = {**params[s][i], "moe": moe}
    return params, info


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--profile-dir", default=None,
                    help="wrap the train loop in jax.profiler.trace(DIR)")
    args = ap.parse_args()
    compile_cache.enable()
    cfg = RunConfig(arch=args.arch, reduced=not args.full, steps=args.steps,
                    seq_len=args.seq_len, global_batch=args.batch,
                    lr=args.lr, ckpt_dir=args.ckpt_dir, remat=args.remat,
                    profile_dir=args.profile_dir)
    out = train(cfg)
    print(f"done: final loss {out['final_loss']:.4f} in {out['seconds']:.1f}s")


if __name__ == "__main__":
    main()
