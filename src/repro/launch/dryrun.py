import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512"
                           ).strip()
# ^ MUST precede any jax import (device count locks at first init).  512
# placeholder host devices back both production meshes; dry-run only — tests
# and benchmarks see the real single device.  Appended, so flags the caller
# set survive.

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this driver builds the real jitted entry point (train_step /
prefill / decode_step) with production in/out shardings, runs
``.lower().compile()`` against ShapeDtypeStruct inputs (no allocation), and
records:

  * ``memory_analysis()``   — per-device bytes (proves the cell fits)
  * ``cost_analysis()``     — HLO FLOPs + HBM bytes (roofline terms 1-2)
  * collective bytes        — parsed from the compiled HLO (roofline term 3)
  * lower/compile wall time, HLO op census, model-FLOPs (6·N·D / 2·N·D)

Artifacts land in ``artifacts/dryrun/<arch>__<shape>__<mesh>[__tag].json``;
``benchmarks/roofline.py`` and EXPERIMENTS.md §Dry-run/§Roofline consume
them.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m \
      --shape train_4k --mesh single            # one cell
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh both]
  ... --set remat=full --set moe_impl=dense --tag myexp   # perf overrides
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import SHAPES, get_arch, input_specs, list_archs, shape_applicable
from repro.distributed import sharding as shard_rules
from repro.launch import hlo as hlo_mod
from repro.launch.mesh import make_production_mesh
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.models.params import count_params, shape_dtype_tree
from repro.train import optimizer as opt_mod
from repro.train import train_step as ts_mod

ARTIFACTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                         "artifacts", "dryrun")

# TPU v5e hardware model (assignment §Roofline)
PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
ICI_BW = 50e9                # bytes/s / link


def active_params(cfg: ModelConfig) -> Dict[str, int]:
    """Total and per-token-active parameter counts (MoE-aware)."""
    specs = transformer.model_specs(cfg)
    total = count_params(specs)
    if cfg.moe is None:
        return dict(total=total, active=total)
    m = cfg.moe
    expert_p = 3 * cfg.d_model * m.d_expert
    n_moe = sum(1 for k in cfg.all_layers() if k.startswith("moe"))
    inactive = n_moe * (m.num_experts - m.top_k) * expert_p
    return dict(total=total, active=total - inactive)


def model_flops(cfg: ModelConfig, shape) -> float:
    """6·N_active·tokens (train) / 2·N_active·tokens (inference)."""
    p = active_params(cfg)["active"]
    if shape.kind == "train":
        return 6.0 * p * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * p * shape.global_batch * shape.seq_len
    return 2.0 * p * shape.global_batch            # decode: 1 token/seq


def apply_overrides(cfg: ModelConfig, overrides: Dict[str, str]) -> ModelConfig:
    """--set key=value model-config overrides for perf experiments."""
    kw: Dict[str, Any] = {}
    for k, v in overrides.items():
        if k == "moe_impl":
            assert cfg.moe is not None
            kw["moe"] = dataclasses.replace(cfg.moe, impl=v)
        elif k in ("sliding_window", "vision_prefix"):
            kw[k] = int(v)
        elif k in ("compute_dtype", "param_dtype"):
            kw[k] = v
        elif k == "sharding":
            kw["sharding_profile"] = v
        elif k == "ep":
            kw["ep_axes"] = (("data", "model") if v == "wide"
                             else ("model",))
    return dataclasses.replace(cfg, **kw) if kw else cfg


def build_cell(cfg: ModelConfig, shape, mesh, *, remat: str = "none",
               seq_chunk: int = 512):
    """Returns (jitted_fn, example_args (SDS), n_static) for one cell."""
    specs = transformer.model_specs(cfg)
    params_sds = shape_dtype_tree(specs)
    prof = cfg.sharding_profile
    if prof == "dp" and shape.kind != "train":
        prof = "2d"            # cache paths need KV-length sharding
    pshard = shard_rules.param_shardings(specs, mesh, prof)
    ins = input_specs(cfg, shape)

    if shape.kind == "train":
        master = cfg.param_dtype != "float32"
        ocfg = opt_mod.OptConfig(master_fp32=master)
        opt_sds = jax.eval_shape(
            lambda p: opt_mod.init(p, master_fp32=master), params_sds)
        oshard = shard_rules.opt_shardings(pshard, mesh, master=master)
        bshard = shard_rules.data_shardings(ins["batch"], mesh, prof)
        step = ts_mod.make_train_step(cfg, ocfg, remat=remat)
        fn = jax.jit(
            step,
            in_shardings=(pshard, oshard, bshard),
            out_shardings=(pshard, oshard, None),
            donate_argnums=(0, 1),
        )
        return fn, (params_sds, opt_sds, ins["batch"])

    if shape.kind == "prefill":
        bshard = shard_rules.data_shardings(ins["batch"], mesh, prof)
        cache_sds = jax.eval_shape(
            lambda: transformer.init_cache(
                cfg, shape.global_batch, shape.seq_len, jnp.bfloat16))
        cshard = shard_rules.cache_shardings(cache_sds, mesh, prof)

        def prefill(params, batch, cache):
            return transformer.prefill(params, cfg, batch, cache)

        fn = jax.jit(
            prefill,
            in_shardings=(pshard, bshard, cshard),
            out_shardings=(None, cshard),
            donate_argnums=(2,),
        )
        return fn, (params_sds, ins["batch"], cache_sds)

    # decode
    cache_sds = ins["cache"]
    cshard = shard_rules.cache_shardings(cache_sds, mesh, prof)
    tok_shard = shard_rules.data_shardings(
        dict(tokens=ins["tokens"]), mesh, prof)["tokens"]

    def decode(params, tokens, index, cache):
        return transformer.decode_step(params, cfg, tokens, index, cache)

    fn = jax.jit(
        decode,
        in_shardings=(pshard, tok_shard, None, cshard),
        out_shardings=(None, cshard),
        donate_argnums=(3,),
    )
    return fn, (params_sds, ins["tokens"], ins["index"], cache_sds)


def run_cell(arch: str, shape_name: str, mesh_kind: str, *,
             remat: str = "auto", overrides: Optional[Dict] = None,
             tag: str = "", save: bool = True) -> Dict:
    spec = get_arch(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(arch, shape_name)
    if not ok:
        rec = dict(arch=arch, shape=shape_name, mesh=mesh_kind,
                   status="skip", reason=why)
        if save:
            _save(rec, arch, shape_name, mesh_kind, tag)
        return rec

    cfg = apply_overrides(spec.config, overrides or {})
    if remat == "auto":
        remat = "full" if shape.kind == "train" else "none"

    multi = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    rec: Dict[str, Any] = dict(
        arch=arch, shape=shape_name, mesh=mesh_kind, status="ok",
        chips=int(np.prod(mesh.devices.shape)), remat=remat,
        overrides=overrides or {}, params=active_params(cfg),
        model_flops=model_flops(cfg, shape),
    )
    try:
        with jax.sharding.set_mesh(mesh):
            fn, args = build_cell(cfg, shape, mesh, remat=remat)
            t0 = time.time()
            lowered = fn.lower(*args)
            rec["lower_s"] = round(time.time() - t0, 2)
            t0 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t0, 2)

        ma = compiled.memory_analysis()
        rec["memory"] = dict(
            argument_bytes=int(ma.argument_size_in_bytes),
            output_bytes=int(ma.output_size_in_bytes),
            temp_bytes=int(ma.temp_size_in_bytes),
            alias_bytes=int(ma.alias_size_in_bytes),
            peak_device_bytes=int(ma.argument_size_in_bytes
                                  + ma.output_size_in_bytes
                                  + ma.temp_size_in_bytes
                                  - ma.alias_size_in_bytes),
        )
        ca = compiled.cost_analysis()
        # raw XLA numbers (scan bodies counted ONCE — recorded for
        # reference, not used for the roofline; see launch/hlo.py)
        rec["cost_raw"] = dict(
            flops=float(ca.get("flops", 0.0)),
            bytes_accessed=float(ca.get("bytes accessed", 0.0)),
        )
        txt = compiled.as_text()
        flat = hlo_mod.analyze(txt)
        rec["cost"] = dict(
            flops=flat["flops"],
            bytes_accessed=flat["traffic"],
        )
        rec["collectives"] = {
            k: dict(count=flat.get(f"coll:{k}:count", 0.0),
                    bytes=flat.get(f"coll:{k}:bytes", 0.0))
            for k in hlo_mod.COLLECTIVES}
        rec["collective_bytes"] = flat["collective_bytes"]
        rec["op_census"] = hlo_mod.op_census(txt)
        rec["trip_counts"] = hlo_mod.while_trip_counts(txt)[:12]
        rec["roofline"] = roofline_terms(rec)
    except Exception as e:  # record the failure — these are bugs to fix
        rec["status"] = "fail"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc(limit=20)
    if save:
        _save(rec, arch, shape_name, mesh_kind, tag)
    return rec


def roofline_terms(rec: Dict) -> Dict:
    """Three roofline terms in seconds (per device — cost_analysis and the
    compiled HLO are already the per-device SPMD module)."""
    t_compute = rec["cost"]["flops"] / PEAK_FLOPS
    t_memory = rec["cost"]["bytes_accessed"] / HBM_BW
    t_coll = rec["collective_bytes"] / ICI_BW
    dominant = max(
        ("compute", t_compute), ("memory", t_memory),
        ("collective", t_coll), key=lambda kv: kv[1])[0]
    chips = rec["chips"]
    useful = rec["model_flops"] / chips
    return dict(
        t_compute=t_compute, t_memory=t_memory, t_collective=t_coll,
        dominant=dominant,
        model_flops_per_chip=useful,
        useful_flop_frac=(useful / rec["cost"]["flops"]
                          if rec["cost"]["flops"] else 0.0),
        # step-time lower bound if terms overlapped perfectly / not at all
        t_min=max(t_compute, t_memory, t_coll),
        t_sum=t_compute + t_memory + t_coll,
        # fraction of ideal (pure-compute of useful flops) achieved at t_min
        roofline_frac=(useful / PEAK_FLOPS) / max(
            max(t_compute, t_memory, t_coll), 1e-30),
    )


def _save(rec: Dict, arch: str, shape: str, mesh_kind: str, tag: str):
    os.makedirs(ARTIFACTS, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    path = os.path.join(
        ARTIFACTS, f"{arch}__{shape}__{mesh_kind}{suffix}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, default=float)
    return path


def summarize(rec: Dict) -> str:
    if rec["status"] == "skip":
        return f"{rec['arch']:>24} {rec['shape']:>12} {rec['mesh']:>7}  SKIP ({rec['reason'][:40]}...)"
    if rec["status"] == "fail":
        return f"{rec['arch']:>24} {rec['shape']:>12} {rec['mesh']:>7}  FAIL {rec['error'][:80]}"
    r = rec["roofline"]
    m = rec["memory"]["peak_device_bytes"] / 2**30
    return (f"{rec['arch']:>24} {rec['shape']:>12} {rec['mesh']:>7}  "
            f"mem/dev={m:6.2f}GiB flops={rec['cost']['flops']:.3e} "
            f"tc={r['t_compute']*1e3:8.2f}ms tm={r['t_memory']*1e3:8.2f}ms "
            f"tx={r['t_collective']*1e3:8.2f}ms dom={r['dominant']:>10} "
            f"roofline={r['roofline_frac']*100:5.1f}% "
            f"(lower {rec['lower_s']}s compile {rec['compile_s']}s)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--remat", default="auto")
    ap.add_argument("--set", action="append", default=[],
                    help="cfg override key=value")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    overrides = dict(kv.split("=", 1) for kv in args.set)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells = []
    if args.all:
        for a in list_archs():
            for s in SHAPES:
                cells.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells.append((args.arch, args.shape))

    t0 = time.time()
    n_fail = 0
    for arch, shape in cells:
        for mk in meshes:
            rec = run_cell(arch, shape, mk, remat=args.remat,
                           overrides=overrides, tag=args.tag)
            print(summarize(rec), flush=True)
            n_fail += rec["status"] == "fail"
    print(f"done in {time.time() - t0:.0f}s, {n_fail} failures", flush=True)
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
