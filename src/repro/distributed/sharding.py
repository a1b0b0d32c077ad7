"""Sharding-tree builders for every jitted entry point.

One convention (DESIGN.md §5): batch → ("pod","data"); heads / d_ff / vocab
/ experts → "model"; params FSDP over "data" where the ParamSpec says so;
KV-cache length and recurrent-state heads → "model".  These builders turn
that convention into NamedSharding trees for jit in/out_shardings — the
model code re-asserts the same layout internally with
``with_sharding_constraint`` so both sides agree and GSPMD has no freedom
to resharde at the boundary.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import params as params_lib

BATCH = ("pod", "data")


def _translate(a, profile: str):
    """Profile translation mirroring models.layers.translate (the in-model
    constraints and the jit-boundary shardings must agree)."""
    if profile == "dp":
        if isinstance(a, (tuple, list)) and "data" in a:
            return ("pod", "data", "model")
        if a == "model":
            return None
    return a


def _filter(spec_axes, mesh: Mesh, shape=None, profile: str = "2d"):
    """PartitionSpec with (a) axis names the mesh lacks dropped, and (b)
    axes dropped on dims they don't divide (vocab 32001, 4 heads or batch 1
    against a 16-wide axis, ... — GSPMD cannot lay those out as jit
    argument shardings; they stay replicated on that dim)."""
    names = set(mesh.axis_names)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def keep(i, a):
        a = _translate(a, profile)
        if a is None:
            return None
        ax = tuple(x for x in (a if isinstance(a, (tuple, list)) else (a,))
                   if x in names)
        # drop trailing axes until the dim divides (batch 128 against a
        # 512-wide ("pod","data","model") product falls back to 16-way
        # rather than replicating outright)
        while ax and shape is not None:
            n = 1
            for x in ax:
                n *= sizes[x]
            if i < len(shape) and shape[i] % n == 0:
                break
            ax = ax[:-1]
        if not ax:
            return None
        return ax if len(ax) > 1 else ax[0]

    return P(*(keep(i, a) for i, a in enumerate(spec_axes)))


def batch_axes(mesh: Mesh):
    return tuple(a for a in BATCH if a in mesh.axis_names)


def param_shardings(spec_tree, mesh: Mesh, profile: str = "2d"):
    return params_lib.tree_map_specs(
        lambda s: NamedSharding(mesh, _filter(s.spec, mesh, s.shape,
                                              profile)),
        spec_tree)


def opt_shardings(pshard, mesh: Mesh, *, master: bool = False):
    """OptState(step, mu, nu[, master]) sharded like the params (ZeRO via
    FSDP spec)."""
    from repro.train.optimizer import OptState
    return OptState(
        step=NamedSharding(mesh, P()),
        mu=jax.tree.map(lambda s: s, pshard),
        nu=jax.tree.map(lambda s: s, pshard),
        master=jax.tree.map(lambda s: s, pshard) if master else None,
    )


def data_shardings(batch_sds: Dict, mesh: Mesh, profile: str = "2d"):
    """Token batches: leading (global) batch dim over ("pod","data")
    (plus "model" under the dp profile)."""
    ba = batch_axes(mesh)

    def f(sds):
        if sds is None:
            return None
        spec = [None] * len(sds.shape)
        if len(sds.shape) >= 1:
            spec[0] = ba if ba else None
        return NamedSharding(mesh, _filter(spec, mesh, sds.shape, profile))

    return jax.tree.map(f, batch_sds, is_leaf=lambda x: x is None)


def cache_shardings(cache_sds: Dict, mesh: Mesh, profile: str = "2d"):
    """KV caches / recurrent states, mirroring transformer.shard_cache:
    batch → ("pod","data"); axis 1 (length or heads) → "model".  Stacked
    ("unit") subtrees carry a leading scan-group dim (replicated)."""
    ba = batch_axes(mesh)
    model = "model" if "model" in mesh.axis_names else None

    def leaf(sds, stacked: bool):
        nd = len(sds.shape)
        off = 1 if stacked else 0
        spec = [None] * nd
        if stacked:
            spec[0] = None
        if nd - off >= 1:
            spec[off] = ba if ba else None
        if nd - off >= 2:
            spec[off + 1] = model
        return NamedSharding(mesh, _filter(spec, mesh, sds.shape, profile))

    def walk(sub, stacked):
        return jax.tree.map(lambda s: leaf(s, stacked), sub,
                            is_leaf=lambda x: x is None or hasattr(x, "shape"))

    out = dict(cache_sds)
    out["unit"] = [walk(s, True) for s in cache_sds["unit"]]
    out["prefix"] = [None if s is None else walk(s, False)
                     for s in cache_sds["prefix"]]
    out["suffix"] = [None if s is None else walk(s, False)
                     for s in cache_sds["suffix"]]
    return out


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())
