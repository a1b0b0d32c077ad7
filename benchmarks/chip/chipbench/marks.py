"""Arithmetic on what the program itself marks: the device scopes it
names below the layers of ``chipbench.layers`` (read from the trace's op
paths) and the host counters of its ``repro.obs.metrics`` registry (read
in-process, after the run).

Every function returns ``None`` where the run holds none of what it
reads (no trace, another unit, a program without the scope or counter);
the harness then leaves the metric out.
"""
from __future__ import annotations

from typing import Optional

HANDOFF = "replay/handoff"        # pic/driver._chunk_runner step
OWNERS = "replay/owners"
MIGRATE = "exchange/migrate"      # runtime/migrate.build_and_apply
SCORE = "score"                   # core/object_selection phases
TAKE = "take"
REQUESTS = "lb.plan.requests"     # core/engine, one a rebalance request


def under(path: str, scope: str) -> bool:
    """Whether ``scope`` (one or more whole components) lies on ``path``."""
    return f"/{scope}/" in f"/{path}/"


def scope_s(run, match) -> Optional[float]:
    """Own device seconds of the ops whose path satisfies ``match``; None
    where no op in the window does."""
    if run.trace is None or not any(match(o.path) for o in run.trace.ops):
        return None
    return run.trace.self_s(match)


def scope_ms_per_unit(run, match, unit: str) -> Optional[float]:
    """Own device ms per completed step or request under ``match``."""
    if run.unit != unit or not run.units:
        return None
    s = scope_s(run, match)
    return None if s is None else 1e3 * s / run.units


def counter_per_request(run, name: str) -> Optional[float]:
    """The program counter ``name`` over ``lb.plan.requests``, both from
    the process's registry: an average over every rebalance request of
    the run, set-up included.  None in a run of steps, or where the
    program lacks either counter."""
    if run.unit != "requests":
        return None
    try:
        from repro.obs import metrics
    except ImportError:
        return None
    snap = metrics.snapshot()
    requests, value = snap.get(REQUESTS), snap.get(name)
    if not requests or value is None:
        return None
    return value / requests
