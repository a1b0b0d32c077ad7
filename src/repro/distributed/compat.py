"""Profiler hooks shared by the instrumented code paths.

Thin names over ``jax.named_scope`` and ``jax.profiler`` so call sites
read the same everywhere.  They catch nothing: a profiler that fails to
start raises where it was asked for.
"""
from __future__ import annotations

import contextlib

import jax


def named_scope(name: str):
    """Profiler scope usable inside traced code (``jax.named_scope``).

    Names the enclosed ops in XLA HLO metadata, so ``jax.profiler`` traces
    and HLO dumps attribute time to the planner stage / kernel dispatch /
    exchange that spent it.  Free when no profiler is attached."""
    return jax.named_scope(name)


def trace_annotation(name: str, **meta):
    """Host-side profiler span (``jax.profiler.TraceAnnotation``).

    Records ``name`` on the calling thread's line of a running profiler
    trace, on the device events' clock; each keyword of ``meta`` arrives
    as a stat of the event (``request=3``).  With no profiler running,
    entering and leaving it records nothing.  ``core.engine`` opens the
    rebalance request's ``lb/plan`` spans with it."""
    return jax.profiler.TraceAnnotation(name, **meta)


def profiler_trace(log_dir):
    """``jax.profiler.trace(log_dir)``; a no-op context when ``log_dir``
    is falsy (the launchers' ``--profile-dir`` left unset)."""
    if not log_dir:
        return contextlib.nullcontext()
    return jax.profiler.trace(log_dir)
