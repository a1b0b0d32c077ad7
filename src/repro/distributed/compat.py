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


def trace_annotation(name: str):
    """Host-side profiler region (``jax.profiler.TraceAnnotation``)."""
    return jax.profiler.TraceAnnotation(name)


def profiler_trace(log_dir):
    """``jax.profiler.trace(log_dir)``; a no-op context when ``log_dir``
    is falsy (the launchers' ``--profile-dir`` left unset)."""
    if not log_dir:
        return contextlib.nullcontext()
    return jax.profiler.trace(log_dir)
