"""Persistent XLA compilation cache for the entry points.

``enable()`` is called by the scripts a user runs (``chip_smoke.py``,
the benchmarks, ``repro.launch.serve`` / ``train``), never on package
import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its
own and this sets nothing.  Otherwise the cache lives at the fixed path
``<repo>/.jax_cache`` (gitignored): the directory is part of the cache
key, so it must not move between runs.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
