"""Shared benchmark utilities: timing, table formatting, result capture.

Every bench imports this module, so importing it turns on the persistent
compilation cache (``repro.launch.compile_cache``).
"""
from __future__ import annotations

import json
import os
import subprocess
import time
from typing import Callable, Dict, List, Optional

from repro.launch import compile_cache

compile_cache.enable()

ARTIFACTS = os.path.join(os.path.dirname(__file__), "..", "artifacts",
                         "bench")


def save_result(name: str, payload: Dict) -> str:
    os.makedirs(ARTIFACTS, exist_ok=True)
    path = os.path.join(ARTIFACTS, f"{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=float)
    return path


def provenance() -> Dict:
    """Where/what produced a bench file: device kind + count, backend,
    jax version, git sha.  Stamped into every ``BENCH_*.json`` by
    :func:`write_bench_json` so perf trajectories across commits carry
    their own context (the CI artifact and the committed file agree on
    the schema; consumers treat missing git metadata as ``None``)."""
    import jax

    devs = jax.devices()
    sha = None
    try:
        proc = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10)
        if proc.returncode == 0:
            sha = proc.stdout.strip() or None
    except Exception:
        sha = None
    return dict(
        device_kind=devs[0].device_kind,
        device_count=len(devs),
        backend=jax.default_backend(),
        jax_version=jax.__version__,
        git_sha=sha,
    )


def write_bench_json(path: str, *, schema: str, generated_by: str,
                     repeats: Optional[int] = None, **out) -> str:
    """The one writer behind every repo-root ``BENCH_*.json``.

    Stable shape: ``schema`` / ``generated_by`` / ``provenance`` (see
    :func:`provenance`) / optional ``repeats`` + the bench's own keys,
    serialized sorted with a trailing newline so diffs across commits
    stay minimal."""
    payload = dict(
        schema=schema,
        generated_by=generated_by,
        provenance=provenance(),
        **out,
    )
    if repeats is not None:
        payload["repeats"] = repeats
    path = os.path.abspath(path)
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=float, sort_keys=True)
        f.write("\n")
    return path


def _timeit(fn: Callable, args, kw, repeat: int):
    """([wall_seconds...], result-from-last-run)."""
    walls = []
    out = None
    for _ in range(repeat):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        walls.append(time.perf_counter() - t0)
    return walls, out


def timeit(fn: Callable, *args, repeat: int = 3, **kw):
    """(result, best_seconds) — best-of-N wall time."""
    walls, out = _timeit(fn, args, kw, repeat)
    return out, min(walls)


def timeit_median(fn: Callable, *args, repeat: int = 3, **kw):
    """(result, median_seconds) — median-of-N wall time.

    The gating statistic for perf assertions: robust to one slow outlier
    (CI noise) without rewarding a lucky fastest run the way best-of-N
    does.  ``result`` is from the last run."""
    walls, out = _timeit(fn, args, kw, repeat)
    return out, sorted(walls)[len(walls) // 2]


def table(headers: List[str], rows: List[List]) -> str:
    w = [max(len(str(h)), max((len(str(r[i])) for r in rows), default=0))
         for i, h in enumerate(headers)]
    out = ["  ".join(str(h).rjust(w[i]) for i, h in enumerate(headers))]
    out.append("  ".join("-" * w[i] for i in range(len(headers))))
    for r in rows:
        out.append("  ".join(str(c).rjust(w[i]) for i, c in enumerate(r)))
    return "\n".join(out)
