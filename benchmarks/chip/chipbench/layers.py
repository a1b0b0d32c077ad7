"""Which device ops belong to which layer, by their scope path.

The paths are the ``jax.named_scope`` names the program puts on its
layers, as they appear in each op's ``op_name`` metadata.
"""
from __future__ import annotations

PUSH = "kernel/pic-push"
HISTOGRAM = "kernel/histogram"
STAGE1 = "lb-plan/stage1-neighbors"
STAGE2 = "lb-plan/stage2-diffusion"
STAGE3 = "lb-plan/stage3-objects"


def planner(path: str) -> bool:
    return "lb-plan/" in path or "jit(plan_fn)" in path


def exchange(path: str) -> bool:
    """The executed exchange: the ``exchange/*`` scopes and the counting
    scatter kernel, and the fired-step branch outside the planner.  In
    the scanned PIC step the exchange (``runtime.migrate.build_and_apply``
    under ``lax.cond``) carries no ``exchange/`` scope of its own, so its
    payload gathers are known by lying in a ``cond`` branch that is not
    the planner's.  That rests on the step's structure, so the readers
    use it only where ``readers.exchange_attributed`` finds it holds:
    one stretch of such ops for each fired step."""
    if "exchange/" in path or "kernel/scatter-dest" in path:
        return True
    return "/cond/branch" in path and not planner(path)


def step_body(path: str) -> bool:
    """Replay-step ops outside every named layer above; the trigger's
    statistics (``trigger/*``) are part of the step body."""
    return not (PUSH in path or HISTOGRAM in path or planner(path)
                or exchange(path))


def replay_module(module: str) -> bool:
    """The compiled PIC chunk runner (``pic/driver._chunk_runner``)."""
    return module.startswith("jit_run_chunk")
