"""Per-chip arithmetic for the cells that shard one replay over several
chips.  A trace reduction sums each op's own time over every chip, so a
per-chip mean divides by the cell's chips; the replay's steps and fires
in the window are the program's counters ``lb.replay.steps`` and
``lb.replay.fires`` (``distributed/replay_shard``), which the adapter
reads at the window's ends.

Every function returns ``None`` where the run holds none of what it
reads; the harness then leaves the metric out.
"""
from __future__ import annotations

from typing import Optional

from chipbench import layers, marks

STEPS = "lb.replay.steps"
FIRES = "lb.replay.fires"


def ms_per(run, match, counter: str) -> Optional[float]:
    """Own device ms under ``match`` per chip and per unit of the
    program counter ``counter`` in the window."""
    n = run.counters.get(counter)
    s = marks.scope_s(run, match)
    if s is None or not n:
        return None
    return 1e3 * s / run.cell.chips / n


def step_body_ms(run) -> Optional[float]:
    """Own device ms per chip and step of the replay step's ops outside
    every layer with a metric of its own: the push, the histogram, the
    planner, the exchange (``layers.step_body``), the psums and the
    resilience scopes."""
    n = run.counters.get(STEPS)
    if run.trace is None or not n:
        return None
    ops = [o for o in run.trace.ops if layers.replay_module(o.module)]
    if not ops:
        return None
    ns = sum(o.self_ns for o in ops if layers.step_body(o.path)
             and not marks.under(o.path, "replay/psum")
             and "/resilience/" not in f"/{o.path}")
    return 1e-6 * ns / run.cell.chips / n
