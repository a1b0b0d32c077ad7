"""Shared arithmetic of the metric readers in ``metrics/``.

A reader returns ``None`` where its run has nothing for it to read (no
trace, no steps, no fires); the harness then leaves the metric out.
"""
from __future__ import annotations

import json
import sys

from chipbench import layers


def traced_ms_per(run, seconds, unit_count):
    if run.trace is None or not unit_count:
        return None
    return 1e3 * seconds / unit_count


def scope_ms_per_step(run, match):
    if run.trace is None or run.unit != "steps":
        return None
    return traced_ms_per(run, run.trace.self_s(match), run.units)


def scope_ms_per_request(run, match):
    if run.trace is None or run.unit != "requests":
        return None
    return traced_ms_per(run, run.trace.self_s(match), run.units)


def step_body_s(run):
    return sum(o.self_ns for o in run.trace.ops
               if layers.replay_module(o.module)
               and layers.step_body(o.path)) * 1e-9


def idle_share_pct(run, unit):
    if run.trace is None or run.unit != unit or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)


def mean_counter(run, name, unit):
    vals = run.counters.get(name)
    if run.unit != unit or not vals:
        return None
    return sum(vals) / len(vals)


def exchange_stretches(trace):
    """Per device, the stretches of executed-exchange ops: runs of them
    in time order with no push op between, one per step that ran them."""
    seq = {}
    for o in trace.ops:
        ex = layers.exchange(o.path)
        if ex or layers.PUSH in o.path:
            seq.setdefault(o.device, []).append((o.start_ns, ex))
    out = {}
    for dev, evs in seq.items():
        evs.sort()
        out[dev] = sum(1 for k, (_, ex) in enumerate(evs)
                       if ex and (k == 0 or not evs[k - 1][1]))
    return out


def exchange_attributed(run):
    """Whether the exchange's device time can be told from the step
    body's: as many exchange stretches on each device as fired steps in
    the traced window.  A ``cond`` swapped for a select, or another
    ``cond`` branch outside the planner, breaks that; the readers of
    ``exchange_ms`` and ``step_body_ms`` then report nothing, and say
    why on stderr."""
    fires = int(run.counters.get("window_fires", 0))
    found = exchange_stretches(run.trace)
    if found and all(n == fires for n in found.values()):
        return True
    print(json.dumps({"exchange_unattributed": {
        "fired_steps": fires, "exchange_stretches": found}}),
        file=sys.stderr)
    return False
