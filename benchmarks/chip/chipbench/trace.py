"""Reduce a JAX profiler trace (``.xplane.pb``) to device times per layer.

What a TPU trace holds (read by hand from a v5e trace, see PERF.md §3):
the plane ``/device:TPU:<i>`` has a line ``XLA Modules`` with one event
per executed program (``jit_run_chunk(<fingerprint>)``) and a line
``XLA Ops`` with one event per executed HLO instruction, named by its
text (``%fusion.312 = s32[...] fusion(...)``).  Control-flow
instructions (``while``, ``conditional``) are events too and enclose
their bodies' events, so an op's own time is its duration less that of
the events nested in it.  The events carry no scope path; the path
(``jit(run_chunk)/while/body/.../kernel/pic-push/...``) is the
instruction's ``op_name`` metadata in the compiled module's HLO text,
which the caller passes in.  Host threads sit on ``/host:CPU``; the
Python thread's line ``python`` holds ``TraceAnnotation`` spans, on the
same clock as the device events.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=')
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r'(?:calls|to_apply)=%?([\w.\-]+)')
_COMP = re.compile(r'^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$')
_EVENT_OP = re.compile(r'^%?([^\s=]+)\s*=')
_MODULE = re.compile(r'^HloModule\s+([^\s,]+)', re.M)


def hlo_paths(hlo_text: str) -> Tuple[str, Dict[str, str]]:
    """(module name, {instruction name: op_name path}) of a compiled
    module's HLO text (``compiled.as_text()``).  An instruction the
    compiler made without metadata of its own (a fusion, a reduce-window)
    takes the commonest path among the instructions of the computation
    it calls."""
    m = _MODULE.search(hlo_text)
    name = m.group(1) if m else ""
    paths: Dict[str, str] = {}
    calls: Dict[str, List[str]] = {}
    comp_paths: Dict[str, Dict[str, int]] = {}
    comp = ""
    for line in hlo_text.splitlines():
        head = _COMP.match(line)
        if head and "=" not in line.split("{")[0]:
            comp = head.group(1)
            continue
        hit = _INSTR.match(line)
        if not hit:
            continue
        op = _OP_NAME.search(line)
        if op:
            paths[hit.group(1)] = op.group(1)
            tally = comp_paths.setdefault(comp, {})
            tally[op.group(1)] = tally.get(op.group(1), 0) + 1
        else:
            calls[hit.group(1)] = _CALLS.findall(line)
    for inst, callees in calls.items():
        tally: Dict[str, int] = {}
        for c in callees:
            for path, n in comp_paths.get(c, {}).items():
                tally[path] = tally.get(path, 0) + n
        if tally:
            paths[inst] = max(tally.items(), key=lambda kv: kv[1])[0]
    return name, paths


@dataclasses.dataclass
class DeviceOp:
    device: str
    module: str
    name: str          # HLO instruction name, e.g. "fusion.312"
    path: str          # op_name metadata ("" where unknown)
    start_ns: float
    dur_ns: float
    self_ns: float     # duration less the events nested in it


@dataclasses.dataclass
class Reduction:
    ops: List[DeviceOp]
    window: Tuple[float, float]        # traced window, ns on trace clock
    busy_ns: Dict[str, float]          # per device, inside the window
    gaps: List[Tuple[str, float]]      # (host activity, ns) idle gaps

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the devices traced."""
        if not self.busy_ns:
            return 0.0
        return sum(self.busy_ns.values()) / len(self.busy_ns) * 1e-9

    def self_s(self, match) -> float:
        """Own device seconds of ops whose path satisfies ``match``
        (a callable on the path), summed over devices."""
        return sum(o.self_ns for o in self.ops if match(o.path)) * 1e-9

    def device_ops(self, top: int = 10) -> List[Tuple[str, float]]:
        """The ``top`` op instances by own time: (path [name], seconds)."""
        agg: Dict[str, float] = {}
        for o in self.ops:
            key = f"{o.path or o.module} [{o.name}]"
            agg[key] = agg.get(key, 0.0) + o.self_ns * 1e-9
        return sorted(agg.items(), key=lambda kv: -kv[1])[:top]

    def idle_gaps(self, top: int = 10) -> List[Tuple[str, float]]:
        agg: Dict[str, float] = {}
        for label, ns in self.gaps:
            agg[label] = agg.get(label, 0.0) + ns * 1e-9
        return sorted(agg.items(), key=lambda kv: -kv[1])[:top]


def find_xplane(log_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return hits[-1]


def _nesting(events: Sequence[Tuple[float, float, str]]):
    """Own time of each (start, dur, name) event, nesting by interval,
    and the index of the event that encloses it (-1 at top level)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    own = [e[1] for e in events]
    parent = [-1] * len(events)
    stack: List[int] = []
    for i in order:
        s, d, _ = events[i]
        while stack and events[stack[-1]][0] + events[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= d
            parent[i] = stack[-1]
        stack.append(i)
    return own, parent, order


def _union(intervals: Iterable[Tuple[float, float]]):
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _stat(event, key):
    for k, v in event.stats:
        if k == key:
            return v
    return None


def reduce_planes(planes, hlo_texts: Sequence[str], *,
                  window_ns: Optional[Tuple[int, int]] = None,
                  window_span: str = "bench/window",
                  min_gap_ns: float = 20e3) -> Reduction:
    """Reduce profiler planes (``ProfileData.planes`` or objects shaped
    like them) over the traced window: ``window_ns``, the host's
    ``time.time_ns()`` at its start and end, placed on the trace's clock
    by the profile's start time where the trace records it; else the
    host span ``window_span``."""
    planes = list(planes)             # ProfileData yields them only once
    paths: Dict[str, Dict[str, str]] = {}
    for text in hlo_texts:
        name, p = hlo_paths(text)
        paths.setdefault(name, {}).update(p)

    host_lines: Dict[str, List[Tuple[float, float, str]]] = {}
    devices = []
    for plane in planes:
        if plane.name.startswith("/device:TPU"):
            lines = {ln.name: list(ln.events) for ln in plane.lines}
            devices.append((plane.name, lines))
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                host_lines[ln.name] = [(e.start_ns, e.duration_ns, e.name)
                                       for e in ln.events]
    # what the host did: every event of the lines that hold the
    # benchmark's spans (the Python thread's), and the Python tracer's
    host = [h for name, evs in host_lines.items()
            if name == "python" or any(e[2].startswith("bench/")
                                       for e in evs)
            for h in evs]
    t0 = _profile_start_ns(planes)
    if window_ns is not None and t0 is not None:
        w0, w1 = window_ns[0] - t0, window_ns[1] - t0
    else:
        spans = [h for h in host if h[2] == window_span]
        if not spans:
            seen = {name: sum(e[2].startswith("bench/") for e in evs)
                    for name, evs in host_lines.items()}
            raise ValueError(f"trace has no host span {window_span!r}; "
                             f"benchmark spans per host line: {seen}")
        w0 = min(s for s, _, _ in spans)
        w1 = max(s + d for s, d, _ in spans)

    ops: List[DeviceOp] = []
    busy: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    host_sorted = sorted(host)
    host_starts = [h[0] for h in host_sorted]
    for dev, lines in devices:
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                       e.name.split("(")[0])
                      for e in lines.get("XLA Modules", []))
        mstarts = [m[0] for m in mods]
        evs = [(e.start_ns, e.duration_ns, e.name)
               for e in lines.get("XLA Ops", [])
               if w0 <= e.start_ns < w1]
        own, parent, order = _nesting(evs)
        dev_ops: List[DeviceOp] = [None] * len(evs)
        for i in order:                  # parents before their children
            s, d, text = evs[i]
            k = bisect.bisect_right(mstarts, s) - 1
            mod = mods[k][2] if k >= 0 and s < mods[k][1] else ""
            hit = _EVENT_OP.match(text)
            op = hit.group(1) if hit else text
            path = paths.get(mod, {}).get(op, "")
            if not path and parent[i] >= 0:
                # compiler-made ops inside a loop or branch body carry no
                # metadata; they belong to the enclosing op's scope
                path = dev_ops[parent[i]].path
            dev_ops[i] = DeviceOp(dev, mod, op, path, s, d, own[i])
        ops += dev_ops
        union = _union((max(s, w0), min(s + d, w1)) for s, d, _ in evs)
        busy[dev] = sum(e - s for s, e in union)
        edges = [w0] + [x for iv in union for x in iv] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 - g0 >= min_gap_ns:
                gaps.append((_host_label(host_sorted, host_starts,
                                         (g0 + g1) / 2), g1 - g0))
    return Reduction(ops, (w0, w1), busy, gaps)


def _profile_start_ns(planes) -> Optional[int]:
    """Wall-clock ns at which the profile starts; event times count from
    it (the ``Task Environment`` plane's ``profile_start_time``, which a
    TPU trace has and a CPU trace lacks)."""
    for plane in planes:
        for k, v in getattr(plane, "stats", []):
            if k == "profile_start_time":
                return int(v)
    return None


def _host_label(host, starts, t):
    """What the Python thread was doing at ``t``: the outermost benchmark
    span and the innermost event that cover it."""
    covering = [h for h in host[:bisect.bisect_right(starts, t)]
                if h[0] + h[1] > t]
    if not covering:
        return "host: outside any span"
    bench = [h[2] for h in covering if h[2].startswith("bench/")
             and h[2] != "bench/window"]
    inner = min(covering, key=lambda h: h[1])[2]
    outer = bench[0] if bench else ""
    return f"{outer} > {inner}" if outer and outer != inner else inner


def reduce_file(log_dir: str, hlo_texts: Sequence[str], **kw) -> Reduction:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(find_xplane(log_dir)).planes,
                         hlo_texts, **kw)
