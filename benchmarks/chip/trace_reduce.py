"""Reduction of a profiler trace to the benchmark's device numbers.

``load(path)`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into
plain event lists; ``reduce(events, window_s)`` computes from them, with
no JAX, what the metrics need:

- ``busy_s``: the union of the intervals in which an operation ran on a
  device, averaged over the devices traced;
- ``kernel_s``: the summed device durations of the top-k search kernel's
  events (an op of a ``topk_retrieval`` program that is its custom call);
- ``device_ops``: device time by program (XLA module), largest first;
- ``idle_gaps``: device idle time between operations, attributed to the
  ``stage:<name>`` host span open at the gap's midpoint, largest first.

Events are ``[name, start_ns, duration_ns, module]`` lists (host spans
have no module), so a small recorded trace is a JSON fixture.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
STAGE_PREFIX = "stage:"
KERNEL_MODULE = "topk_retrieval"
NO_STAGE = "no stage fn (scheduler/runtime)"


def load(path: str) -> dict:
    """xplane.pb -> {"devices": {plane: [event, ...]}, "host": [...]}.
    An op event's module is its ``hlo_module`` stat, or else the program
    on the plane's ``XLA Modules`` line whose interval holds its start."""
    from jax.profiler import ProfileData

    out: dict = {"devices": {}, "host": []}
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            ops, modules = [], []
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                for ev in line.events:
                    stats = dict(ev.stats)
                    item = [ev.name, float(ev.start_ns),
                            float(ev.duration_ns),
                            str(stats.get("hlo_module", ""))]
                    (ops if line.name == OPS_LINE else modules).append(item)
            _fill_modules(ops, modules)
            out["devices"][plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(STAGE_PREFIX):
                        out["host"].append([ev.name, float(ev.start_ns),
                                            float(ev.duration_ns)])
    return out


def _fill_modules(ops: List[list], modules: List[list]):
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    for op in ops:
        if op[3]:
            continue
        i = bisect.bisect_right(starts, op[1]) - 1
        if i >= 0 and op[1] <= modules[i][1] + modules[i][2]:
            op[3] = modules[i][0]


def summary(events: dict, top: int = 12) -> List[str]:
    """Lines that show what a trace holds: per device plane the event
    count and the ops that took most time, with their programs."""
    lines = [f"trace: {len(events['host'])} host stage spans"]
    for plane, evs in events["devices"].items():
        t: Dict[tuple, float] = defaultdict(float)
        for name, _, d, module in evs:
            t[(name, module)] += d * 1e-9
        lines.append(f"trace: {plane}: {len(evs)} op events, "
                     f"{sum(1 for e in evs if is_kernel(e))} kernel events")
        for (name, module), sec in sorted(t.items(),
                                          key=lambda kv: -kv[1])[:top]:
            lines.append(f"trace:   {sec:.6f} s  {name[:100]}  [{module}]")
        for name in sorted({e[0] for e in evs if is_kernel(e)})[:3]:
            lines.append(f"trace:   kernel event: {name[:300]}")
    return lines


def union(intervals: List[tuple]) -> List[tuple]:
    """Sorted, merged (start, end) intervals."""
    merged: List[list] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [tuple(m) for m in merged]


def is_kernel(ev) -> bool:
    """An op of the top-k search program that is its Pallas custom call."""
    name, module = ev[0].lower(), ev[3]
    return KERNEL_MODULE in module and any(
        w in name for w in ("custom", "pallas", "topk", "kernel"))


def _stages_at(host: List[list], times: List[float]) -> List[str]:
    """For each of the sorted ``times``, the innermost (latest-opened)
    host stage span open then, or ``NO_STAGE``."""
    spans = sorted(host, key=lambda h: h[1])
    out, active, i = [], [], 0
    for t in times:
        while i < len(spans) and spans[i][1] <= t:
            active.append(spans[i])
            i += 1
        active = [h for h in active if h[1] + h[2] >= t]
        out.append(max(active, key=lambda h: h[1])[0] if active
                   else NO_STAGE)
    return out


def reduce(events: dict, window_s: float, top: int = 10) -> dict:
    devices = events["devices"]
    if not devices:
        return {"busy_s": 0.0, "window_s": window_s, "kernel_s": 0.0,
                "kernel_events": 0, "device_ops": [], "idle_gaps": []}
    busy, kernel_s, n_kernel = 0.0, 0.0, 0
    by_module: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    for evs in devices.values():
        spans = union([(s, s + d) for _, s, d, _ in evs])
        busy += sum(e - s for s, e in spans) * 1e-9
        per_module: Dict[str, list] = defaultdict(list)
        for ev in evs:
            # a loop's event spans the events of its body: count the
            # union of a program's op intervals, not their sum
            per_module[ev[3] or ev[0]].append((ev[1], ev[1] + ev[2]))
            if is_kernel(ev):
                kernel_s += ev[2] * 1e-9
                n_kernel += 1
        for module, iv in per_module.items():
            by_module[module] += sum(e - s for s, e in union(iv)) * 1e-9
        holes = [(e0, s1) for (_, e0), (s1, _) in zip(spans, spans[1:])]
        names = _stages_at(events["host"], [(a + b) / 2 for a, b in holes])
        for (a, b), name in zip(holes, names):
            gaps[name] += (b - a) * 1e-9
    n = len(devices)
    rank = sorted(by_module.items(), key=lambda kv: -kv[1])[:top]
    gap_rank = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy / n, "window_s": window_s,
            "kernel_s": kernel_s / n, "kernel_events": n_kernel // n,
            "device_ops": [[k, v / n] for k, v in rank],
            "idle_gaps": [[k, v / n] for k, v in gap_rank]}
