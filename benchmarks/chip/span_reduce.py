"""The program's own spans (``repro.serving.spans``) against a profiler
trace: one clock for both, the device's idle time put down to what the
program was doing, and the per-layer numbers the spans give.

``load_hero(path)`` reads the ``hero:<name>`` annotations the recorder
opens (``enable(annotate=True)``) from an ``.xplane.pb``, each with its
``span_id`` stat.  Matching them by id to the recorded spans gives the
offset between the host's ``time.monotonic()`` and the trace's clock
(``clock``); every recorded span, inside the traced seconds or not, is
then placed on the trace's clock as ``t0 + offset``.  Device op events
come from ``trace_reduce.load``; their merged intervals are the device's
busy time (``busy``).  With more than one device plane, busy means some
device is busy.

Recorded spans are any objects with ``name``, ``t0``, ``t1`` (monotonic
seconds), ``id``, ``parent`` (0 at a thread's outermost span), ``qids``
and ``attrs``; queries are the harness's records (``due``, ``last``).
Trace times are nanoseconds, as ``trace_reduce`` keeps them.  Nothing
here needs JAX but ``load_hero``; span names come from the program's
registry (``repro.core.events``).
"""
from __future__ import annotations

import statistics
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import trace_reduce
from repro.core.events import (CT_LM_KV_BYTES_RESERVED as KV_RESERVED,
                               CT_LM_KV_BYTES_USED as KV_USED,
                               SP_EXECUTOR_QUEUE as EXEC_QUEUE,
                               SP_EXECUTOR_RUN as EXEC_RUN,
                               SP_LM_CALL as LM_CALL, SP_LM_STEP as LM_STEP,
                               SP_RUNTIME_DISPATCH_PASS as PASS,
                               SP_RUNTIME_POLL as POLL,
                               SP_RUNTIME_READY_WAIT as READY_WAIT,
                               SP_RUNTIME_RUN as RUN)

HERO_PREFIX = "hero:"
# spans of waiting rather than working, lowest first: a gap is put down
# to one of these only where no span of work is open on any thread (a
# ready node not yet launched says more than the runtime's poll)
WAITING = (RUN, POLL, EXEC_QUEUE, READY_WAIT)
NO_SPAN_WAITING = "(no program span, a due query unanswered)"
NO_SPAN_IDLE = "(no program span, no query waiting)"
NO_ATTR = "(runtime)"        # a span with no stage or PU above it

Interval = Tuple[float, float]


# -- reading and the clock ---------------------------------------------------

def load_hero(path: str) -> List[list]:
    """xplane.pb -> ``[name, start_ns, duration_ns, span_id]`` of every
    ``hero:`` host annotation (name without the prefix)."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(HERO_PREFIX):
                    sid = dict(ev.stats).get("span_id")
                    if sid is not None:
                        out.append([ev.name[len(HERO_PREFIX):],
                                    float(ev.start_ns),
                                    float(ev.duration_ns), int(sid)])
    return out


def clock(hero: Sequence[list], recorded: Sequence) -> Optional[dict]:
    """Offset of the trace's clock from ``time.monotonic()``, in seconds:
    the median over matched spans of (trace start - ``t0``); with its
    spread (interquartile range and full range) and the median gap
    between a matched span's trace and monotonic durations."""
    by_id = {s.id: s for s in recorded}
    offsets, gaps = [], []
    for name, start, dur, sid in hero:
        s = by_id.get(sid)
        if s is None or s.name != name:
            continue
        offsets.append(start * 1e-9 - s.t0)
        gaps.append(abs(dur * 1e-9 - (s.t1 - s.t0)))
    if not offsets:
        return None
    q1, _, q3 = (statistics.quantiles(offsets, n=4) if len(offsets) > 1
                 else (offsets[0],) * 3)
    return {"offset_s": statistics.median(offsets), "matched": len(offsets),
            "offset_iqr_s": q3 - q1,
            "offset_range_s": max(offsets) - min(offsets),
            "duration_gap_median_s": statistics.median(gaps)}


def busy(devices: Dict[str, list]) -> List[Interval]:
    """Merged ``(start_ns, end_ns)`` intervals in which some op ran."""
    return trace_reduce.union([(s, s + d) for evs in devices.values()
                               for _, s, d, _ in evs])


def on_trace_clock(t: float, offset: float) -> float:
    """A monotonic time in seconds -> trace nanoseconds."""
    return (t + offset) * 1e9


# -- interval arithmetic -------------------------------------------------------

def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def complement(intervals: Sequence[Interval], lo: float, hi: float
               ) -> List[Interval]:
    """The parts of ``[lo, hi]`` that the sorted, merged ``intervals``
    leave uncovered."""
    out, at = [], lo
    for a, b in clip(intervals, lo, hi):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if at < hi:
        out.append((at, hi))
    return out


def intersect(x: Sequence[Interval], y: Sequence[Interval]
              ) -> List[Interval]:
    """Intersection of two sorted, merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(x) and j < len(y):
        a, b = max(x[i][0], y[j][0]), min(x[i][1], y[j][1])
        if b > a:
            out.append((a, b))
        if x[i][1] < y[j][1]:
            i += 1
        else:
            j += 1
    return out


def length(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def waiting(queries: Sequence, offset: float, end: float) -> List[Interval]:
    """Trace-clock intervals in which some due query is unanswered: each
    query from its due time to its last answer token (or ``end``, a
    monotonic time, if its answer never streamed in full)."""
    return trace_reduce.union([
        (on_trace_clock(r.due, offset),
         on_trace_clock(r.last if r.answered else end, offset))
        for r in queries])


# -- the span tree ------------------------------------------------------------

def _depths(recorded: Sequence) -> Dict[int, int]:
    """Span id -> ancestors among the recorded spans (0 at a root)."""
    parent = {s.id: s.parent for s in recorded}
    depth: Dict[int, int] = {}
    for s in recorded:
        chain, i = [], s.id
        while i in parent and i not in depth:
            chain.append(i)
            i = parent[i]
        d = depth.get(i, -1)
        for j in reversed(chain):
            d += 1
            depth[j] = d
    return depth


def _inherited(recorded: Sequence, key: str) -> Dict[int, str]:
    """Span id -> ``attrs[key]`` of the span or its nearest ancestor."""
    by_id = {s.id: s for s in recorded}
    out: Dict[int, str] = {}
    for s in recorded:
        x = s
        while x is not None and key not in x.attrs:
            x = by_id.get(x.parent)
        out[s.id] = str(x.attrs[key]) if x is not None else NO_ATTR
    return out


def innermost(recorded: Sequence, offset: float, lo: float, hi: float
              ) -> List[Tuple[float, float, object]]:
    """Trace-clock segments of ``[lo, hi]``, each with the innermost span
    open on any thread then (or None): the deepest, the latest opened
    among equally deep ones, and a span of ``WAITING`` only where no
    other is open."""
    depth = _depths(recorded)
    wait = {name: i for i, name in enumerate(WAITING)}
    rank = {s.id: (wait.get(s.name, len(WAITING)), depth[s.id], s.t0)
            for s in recorded}
    edges = []
    for s in recorded:
        a, b = on_trace_clock(s.t0, offset), on_trace_clock(s.t1, offset)
        if b > lo and a < hi and b > a:
            edges.append((max(a, lo), 1, s))
            edges.append((min(b, hi), 0, s))
    edges.sort(key=lambda e: (e[0], e[1]))
    out, active, at = [], {}, lo
    for t, opening, s in edges:
        if t > at:
            top = max(active.values(), key=lambda x: rank[x.id],
                      default=None)
            out.append((at, t, top))
            at = t
        if opening:
            active[s.id] = s
        else:
            active.pop(s.id, None)
    if at < hi:
        out.append((at, hi, None))
    return out


def attribute(idle: Sequence[Interval], recorded: Sequence, offset: float,
              queue: Sequence[Interval]) -> Dict[str, Dict[str, float]]:
    """Device idle seconds by the innermost program span open (``span``),
    and by the ``stage`` and the ``pu`` of that span or its nearest
    ancestor that has one (the runtime thread's own spans have neither);
    in each table, idle time with no span open is split by whether a due
    query was unanswered (``queue``)."""
    if not idle:
        return {"span": {}, "stage": {}, "pu": {}}
    stage = _inherited(recorded, "stage")
    pu = _inherited(recorded, "pu")
    tables: Dict[str, Dict[str, float]] = {
        k: defaultdict(float) for k in ("span", "stage", "pu")}
    segs = innermost(recorded, offset, idle[0][0], idle[-1][1])
    j = 0
    # the segments partition the idle range, so each piece lies in one
    for a, b in intersect(idle, [(a, b) for a, b, _ in segs]):
        while segs[j][1] <= a:
            j += 1
        s = segs[j][2]
        sec = (b - a) * 1e-9
        if s is None:
            held = length(intersect([(a, b)], queue)) * 1e-9
            for t in tables.values():
                t[NO_SPAN_WAITING] += held
                t[NO_SPAN_IDLE] += sec - held
            continue
        tables["span"][s.name] += sec
        tables["stage"][stage[s.id]] += sec
        tables["pu"][pu[s.id]] += sec
    return {k: dict(sorted(v.items(), key=lambda kv: -kv[1]))
            for k, v in tables.items()}


def lm_device_by_stage(recorded: Sequence, busy_iv: Sequence[Interval],
                       offset: float, lo: float, hi: float
                       ) -> Dict[str, float]:
    """Device busy seconds inside ``lm.call`` spans, by the stage of the
    dispatch that made the call (refine and chat share one program); two
    calls open at once both count the time."""
    stage = _inherited(recorded, "stage")
    out: Dict[str, float] = defaultdict(float)
    for s in recorded:
        if s.name == LM_CALL:
            iv = clip([(on_trace_clock(s.t0, offset),
                        on_trace_clock(s.t1, offset))], lo, hi)
            out[stage[s.id]] += length(intersect(busy_iv, iv)) * 1e-9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


# -- the per-layer numbers ----------------------------------------------------

def _in(s, window: Interval) -> bool:
    return window[0] <= s.t0 and s.t1 <= window[1]


def dispatch_pass_share(recorded: Sequence, window: Interval
                        ) -> Optional[float]:
    """Self time of ``runtime.dispatch_pass`` spans in the window over
    the window's wall, in percent."""
    kids: Dict[int, list] = defaultdict(list)
    for s in recorded:
        kids[s.parent].append(s)
    passes = [s for s in recorded if s.name == PASS and _in(s, window)]
    if not passes or window[1] <= window[0]:
        return None
    own = 0.0
    for s in passes:
        covered = trace_reduce.union(
            [(max(c.t0, s.t0), min(c.t1, s.t1)) for c in kids[s.id]
             if c.t1 > s.t0 and c.t0 < s.t1])
        own += (s.t1 - s.t0) - length(covered)
    return 100.0 * own / (window[1] - window[0])


def ready_wait_p90_ms(recorded: Sequence, window: Interval
                      ) -> Optional[float]:
    """90th percentile, in ms, of the wait from a node's first sight in
    the ready pool to its launch, over the window's non-io dispatches."""
    w = [(s.t1 - s.t0) * 1e3 for s in recorded
         if s.name == READY_WAIT and s.attrs.get("pu") != "io"
         and window[0] <= s.t1 <= window[1]]
    return float(np.percentile(w, 90)) if w else None


def wasted_dispatch_share(recorded: Sequence, window: Interval
                          ) -> Optional[float]:
    """Wall of ``executor.run`` spans whose task was cancelled (straggler
    copies that ran on) over the wall of every ``executor.run``, %."""
    runs = [s for s in recorded if s.name == EXEC_RUN and _in(s, window)]
    total = sum(s.t1 - s.t0 for s in runs)
    if total <= 0:
        return None
    wasted = sum(s.t1 - s.t0 for s in runs if s.attrs.get("cancelled"))
    return 100.0 * wasted / total


def lm_step_idle_ms(recorded: Sequence, busy_iv: Sequence[Interval],
                    offset: float, lo: float, hi: float) -> Optional[float]:
    """Device idle time inside the ``lm.call`` spans that lie wholly in
    the traced window ``[lo, hi]`` (trace ns), over the ``lm.step`` spans
    of those calls, in ms."""
    steps: Dict[int, int] = defaultdict(int)
    for s in recorded:
        if s.name == LM_STEP:
            steps[s.parent] += 1
    idle, n = 0.0, 0
    for s in recorded:
        if s.name != LM_CALL or not steps[s.id]:
            continue
        a, b = on_trace_clock(s.t0, offset), on_trace_clock(s.t1, offset)
        if a < lo or b > hi:
            continue
        idle += length(complement(busy_iv, a, b)) * 1e-9
        n += steps[s.id]
    return 1e3 * idle / n if n else None


def lm_kv_used_share(counters: Dict[str, float]) -> Optional[float]:
    """KV-cache bytes the LM calls wrote over the bytes they allocated,
    in percent."""
    reserved = counters.get(KV_RESERVED, 0)
    return 100.0 * counters.get(KV_USED, 0) / reserved if reserved else None


def idle_with_work_share(busy_iv: Sequence[Interval],
                         queue: Sequence[Interval], lo: float, hi: float
                         ) -> Optional[float]:
    """Share of the traced window ``[lo, hi]`` in which the device is
    idle while some due query is unanswered, in percent."""
    if hi <= lo:
        return None
    idle = complement(busy_iv, lo, hi)
    return 100.0 * length(intersect(idle, queue)) / (hi - lo)


# -- one traced run -----------------------------------------------------------

def reduce(recorded: Sequence, counters: Dict[str, float],
           hero: Sequence[list], devices: Dict[str, list],
           window: Interval, trace_window: Optional[Interval],
           queries: Sequence) -> dict:
    """Every number above for one run: ``window`` is the measured window
    and ``trace_window`` the profiler's, both monotonic seconds.  The
    numbers that need the device trace are None without one."""
    out = {"dispatch_pass_share": dispatch_pass_share(recorded, window),
           "ready_wait_p90_ms": ready_wait_p90_ms(recorded, window),
           "wasted_dispatch_share": wasted_dispatch_share(recorded, window),
           "lm_kv_used_share": lm_kv_used_share(counters),
           "lm_step_idle_ms": None, "idle_with_work_share": None,
           "clock": clock(hero, recorded), "idle": None,
           "lm_device_by_stage": None}
    busy_iv = busy(devices)
    if out["clock"] is None or not busy_iv or trace_window is None:
        return out
    off = out["clock"]["offset_s"]
    lo, hi = (on_trace_clock(t, off) for t in trace_window)
    queue = waiting(queries, off, window[1])
    idle = complement(busy_iv, lo, hi)
    out.update(
        lm_step_idle_ms=lm_step_idle_ms(recorded, busy_iv, off, lo, hi),
        idle_with_work_share=idle_with_work_share(busy_iv, queue, lo, hi),
        idle=dict(attribute(idle, recorded, off, queue),
                  total_s=length(idle) * 1e-9),
        lm_device_by_stage=lm_device_by_stage(recorded, busy_iv, off,
                                              lo, hi))
    return out


def table(reduced: dict) -> List[str]:
    """Lines for standard error: the clock, the idle attribution and the
    device time inside LM calls."""
    c = reduced.get("clock")
    lines = []
    if c:
        lines.append(f"spans: {c['matched']} matched in the trace, offset "
                     f"{c['offset_s']:.9f} s (IQR {c['offset_iqr_s']:.3e}, "
                     f"range {c['offset_range_s']:.3e} s), median duration "
                     f"gap {c['duration_gap_median_s'] * 1e3:.6f} ms")
    idle = reduced.get("idle")
    if idle:
        total = idle["total_s"] or 1.0
        for key in ("span", "stage", "pu"):
            lines.append(f"spans: device idle {idle['total_s']:.6f} s "
                         f"by {key}:")
            lines += [f"spans:   {sec:.6f} s  {100 * sec / total:5.1f}%  "
                      f"{name}" for name, sec in idle[key].items()]
    if reduced.get("lm_device_by_stage"):
        lines.append("spans: device busy inside lm.call by stage:")
        lines += [f"spans:   {sec:.6f} s  {name}"
                  for name, sec in reduced["lm_device_by_stage"].items()]
    return lines
