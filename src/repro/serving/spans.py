"""Spans and counters of the live runtime, on the host's monotonic clock.

Off by default: every call site then costs one module-global boolean
test.  Turned on, each span records its name, its start and end on
``time.monotonic()``, an id, the id of the span open on the same thread
when it began (its parent), the query ids it serves and its attributes,
into one in-memory list that worker threads append to under a lock.

With ``enable(annotate=True)`` each span also opens a
``jax.profiler.TraceAnnotation`` named ``hero:<name>`` that carries the
span id as its ``span_id`` stat, so a profiler trace shows the span on
the device trace's clock beside the device ops, and a reader can match
it to its record here: the median of (trace start - ``t0``) over the
matched spans places every recorded span on that clock.

    from repro.serving import spans
    spans.enable(annotate=True)
    with spans.span(SP_LM_CALL, width=8) as sp:   # sp is None when off
        ...
    spans.record(SP_RUNTIME_READY_WAIT, t_ready, time.monotonic())
    spans.count(CT_LM_STEPS, 7)
    recorded, counters = spans.drain()

Names are the ``SP_*`` (spans) and ``CT_*`` (counters) constants of
``repro.core.events``; this module imports nothing of the program.
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import Dict, List, Sequence, Tuple

_on = False
_annotation = None       # jax.profiler.TraceAnnotation when annotating
_lock = threading.Lock()
_spans: List["Span"] = []
_counters: Dict[str, float] = {}
_ids = itertools.count(1)
_local = threading.local()


class Span:
    """One recorded span; ``parent`` is 0 at a thread's outermost span
    and for spans recorded with :func:`record`."""

    __slots__ = ("name", "t0", "t1", "id", "parent", "qids", "attrs")

    def __init__(self, name: str, t0: float, t1: float, span_id: int,
                 parent: int, qids: Tuple, attrs: dict):
        self.name, self.t0, self.t1 = name, t0, t1
        self.id, self.parent = span_id, parent
        self.qids, self.attrs = qids, attrs


class _Off:
    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_OFF = _Off()


class _Open:
    __slots__ = ("span", "ann", "stack")

    def __init__(self, name: str, qids: Sequence, attrs: dict):
        self.span = Span(name, 0.0, 0.0, next(_ids), 0, tuple(qids), attrs)
        self.ann = None

    def __enter__(self) -> Span:
        sp = self.span
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        self.stack = stack
        sp.parent = stack[-1] if stack else 0
        stack.append(sp.id)
        if _annotation is not None:
            self.ann = _annotation(f"hero:{sp.name}", span_id=sp.id)
            self.ann.__enter__()
        sp.t0 = time.monotonic()
        return sp

    def __exit__(self, *exc):
        sp = self.span
        sp.t1 = time.monotonic()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        self.stack.pop()
        with _lock:
            _spans.append(sp)
        return False


def span(name: str, qids: Sequence = (), **attrs):
    """Context manager that records the span it encloses; it yields the
    :class:`Span` (whose ``attrs`` may still be set before the block
    ends), or None when the recorder is off."""
    if not _on:
        return _OFF
    return _Open(name, qids, attrs)


def record(name: str, t0: float, t1: float, qids: Sequence = (),
           **attrs) -> None:
    """Record a span whose start and end are not lexically scoped (no
    annotation, no parent)."""
    if not _on:
        return
    sp = Span(name, t0, t1, next(_ids), 0, tuple(qids), attrs)
    with _lock:
        _spans.append(sp)


def count(name: str, n: float = 1) -> None:
    if not _on:
        return
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def enabled() -> bool:
    return _on


def enable(annotate: bool = False) -> None:
    global _on, _annotation
    if annotate:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    else:
        _annotation = None
    _on = True


def disable() -> None:
    global _on, _annotation
    _on, _annotation = False, None


def clear() -> None:
    with _lock:
        _spans.clear()
        _counters.clear()


def drain() -> Tuple[List[Span], Dict[str, float]]:
    """-> (spans, counters) recorded since the last clear or drain, and
    empties the recorder."""
    global _spans, _counters
    with _lock:
        out = _spans, _counters
        _spans, _counters = [], {}
    return out

