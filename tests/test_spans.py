"""The span recorder (repro.serving.spans) and the spans a live HeroSession
run records at reduced widths."""
import sys
import threading

import jax
import pytest

from repro.api import HeroSession, SessionOptions
from repro.core.events import (ALL_SPANS, CT_LM_FETCHES,
                               CT_LM_KV_BYTES_RESERVED,
                               CT_LM_KV_BYTES_USED, CT_LM_STEPS,
                               CT_RUNTIME_PASSES, SP_EXECUTOR_RUN,
                               SP_LM_CALL, SP_LM_STEP,
                               SP_RUNTIME_DISPATCH_PASS, SP_RUNTIME_POLL,
                               SP_RUNTIME_READY_WAIT, SP_RUNTIME_RUN)
from repro.launch.serve import SERVE_OVERRIDES, build_stage_fns
from repro.rag import default_means, sample_traces
from repro.serving import spans


@pytest.fixture(autouse=True)
def recorder():
    spans.disable()
    spans.clear()
    yield
    spans.disable()
    spans.clear()


class FakeAnnotation:
    opened = []

    def __init__(self, name, **kw):
        FakeAnnotation.opened.append((name, kw))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def annotations(monkeypatch):
    FakeAnnotation.opened = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    return FakeAnnotation.opened


def test_off_records_nothing_and_opens_no_annotation(annotations):
    with spans.span(SP_LM_CALL, ("q0",), width=8) as sp:
        assert sp is None
        spans.record(SP_RUNTIME_READY_WAIT, 1.0, 2.0)
        spans.count(CT_LM_STEPS, 3)
    assert spans.drain() == ([], {})
    assert annotations == []


def test_on_without_annotate_opens_no_annotation(annotations):
    spans.enable()
    with spans.span(SP_LM_CALL):
        pass
    assert len(spans.drain()[0]) == 1 and annotations == []


def test_annotation_carries_the_span_id(annotations):
    spans.enable(annotate=True)
    with spans.span(SP_LM_STEP) as sp:
        pass
    assert annotations == [(f"hero:{SP_LM_STEP}", {"span_id": sp.id})]


def test_nested_spans_get_parent_ids():
    spans.enable()
    with spans.span(SP_LM_CALL, ("q3",), rows=2) as outer:
        with spans.span(SP_LM_STEP) as inner:
            pass
        spans.record(SP_RUNTIME_READY_WAIT, outer.t0, outer.t0)
    recorded, _ = spans.drain()
    by = {s.name: s for s in recorded}
    assert by[SP_LM_CALL].parent == 0
    assert by[SP_LM_STEP].parent == outer.id != inner.id
    # record()ed intervals need not nest in the caller's span
    assert by[SP_RUNTIME_READY_WAIT].parent == 0
    assert by[SP_LM_CALL].qids == ("q3",)
    assert by[SP_LM_CALL].attrs == {"rows": 2}
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1


def test_two_threads_do_not_interleave_stacks():
    spans.enable()
    barrier = threading.Barrier(2)

    def work(name):
        with spans.span(SP_EXECUTOR_RUN, (name,)):
            barrier.wait()          # both outer spans open at once
            with spans.span(SP_LM_CALL, (name,)):
                barrier.wait()

    threads = [threading.Thread(target=work, args=(n,)) for n in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    recorded, _ = spans.drain()
    outer = {s.qids: s.id for s in recorded if s.name == SP_EXECUTOR_RUN}
    for s in recorded:
        if s.name == SP_LM_CALL:
            assert s.parent == outer[s.qids]


def test_counters_add_up():
    spans.enable()
    spans.count(CT_RUNTIME_PASSES)
    spans.count(CT_RUNTIME_PASSES)
    spans.count(CT_LM_STEPS, 7)
    assert spans.drain()[1] == {CT_RUNTIME_PASSES: 2, CT_LM_STEPS: 7}


def test_many_threads_lose_no_count_and_no_span():
    spans.enable()

    def work():
        for _ in range(300):
            with spans.span(SP_LM_STEP):
                spans.count(CT_LM_STEPS)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    recorded, counters = spans.drain()
    assert counters == {CT_LM_STEPS: 32 * 300}
    assert len(recorded) == 32 * 300
    assert len({s.id for s in recorded}) == len(recorded)
    assert all(s.parent == 0 for s in recorded)


def test_drain_empties_the_recorder():
    spans.enable()
    with spans.span(SP_RUNTIME_POLL):
        pass
    spans.count(CT_LM_STEPS)
    recorded, counters = spans.drain()
    assert len(recorded) == 1 and counters == {CT_LM_STEPS: 1}
    assert spans.drain() == ([], {})
    with spans.span(SP_RUNTIME_POLL):
        pass
    spans.clear()
    assert spans.drain() == ([], {})


# -- a live run ---------------------------------------------------------------

# spans of the runtime's own thread, recorded once per pass or run rather
# than on behalf of one query
RUNTIME_WIDE = {SP_RUNTIME_RUN, SP_RUNTIME_DISPATCH_PASS, SP_RUNTIME_POLL}


@pytest.mark.slow
def test_live_w2_records_every_span_for_every_query():
    fns = build_stage_fns(reduced_widths=True)
    traces = sample_traces("finqabench", 2, seed=1)
    sess = HeroSession(world="sd8gen4", family="qwen3", backend="live",
                       means=default_means(traces),
                       options=SessionOptions(
                           coalesce=True, cfg_overrides=SERVE_OVERRIDES),
                       stage_fns=fns)
    for i, t in enumerate(traces):
        sess.submit(t, wf=2, arrival_time=0.02 * i)
    spans.enable()
    sess.run(mode="shared", timeout=300)
    recorded, counters = spans.drain()
    spans.disable()

    assert {s.name for s in recorded} == ALL_SPANS
    kids = {}
    for s in recorded:
        kids.setdefault(s.parent, []).append(s)

    def below(s):
        out = {s.name}
        for c in kids.get(s.id, ()):
            out |= below(c)
        return out

    for q in ("q0", "q1"):
        seen = set()
        for s in recorded:
            if q in s.qids:
                seen |= below(s)
        assert seen == ALL_SPANS - RUNTIME_WIDE, q

    (run,) = [s for s in recorded if s.name == SP_RUNTIME_RUN]
    assert all(s.t0 <= s.t1 for s in recorded)
    # a node is first seen ready by a pass of the run, and launched later
    waits = [s for s in recorded if s.name == SP_RUNTIME_READY_WAIT]
    assert waits and all(run.t0 <= s.t0 <= s.t1 <= run.t1 for s in waits)
    # one fetch per LM call, whatever its steps
    calls = [s for s in recorded if s.name == SP_LM_CALL]
    steps = [s for s in recorded if s.name == SP_LM_STEP]
    assert counters[CT_LM_FETCHES] == len(steps) == len(calls)
    assert counters[CT_RUNTIME_PASSES] == sum(
        1 for s in recorded if s.name == SP_RUNTIME_DISPATCH_PASS)
    assert 0 < counters[CT_LM_KV_BYTES_USED] < counters[
        CT_LM_KV_BYTES_RESERVED]
