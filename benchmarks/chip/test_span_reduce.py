"""Program spans against a device trace (``span_reduce``) on a small
synthetic run, and a reduced-width run of a cell with the recorder on
(``spanrun.run_one``), on the CPU."""
from types import SimpleNamespace

import pytest

import rehearsal
import span_reduce as sr
import spanrun
from repro.serving.spans import Span

US = 1e-6
BASE = 999.0      # monotonic seconds at trace time 0
OFFSET = -BASE    # trace seconds - monotonic seconds


def sp(i, name, a, b, parent=0, **attrs):
    """A recorded span from ``a`` to ``b`` microseconds of trace time."""
    return Span(name, BASE + a * US, BASE + b * US, i, parent, (), attrs)


# device ops busy [0, 100], [300, 500], [800, 1000] us; idle [100, 300]
# and [500, 800]; one query due at 0 and answered at 780 us
DEVICES = {"/device:TPU:0": [["fusion.1", 0, 60e3, "jit_decode"],
                             ["fusion.2", 40e3, 60e3, "jit_decode"],
                             ["fusion.3", 300e3, 200e3, "jit_decode"],
                             ["fusion.4", 800e3, 200e3, "jit_prefill"]]}
SPANS = [
    # the runtime's thread
    sp(1, "runtime.run", 0, 750),
    sp(2, "runtime.poll", 100, 200, parent=1),
    sp(3, "runtime.dispatch_pass", 200, 250, parent=1),
    # a PU worker
    sp(4, "executor.run", 50, 700, stage="refine_decode", pu="gpu",
       cancelled=False),
    sp(5, "lm.call", 60, 650, parent=4, rows=1),
    sp(6, "lm.step", 120, 180, parent=5),
    sp(7, "lm.fetch", 150, 180, parent=6),
    sp(8, "lm.step", 500, 560, parent=5),
    # a node waiting in the ready pool, and one in its PU's queue
    sp(9, "runtime.ready_wait", 0, 720, pu="npu", stage="rerank"),
    sp(10, "executor.queue", 700, 740, pu="npu", stage="rerank"),
]
QUERY = SimpleNamespace(due=BASE, last=BASE + 780 * US, answered=True)
LO, HI = 0.0, 1000e3


def busy():
    return sr.busy(DEVICES)


def queue():
    return sr.waiting([QUERY], OFFSET, BASE + 1)


def test_busy_merges_overlapping_ops():
    assert busy() == [(0, 100e3), (300e3, 500e3), (800e3, 1000e3)]


def test_offset_recovered_from_matched_ids():
    hero = [["lm.call", 60e3, 590e3, 5],
            ["lm.step", 120e3 + 2, 60e3, 6],      # 2 ns late
            ["lm.fetch", 150e3 - 1, 30e3 + 1, 7],
            ["lm.step", 500e3, 60e3 + 3, 8],
            ["lm.step", 10e3, 1e3, 5],            # the id of another span
            ["lm.step", 10e3, 1e3, 99]]           # an id never recorded
    c = sr.clock(hero, SPANS)
    assert c["matched"] == 4
    assert c["offset_s"] == pytest.approx(OFFSET, abs=2e-9)
    assert c["offset_range_s"] == pytest.approx(3e-9, abs=1e-10)
    assert c["duration_gap_median_s"] < 3e-9
    assert sr.clock(hero[-2:], SPANS) is None


def test_idle_gaps_by_innermost_span_on_any_thread():
    idle = sr.complement(busy(), LO, HI)
    t = sr.attribute(idle, SPANS, OFFSET, queue())
    us = {k: v / US for k, v in t["span"].items()}
    # the poll is the runtime waiting: the worker's LM call wins over it;
    # an equally deep pass opened later wins over the call; where no work
    # is open, a ready node's wait wins over a queued task and the run
    assert us == pytest.approx({
        "lm.call": 180, "lm.step": 90, "lm.fetch": 30,
        "runtime.dispatch_pass": 50, "executor.run": 50,
        "runtime.ready_wait": 20, "executor.queue": 20,
        "runtime.run": 10, sr.NO_SPAN_WAITING: 30, sr.NO_SPAN_IDLE: 20})
    assert sum(t["span"].values()) == pytest.approx(500 * US)
    assert t["stage"]["refine_decode"] / US == pytest.approx(350)
    assert t["stage"]["rerank"] / US == pytest.approx(40)
    assert t["stage"][sr.NO_ATTR] / US == pytest.approx(60)
    assert t["pu"]["gpu"] / US == pytest.approx(350)


def test_lm_device_time_by_stage():
    got = sr.lm_device_by_stage(SPANS, busy(), OFFSET, LO, HI)
    assert got["refine_decode"] / US == pytest.approx(240)


# -- one test per per-layer number ---------------------------------------------

def test_dispatch_pass_share():
    window = (BASE, BASE + 1000 * US)
    assert sr.dispatch_pass_share(SPANS, window) == pytest.approx(5.0)
    # a child's time is not the pass's own
    kids = SPANS + [sp(11, "vdb.add", 210, 230, parent=3)]
    assert sr.dispatch_pass_share(kids, window) == pytest.approx(3.0)
    assert sr.dispatch_pass_share(SPANS, (BASE + 1, BASE + 2)) is None


def test_ready_wait_p90_ms():
    waits = [sp(10 + i, "runtime.ready_wait", 0, (i + 1) * 1e3, pu="gpu")
             for i in range(10)]
    waits.append(sp(30, "runtime.ready_wait", 0, 100e3, pu="io"))
    window = (BASE, BASE + 1)
    assert sr.ready_wait_p90_ms(waits, window) == pytest.approx(9.1)
    assert sr.ready_wait_p90_ms(waits, (BASE + 1, BASE + 2)) is None


def test_wasted_dispatch_share():
    runs = [sp(1, "executor.run", 0, 300, cancelled=False),
            sp(2, "executor.run", 100, 200, cancelled=True),
            sp(3, "executor.run", 300, 400, cancelled=False)]
    window = (BASE, BASE + 1)
    assert sr.wasted_dispatch_share(runs, window) == pytest.approx(20.0)
    assert sr.wasted_dispatch_share(runs[:0], window) is None


def test_lm_step_idle_ms():
    # the call [60, 650] us holds 240 us of device work and two steps
    got = sr.lm_step_idle_ms(SPANS, busy(), OFFSET, LO, HI)
    assert got == pytest.approx((590 - 240) / 2 * 1e-3)
    # a call the traced window cuts is left out
    assert sr.lm_step_idle_ms(SPANS, busy(), OFFSET, 100e3, HI) is None


def test_lm_kv_used_share():
    assert sr.lm_kv_used_share({sr.KV_USED: 24, sr.KV_RESERVED: 2400}) \
        == pytest.approx(1.0)
    assert sr.lm_kv_used_share({}) is None


def test_idle_with_work_share():
    got = sr.idle_with_work_share(busy(), queue(), LO, HI)
    # idle [100, 300] + [500, 780] while the query waits, of 1000 us
    assert got == pytest.approx(48.0)


def test_reduce_without_a_trace_gives_host_numbers_only():
    r = sr.reduce(SPANS, {sr.KV_USED: 1, sr.KV_RESERVED: 4}, [], {},
                  (BASE, BASE + 1000 * US), None, [QUERY])
    assert r["dispatch_pass_share"] == pytest.approx(5.0)
    assert r["lm_kv_used_share"] == pytest.approx(25.0)
    assert r["idle_with_work_share"] is None and r["idle"] is None


def test_reduce_with_a_trace():
    hero = [[s.name, (s.t0 + OFFSET) * 1e9, (s.t1 - s.t0) * 1e9, s.id]
            for s in SPANS]
    r = sr.reduce(SPANS, {}, hero, DEVICES, (BASE, BASE + 1000 * US),
                  (BASE, BASE + 1000 * US), [QUERY])
    assert r["idle_with_work_share"] == pytest.approx(48.0)
    assert r["idle"]["total_s"] == pytest.approx(500 * US)
    assert any("lm.fetch" in line for line in sr.table(r))


# -- a cell, with the recorder on, at reduced widths ---------------------------

def test_closed_loop_run_with_spans_on():
    import jax

    cell = rehearsal.reduced_cell("qwen3-w2-single", max_queries=40)

    def hook(pipe):
        rehearsal.interpret_topk(pipe)

    out = spanrun.run_one(cell, 2 ** 31 + 5, 3.0, True, True,
                          jax.devices()[0], pipe_hook=hook)
    assert out["correct"] is True, out["checks"]
    got = out["spans"]
    for name in ("dispatch_pass_share", "ready_wait_p90_ms",
                 "wasted_dispatch_share", "lm_kv_used_share"):
        assert got[name] is not None, name
    # the CPU has no TPU plane: nothing needs the device's intervals
    assert got["lm_step_idle_ms"] is None and got["idle"] is None
    # the host annotations come back matched, on one clock
    c = got["clock"]
    assert c["matched"] > 100
    assert c["duration_gap_median_s"] < 1e-4
    assert c["offset_iqr_s"] < 1e-3
    assert got["counters"]["lm.kv_bytes_reserved"] > 0
