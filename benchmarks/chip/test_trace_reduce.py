"""The trace reduction on a small recorded trace (CPU only)."""
import json
from pathlib import Path

import pytest

import trace_reduce

FIXTURE = Path(__file__).parent / "fixtures" / "trace_small.json"


@pytest.fixture
def events():
    return json.loads(FIXTURE.read_text())


def test_busy_union_and_idle_share(events):
    r = trace_reduce.reduce(events, events["window_s"])
    # [0, 150] + [300, 510] + [800, 1000] ns: overlapping and touching
    # ops count once
    assert r["busy_s"] == pytest.approx(560e-9)
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(1 - 560 / 1200)


def test_kernel_events_sum(events):
    r = trace_reduce.reduce(events, events["window_s"])
    assert r["kernel_events"] == 1
    assert r["kernel_s"] == pytest.approx(200e-9)


def test_device_ops_by_program(events):
    r = trace_reduce.reduce(events, events["window_s"])
    ops = dict(r["device_ops"])
    assert ops["jit_topk_retrieval"] == pytest.approx(210e-9)
    # overlapping (nested) ops of one program count once
    assert ops["jit_decode"] == pytest.approx(150e-9)
    assert r["device_ops"][0][0] == "jit_topk_retrieval"


def test_idle_gaps_by_host_span(events):
    r = trace_reduce.reduce(events, events["window_s"])
    gaps = dict(r["idle_gaps"])
    # gap [150, 300]: no span open at 225; gap [510, 800]: rerank at 655
    assert gaps[trace_reduce.NO_STAGE] == pytest.approx(150e-9)
    assert gaps["stage:rerank"] == pytest.approx(290e-9)


def test_module_from_the_modules_line():
    ops = [["fusion.9", 120, 5, ""], ["fusion.8", 400, 5, ""]]
    trace_reduce._fill_modules(ops, [["jit_decode", 100, 50, ""]])
    assert ops[0][3] == "jit_decode" and ops[1][3] == ""


def test_no_device_plane_reads_nothing():
    r = trace_reduce.reduce({"devices": {}, "host": []}, 1.0)
    assert r["busy_s"] == 0 and r["kernel_events"] == 0
