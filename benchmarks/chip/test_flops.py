"""Operation and byte counts of decode_mfu and topk_retrieval_roofline,
and that a share is reported as read, never clipped (CPU only)."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

import flops
import harness
import peaks
import trace_reduce

CONFIG = json.loads((Path(__file__).parent / "configs" / "qwen3-rag.json")
                    .read_text())
V5E = peaks.peaks("TPU v5 lite")


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


@pytest.mark.parametrize("role", sorted(CONFIG["models"]))
def test_param_count_matches_the_program(role):
    from repro.configs import get_family

    m = CONFIG["models"][role]
    cfg = get_family("qwen3")[role]
    # the program's analytic count leaves out the final norm's d scales
    assert flops.dense_param_count(m) == m["params"]
    assert m["params"] == cfg.param_count() + cfg.d_model


def test_decode_flops():
    m = CONFIG["models"]["chat"]
    assert flops.decode_flops(m, 7) == 2 * 7 * m["params"]


def test_topk_least_time_full_store_is_memory_bound():
    t, bound = flops.topk_least_time(65536, 1024, 1, 4, V5E)
    assert bound == "memory"
    assert t == pytest.approx((65536 * 1024 * 4 + 1024 * 4) / 819e9)


def test_topk_least_time_compute_bound_for_many_queries():
    t, bound = flops.topk_least_time(65536, 1024, 4096, 1, V5E)
    assert bound == "compute"
    assert t == pytest.approx(2 * 65536 * 1024 * 4096 / 197e12)


def _ctx(kernel_s, kernel_events=1):
    span = harness.Span("vsearch", 0.0, 1.0, [0], 1, 0, 65536)
    return SimpleNamespace(
        peaks=V5E, trace={"kernel_s": kernel_s,
                          "kernel_events": kernel_events},
        trace_window=(0.0, 2.0), spans=[span], store_dim=1024,
        store_itemsize=4)


def test_roofline_share_is_not_clipped():
    read = harness.reader("topk_retrieval_roofline")
    least, _ = flops.topk_least_time(65536, 1024, 1, 4, V5E)
    assert read(_ctx(4 * least)) == pytest.approx(25.0)
    # a kernel time under the least time reads above 100: a stale count
    # shows instead of hiding under a clip
    assert read(_ctx(least / 2)) == pytest.approx(200.0)


def test_roofline_reads_nothing_without_kernel_events():
    read = harness.reader("topk_retrieval_roofline")
    assert read(_ctx(0.0, 0)) is None


def test_decode_mfu_counts_real_tokens_over_the_union_of_spans():
    read = harness.reader("decode_mfu")
    spans = [harness.Span("chat_decode", 0.0, 1.0, [0, 1], 2, 16, None),
             harness.Span("rewrite_decode", 0.5, 2.0, [2], 1, 8, None),
             harness.Span("vsearch", 0.0, 5.0, [0], 1, 0, 10)]
    ctx = SimpleNamespace(peaks=V5E, config=CONFIG, spans=spans)
    work = (flops.decode_flops(CONFIG["models"]["chat"], 16)
            + flops.decode_flops(CONFIG["models"]["search"], 8))
    assert read(ctx) == pytest.approx(100 * work / (2.0 * 197e12))


def test_decode_mfu_of_the_dense_configuration_is_the_dense_count():
    # a configuration whose entries name no reference module reads the
    # same float as 2 x the dense parameters per token gives
    read = harness.reader("decode_mfu")
    spans = [harness.Span("chat_decode", 0.0, 1.3, [0, 1], 2, 13, None),
             harness.Span("refine_decode", 0.2, 1.9, [3], 1, 7, None),
             harness.Span("rewrite_decode", 2.5, 3.1, [2], 1, 5, None)]
    ctx = SimpleNamespace(peaks=V5E, config=CONFIG, spans=spans)
    roles = CONFIG["stage_roles"]
    work = sum(flops.decode_flops(CONFIG["models"][roles[s.stage]],
                                  s.tokens) for s in spans)
    wall = sum(e - s for s, e in trace_reduce.union(
        [(s.t0, s.t1) for s in spans]))
    assert read(ctx) == 100.0 * work / (wall * V5E["bf16_flops"])
