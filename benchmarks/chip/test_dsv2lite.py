"""The ``dsv2lite-w2-single`` cell on the CPU at reduced widths: the
DeepSeek-V2-Lite generator reduced as the program's ``reduced()`` reduces
its MoE and MLA groups, compared with ``ref_mla_moe.py`` through the
harness as it stands; a dropped-token fault turns ``correct`` false; the
``moe_decode_roofline`` reader over what a traced run recorded."""
import copy
import json
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

import harness
import peaks
import ref_mla_moe
import rehearsal

CONFIG = json.loads((Path(__file__).parent / "configs" / "dsv2lite-rag.json")
                    .read_text())
CELL = "dsv2lite-w2-single"
GENERATORS = ("search", "chat")


def reduced_config() -> dict:
    """The configuration with its generator's MoE and MLA groups reduced
    as ``repro.configs.reduced`` reduces them (the top-level widths are
    ``rehearsal.REDUCED``'s)."""
    cfg = copy.deepcopy(CONFIG)
    for role in GENERATORS:
        m = cfg["models"][role]
        m["moe"].update(num_experts=4, top_k=2, num_shared_experts=1, d_ff=64,
                        first_k_dense=1, dense_d_ff=128, num_experts_held=1,
                        first_expert=0)
        m["mla"].update(kv_lora_rank=32, q_lora_rank=0, qk_nope_head_dim=16,
                        qk_rope_head_dim=8, v_head_dim=16)
    return cfg


def run(trace: bool, seed: int, keep=None):
    cell = rehearsal.reduced_cell(CELL, reduced_config(), max_queries=40)
    return harness.run(cell, seed, 3.0, trace, t_start=time.monotonic(),
                       device=jax.devices()[0],
                       pipe_hook=rehearsal.interpret_topk, keep=keep)


def test_reduction_keeps_the_share_and_the_direct_q():
    from repro.configs import get_family, reduced

    built = reduced(get_family("deepseek-v2-lite")["chat"])
    for role in GENERATORS:
        m = dict(reduced_config()["models"][role], **rehearsal.REDUCED)
        harness.check_widths(role, m, built)
    assert built.mla.q_lora_rank == 0
    assert 0 < built.moe.num_experts_held <= built.moe.num_experts


def test_published_widths_pass_the_width_check():
    from repro.configs import get_family

    family = get_family("deepseek-v2-lite")
    for role, m in CONFIG["models"].items():
        harness.check_widths(role, m, family[role])
    chat = CONFIG["models"]["chat"]
    assert ref_mla_moe.param_count(chat) == chat["params"] == 4_910_345_728
    with pytest.raises(RuntimeError, match=r"moe\.num_experts_held"):
        bad = copy.deepcopy(chat)
        bad["moe"]["num_experts_held"] = 64
        harness.check_widths("chat", bad, family["chat"])


def test_the_cell_is_correct_and_counts_its_experts():
    keep: dict = {}
    out = run(True, 2 ** 31 + 101, keep)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    counters = keep["ctx"].counters
    assert counters["moe.layer_steps"] > 0 and counters["moe.dropped"] == 0
    assert 0 < counters["moe.routed_held"] <= counters["moe.routed"]
    # no peak or device trace on the CPU: the reader finds nothing there
    assert "moe_decode_roofline" not in out["metrics"]
    calls = [s for s in keep["ctx"].program_spans if s.name == "lm.call"]
    assert {s.attrs["role"] for s in calls} == set(GENERATORS)

    # the reader over those spans and counters, with one device op covering
    # each call as if it were a decode step's: least time over that time
    ctx = keep["ctx"]
    lo, hi = ctx.window
    inside = [s for s in calls if lo <= s.t0 and s.t1 <= hi]
    ops = [["fusion", s.t0 * 1e9, (s.t1 - s.t0) * 1e9, "jit_decode_1_"]
           for s in inside]
    v5e = peaks.peaks("TPU v5 lite")
    traced = SimpleNamespace(
        counters=counters, program_spans=ctx.program_spans,
        clock={"offset_s": 0.0}, trace_window=(lo, hi),
        devices={"/device:TPU:0": ops}, config=ctx.config, peaks=v5e)
    m = ctx.config["models"]["chat"]
    hit = counters["moe.experts_hit"] / counters["moe.layer_steps"]
    least = sum(ref_mla_moe.decode_step_bytes(
        m, hit, s.attrs["rows"] * (s.attrs["prompt"] + j))
        for s in inside for j in range(1, s.attrs["steps"] + 1))
    want = 100 * least / v5e["hbm_bytes_per_s"] / sum(s.t1 - s.t0
                                                     for s in inside)
    got = harness.reader("moe_decode_roofline")(traced)
    assert got == pytest.approx(want, rel=1e-9) and got > 0


def drop_routed_tokens(monkeypatch):
    """The fault "tokens dropped by the expert layer": no token gets its
    routed experts' output, only the shared experts' (as a capacity that
    a call's copies of one prompt overflow would have it)."""
    from repro.models import moe

    ffn = moe.moe_ffn

    def dropping(p, x, cfg, *, token_mask=None):
        _, aux, stats = ffn(p, x, cfg, token_mask=token_mask)
        shared, _, _ = ffn(dict(p, w_down=jnp.zeros_like(p["w_down"])), x,
                           cfg)
        return shared, aux, stats

    monkeypatch.setattr(moe, "moe_ffn", dropping)


def test_dropped_tokens_are_caught(monkeypatch):
    """At this size one expert of four is held, so the routed part is a
    small share of a token's update and moves the served tokens on about
    half the seeds; seed 5 is one where it moves the second token."""
    drop_routed_tokens(monkeypatch)
    out = run(False, 5)
    assert out["correct"] is False
    c = out["checks"]["decode_logit_gap"]
    assert not c["value"] <= c["limit"], out["checks"]
