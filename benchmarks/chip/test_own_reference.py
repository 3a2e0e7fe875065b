"""A configuration that names its own reference module
(``fixtures/own-reference.json``, ``fixtures/counted_reference.py``) is
compared, counted and width-checked by the harness as it stands (CPU,
reduced widths)."""
import copy
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

import flops
import harness
import peaks
import rehearsal
from fixtures import counted_reference

FIXTURE = json.loads((Path(__file__).parent / "fixtures"
                      / "own-reference.json").read_text())
QWEN3 = json.loads((Path(__file__).parent / "configs" / "qwen3-rag.json")
                   .read_text())


def test_the_named_module_is_the_reference():
    assert harness.reference_module(FIXTURE["models"]["chat"]) \
        is counted_reference
    assert harness.reference_module(FIXTURE["models"]["search"]).__name__ \
        == "reference"
    with pytest.raises(ValueError):
        harness.reference_module(dict(FIXTURE["models"]["chat"],
                                      reference="json"))


def test_compare_runs_the_named_reference():
    counted_reference.CALLS.clear()
    cell = rehearsal.reduced_cell("qwen3-w2-single", FIXTURE,
                                  max_queries=40)
    out = rehearsal.run(cell, 2 ** 31 + 29, 2.0, False)
    assert out["correct"] is True, out["checks"]
    calls = counted_reference.CALLS
    # chat, embed and rerank: one model each; search stays on reference.py
    assert calls["init"] == 3
    assert calls["logits"] >= 1 and calls["embed"] == 1 \
        and calls["rerank"] == 1


def test_decode_mfu_counts_with_the_named_module():
    read = harness.reader("decode_mfu")
    spans = [harness.Span("chat_decode", 0.0, 1.0, [0], 1, 16, None),
             harness.Span("rewrite_decode", 0.5, 2.0, [1], 1, 8, None)]
    v5e = peaks.peaks("TPU v5 lite")
    own = read(SimpleNamespace(peaks=v5e, config=FIXTURE, spans=spans))
    work = (2 * flops.decode_flops(FIXTURE["models"]["chat"], 16)
            + flops.decode_flops(FIXTURE["models"]["search"], 8))
    assert own == pytest.approx(100 * work / (2.0 * v5e["bf16_flops"]))
    assert counted_reference.CALLS["decode_flops"] >= 1


@pytest.mark.parametrize("key,change", [
    ("moe.top_k", lambda m: m["moe"].update(top_k=2)),
    ("d_fff", lambda m: m.update(d_fff=128)),
    ("moe.topk", lambda m: m["moe"].update(topk=0)),
])
def test_a_width_the_program_does_not_build_stops_setup(key, change):
    cfg = copy.deepcopy(FIXTURE)
    change(cfg["models"]["chat"])
    cell = rehearsal.reduced_cell("qwen3-w2-single", cfg, max_queries=40)
    with pytest.raises(RuntimeError, match=rf"chat: .*\b{key}\b"):
        rehearsal.run(cell, 5, 1.0, False)


def test_every_key_of_the_shipped_configuration_is_checked():
    from repro.configs import get_family

    family = get_family("qwen3")
    for role, m in QWEN3["models"].items():
        harness.check_widths(role, m, family[role])
        with pytest.raises(RuntimeError, match="norm_eps"):
            harness.check_widths(role, dict(m, norm_eps=1e-5), family[role])
