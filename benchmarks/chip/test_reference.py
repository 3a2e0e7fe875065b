"""The plain reference against the program at reduced widths (CPU): its
weights, drawn again from the seed, are the program's, and its float32
forward gives the program's logits.  The fp8 control reads worse."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference
import rehearsal

SEED = 2 ** 31 + 3


def _model(role):
    from repro.configs import get_family, reduced

    cfg = reduced(get_family("qwen3")[role])
    m = dict(rehearsal.harness.load_cell("qwen3-w2-single")
             .config["models"][role])
    m.update(rehearsal.REDUCED)
    return cfg, m


@pytest.fixture(scope="module")
def chat():
    from repro.launch import serve

    pipe = serve.build_pipeline(seed=SEED, reduced_widths=True)
    cfg, m = _model("chat")
    return pipe.models["chat"][1], cfg, m


def test_weights_are_the_programs(chat):
    params, cfg, m = chat
    ref = reference.Reference(m, SEED)
    E = ref._embed_table(ref._embed_key)
    np.testing.assert_array_equal(np.asarray(E), np.asarray(params["embed"]))
    for i, k in enumerate(ref._layer_keys):
        p = ref._layer_params(k)
        blk = jax.tree.map(lambda a: a[i], params["blocks"])
        for name, prog in (("wq", blk["attn"]["wq"]), ("wk", blk["attn"]["wk"]),
                           ("wv", blk["attn"]["wv"]), ("wo", blk["attn"]["wo"]),
                           ("w_up", blk["mlp"]["w_up"]),
                           ("w_down", blk["mlp"]["w_down"]),
                           ("w_gate", blk["mlp"]["w_gate"])):
            np.testing.assert_array_equal(np.asarray(p[name]),
                                          np.asarray(prog), err_msg=name)


def test_logits_are_the_programs(chat):
    from repro.models import lm

    params, cfg, m = chat
    toks = [[5, 9, 33, 7, 100, 2, 41], [8, 8, 1, 200]]
    got = reference.Reference(m, SEED).logits(toks)
    for t, g in zip(toks, got):
        with jax.default_matmul_precision("highest"):
            want = lm.apply(params, cfg, {"tokens": jnp.asarray([t])},
                            mode="train")[0][0]
        np.testing.assert_allclose(g, np.asarray(want), atol=2e-4)


def test_logit_gap_of_greedy_tokens_is_zero(chat):
    import checks

    _, _, m = chat
    ref = reference.Reference(m, SEED)
    prompt = [5, 9, 33, 7]
    seq = list(prompt)
    for _ in range(4):
        seq.append(int(ref.logits([seq])[0][-1].argmax()))
    calls = [(prompt, seq[len(prompt):])]
    assert checks.lm_gap(ref, calls) == 0.0
    altered = [(prompt, seq[len(prompt):-1] + [(seq[-1] + 1) % 256])]
    assert checks.lm_gap(ref, altered) > 0.0


def test_fp8_control_reads_worse():
    _, m = _model("embed")
    seqs = [[4 + (i * 7 + j) % 250 for j in range(20)] for i in range(6)]
    r = reference.Reference(m, SEED).embed(seqs)
    c = reference.Reference(m, SEED, quant="fp8").embed(seqs)
    assert np.abs(r - c).max() > 1e-3


def test_unknown_recipe_is_refused():
    _, m = _model("chat")
    with pytest.raises(ValueError):
        reference.Reference(dict(m, init="xavier"), 0)
