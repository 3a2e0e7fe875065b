"""DeepSeek-V2-Lite's mechanisms against the plain reference of the chip
benchmark (``benchmarks/chip/ref_mla_moe.py``, loaded by path): MLA with
no q-LoRA and YaRN, a dropless MoE layer told which experts it holds, the
chip's share of an expert-parallel layer, and the generator on the live
pipeline.  CPU, small widths, float32, seeded weights."""
import dataclasses
import functools
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import MoEConfig, get_family, reduced
from repro.models import build_model
from repro.models import mla as MLA
from repro.models import moe as MOE

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"


def _load(name):
    spec = importlib.util.spec_from_file_location(f"chip_{name}",
                                                  CHIP / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("ref_mla_moe")
LITE = get_family("deepseek-v2-lite")["chat"]


def entry(cfg, role_index: int = 2) -> dict:
    """The configuration as a benchmark model entry (nested dicts)."""
    def as_dict(dc):
        return {f.name: (as_dict(v) if dataclasses.is_dataclass(v) else v)
                for f in dataclasses.fields(dc)
                for v in [getattr(dc, f.name)]}
    return dict(as_dict(cfg), role_index=role_index,
                init="truncated_normal_fan_in")


def small(held: int = 0, first: int = 0, **moe):
    """The reduced generator with ``held`` of its 4 experts from
    ``first`` (0: all)."""
    cfg = reduced(LITE)
    return dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, num_experts_held=held, first_expert=first, **moe))


# -- the whole model: prefill, then decode through the latent cache ----------

@pytest.mark.parametrize("held,first,norm", [
    (0, 0, False),     # every expert held
    (1, 0, False),     # the reduction of the chip's share
    (2, 1, False),     # a share from expert 1
    (2, 2, True),      # renormalised gates
])
def test_prefill_then_decode_matches_the_reference(held, first, norm):
    cfg = small(held, first, norm_topk_prob=norm)
    seed, P, S = 11, 12, 20
    params = build_model(cfg).init(
        jax.random.fold_in(jax.random.PRNGKey(seed), 2))
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (3, S), 4,
                                         cfg.vocab_size))
    want = ref.Reference(entry(cfg), seed).logits([list(t) for t in toks])
    model = build_model(cfg)
    prefill, decode = jax.jit(model.prefill), jax.jit(model.decode_step)
    with jax.default_matmul_precision("highest"):
        cache = model.init_cache(3, 32)
        logits, cache = prefill(params, {"tokens": toks[:, :P]}, cache)
        got = [np.asarray(logits)]
        for t in range(P, S):
            lg, cache = decode(params, toks[:, t:t + 1], cache)
            got.append(np.asarray(lg)[:, None])
    got = np.concatenate(got, axis=1)
    for row, w in zip(got, want):
        np.testing.assert_allclose(row, w, atol=2e-5, rtol=0)


# -- YaRN ----------------------------------------------------------------------

def test_yarn_frequencies_and_softmax_scale():
    m = LITE.mla
    inv = MLA.rope_inv_freq(m, LITE.rope_theta)
    base = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    # correction range [10, 23] for 4,096 positions, beta 32 and 1
    np.testing.assert_allclose(inv[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], base[23:] / 40, rtol=1e-6)
    assert np.all((inv[11:23] < base[11:23]) & (inv[11:23] > base[11:23] / 40))
    np.testing.assert_allclose(
        inv, ref.yarn_inv_freq(entry(LITE)["mla"], LITE.rope_theta),
        rtol=1e-6)
    mscale = 0.1 * 0.707 * np.log(40) + 1
    assert mscale == pytest.approx(1.26080, abs=1e-5)
    assert MLA.softmax_scale(m) == pytest.approx(192 ** -0.5 * mscale ** 2,
                                                 rel=1e-12)
    assert ref.yarn_scales(entry(LITE)["mla"]) == pytest.approx(
        (192 ** -0.5 * 1.58963, 1.0), rel=1e-5)
    assert MLA.rope_inv_freq(dataclasses.replace(m, yarn=type(m.yarn)()),
                             1e4) is None


# -- the MoE layer ---------------------------------------------------------------

def _layer(cfg: MoEConfig, d: int, key):
    return MOE.init_moe(key, d, cfg, jnp.float32)


def _ref_params(p, experts):
    return {"router": p["router"],
            "experts": [{"gate": p["w_gate"][i], "up": p["w_up"][i],
                         "down": p["w_down"][i]} for i in range(len(experts))],
            "shared": {k[2:]: v for k, v in p["shared"].items()}}


def _ref_entry(cfg: MoEConfig, d: int) -> dict:
    return {"d_model": d, "moe": dataclasses.asdict(cfg)}


@pytest.mark.parametrize("T", [8, 64, 512])
def test_every_token_on_one_held_expert_is_computed(T):
    """Every token routes first to held expert 1: a capacity-bound layer
    would drop most of them; this one computes them all."""
    d = 32
    cfg = MoEConfig(num_experts=8, num_shared_experts=1, top_k=2, d_ff=16,
                    norm_topk_prob=False, num_experts_held=4, first_expert=0)
    p = _layer(cfg, d, jax.random.PRNGKey(0))
    p["router"] = p["router"].at[:, 1].set(1.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (1, T, d)))
    with jax.default_matmul_precision("highest"):
        y, _, stats = MOE.moe_ffn(p, x, cfg,
                                  token_mask=jnp.ones((1, T), bool))
        want = ref.moe(_ref_params(p, range(4)), x, _ref_entry(cfg, d),
                       range(4))
    np.testing.assert_allclose(y, want, atol=1e-5, rtol=0)
    got = dict(zip(MOE.ROUTE_STATS, np.asarray(stats).tolist()))
    assert got["routed"] == 2 * T and got["routed_held"] >= T
    assert got["computed"] == got["routed_held"]     # moe.dropped == 0


@pytest.mark.parametrize("E", [2, 4])
def test_four_shares_add_up_to_the_uncut_layer(E):
    """Guide §4's share test: the four chips' shares of a 4E-expert layer,
    the shared experts counted once, add up to the uncut reference."""
    d, n = 32, 4
    whole = MoEConfig(num_experts=n * E, num_shared_experts=2, top_k=3,
                      d_ff=16, norm_topk_prob=False)
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 5, d))
    with jax.default_matmul_precision("highest"):
        parts = [jax.jit(MOE.moe_ffn, static_argnums=2)(
                     _layer(share, d, key), x, share)[0]
                 for share in (dataclasses.replace(
                     whole, num_experts_held=E, first_expert=i * E)
                     for i in range(n))]
        shared = ref.moe(dict(_ref_params(_layer(whole, d, key), []),
                              experts=[]), x, _ref_entry(whole, d), [])
        drawn = ref.draw_moe(key, _ref_entry(whole, d), range(n * E),
                             jnp.float32)
        uncut = ref.moe(drawn, x, _ref_entry(whole, d), range(n * E))
    total = sum(parts) - (n - 1) * shared
    np.testing.assert_allclose(total, uncut, atol=1e-5, rtol=0)


# -- counts ----------------------------------------------------------------------

def test_parameter_counts_of_the_share_and_the_whole_model():
    whole = dataclasses.replace(LITE, moe=dataclasses.replace(
        LITE.moe, num_experts_held=0))
    # param_count leaves out the final norm's d scales
    assert LITE.param_count() + LITE.d_model == 4_910_345_728
    assert whole.param_count() + LITE.d_model == 15_706_484_224
    shapes = jax.eval_shape(build_model(LITE).init, jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 4_910_345_728
    assert ref.param_count(entry(LITE)) == 4_910_345_728
    # a token uses the head, attention, the dense layer, the shared
    # experts and router, and 6 x 16/64 = 1.5 held routed experts a layer
    d, V = 2048, 102400
    attn = d * 16 * 192 + d * 576 + 512 + 512 * 16 * 256 + 16 * 128 * d
    expert = 3 * d * 1408
    active = (2 * V * d + 27 * (attn + 2 * d) + 3 * d * 10944
              + 26 * (1.5 * expert + 2 * expert + d * 64))
    assert LITE.active_param_count() == active
    assert whole.active_param_count() == active + 26 * 4.5 * expert
    assert ref.decode_flops(entry(LITE), 1) == 2 * (active - V * d + d)


def test_stages_price_the_share_by_active_parameters_and_latent_bytes():
    from repro.rag.stages import _kv_bytes_token, build_stages

    stages = build_stages(get_family("deepseek-v2-lite"))
    for name in ("rewrite_decode", "chat_decode", "chat_prefill"):
        assert stages[name].params == LITE.active_param_count()
        assert stages[name].params < LITE.param_count() / 2
    assert _kv_bytes_token(LITE, 2.0) == 31_104 == ref.cache_slot_bytes(
        entry(LITE))
    assert stages["chat_decode"].kv_bytes_token == 15_552


# the parent's stage catalog of the Qwen3 family, field by field
QWEN3_STAGES = {
    "embed": (595768320, 1024, "batchable", 128, 0.0),
    "rerank": (595768320, 1024, "batchable", 160, 0.0),
    "vsearch": (0, 1024, "search", 128, 0.0),
    "rewrite_prefill": (1720565760, 2048, "stream_prefill", 128, 0.0),
    "rewrite_decode": (1720565760, 2048, "stream_decode", 128, 57344.0),
    "plan_prefill": (1720565760, 2048, "stream_prefill", 128, 0.0),
    "plan_decode": (1720565760, 2048, "stream_decode", 128, 57344.0),
    "refine_prefill": (4022456320, 2560, "stream_prefill", 128, 0.0),
    "refine_decode": (4022456320, 2560, "stream_decode", 128, 73728.0),
    "chat_prefill": (4022456320, 2560, "stream_prefill", 128, 0.0),
    "chat_decode": (4022456320, 2560, "stream_decode", 128, 73728.0),
    "web": (0, 0, "io", 128, 0.0),
    "rewrite_draft": (619569152, 1024, "stream_decode", 128, 49152.0),
    "plan_draft": (619569152, 1024, "stream_decode", 128, 49152.0),
    "refine_draft": (619569152, 1024, "stream_decode", 128, 49152.0),
    "chat_draft": (619569152, 1024, "stream_decode", 128, 49152.0),
}


def test_qwen3_stage_catalog_is_unchanged():
    from repro.rag.stages import build_stages

    got = {k: (v.params, v.d_model, v.kind, v.item_tokens, v.kv_bytes_token)
           for k, v in build_stages(get_family("qwen3")).items()}
    assert got == QWEN3_STAGES


@pytest.mark.parametrize("role,index", [("embed", 0), ("search", 2),
                                        ("chat", 3)])
def test_qwen3_init_is_bit_identical_to_its_recipe(role, index):
    """The dense family's key tree as the benchmark's dense reference
    (``reference.py``) states it, bit for bit at float32."""
    dense = _load("reference")
    cfg = reduced(get_family("qwen3")[role])
    seed = 2 ** 31 + 5
    params = build_model(cfg).init(
        jax.random.fold_in(jax.random.PRNGKey(seed), index))
    r = dense.Reference(entry(cfg, index), seed)
    assert np.array_equal(params["embed"], r._embed_table(r._embed_key))
    for i, k in enumerate(r._layer_keys):
        want = r._layer_params(k)
        got = params["blocks"]
        pairs = [("wq", got["attn"]["wq"]), ("wk", got["attn"]["wk"]),
                 ("wv", got["attn"]["wv"]), ("wo", got["attn"]["wo"]),
                 ("w_up", got["mlp"]["w_up"]),
                 ("w_down", got["mlp"]["w_down"]),
                 ("w_gate", got["mlp"]["w_gate"])]
        for name, stacked in pairs:
            assert np.array_equal(stacked[i], want[name]), (role, i, name)


# -- the generator on the live pipeline ------------------------------------------

@pytest.fixture(scope="module")
def pipe():
    from repro.launch import serve
    return serve.build_pipeline(5, family="deepseek-v2-lite",
                                reduced_widths=True)


def test_search_and_chat_are_one_model_prompted_in_its_vocabulary(pipe):
    from repro.launch import serve
    from repro.rag.tokenizer import HashTokenizer

    assert pipe.models["search"] is pipe.models["chat"]
    assert pipe.rewriter.params is pipe.chat.params
    assert (pipe.rewriter.max_len, pipe.chat.max_len) == (256, 512)
    assert (pipe.rewriter.role, pipe.chat.role) == ("search", "chat")
    prompts = []
    for agent in (pipe.rewriter, pipe.chat):
        real = agent.generate
        agent.generate = (lambda ids, *a, _g=real, **k:
                          prompts.append(list(ids)) or _g(ids, *a, **k))
    serve.stage_fns(pipe)
    vocab = pipe.models["chat"][0].vocab_size
    assert prompts and all(0 <= t < vocab for p in prompts for t in p)
    # at published widths the retrieval tokenizer's ids (151,669) do not
    # fit DeepSeek's 102,400: the generator's prompt has its own
    big = HashTokenizer(151669).encode("what was the revenue growth")
    own = HashTokenizer(LITE.vocab_size).encode("what was the revenue growth")
    assert max(big) >= LITE.vocab_size > max(own)


def _moe_counters(agent):
    """Two calls of ``agent`` (3 rows for 5 tokens, then 1 row for 4)
    under the span recorder -> (served tokens, lm.call spans, counters)."""
    from repro.core.events import SP_LM_CALL
    from repro.serving import spans

    prompt = list(range(4, 20))
    spans.enable()
    spans.clear()
    try:
        served = [r.token_ids for r in
                  agent.generate_batch([prompt] * 3, max_new=5)]
        served.append(agent.generate(prompt, max_new=4,
                                     stop_at_eos=False).token_ids)
        recorded, counters = spans.drain()
    finally:
        spans.disable()
    return served, [s for s in recorded if s.name == SP_LM_CALL], counters


def test_one_fetch_per_call_with_the_moe_counters(pipe):
    from repro.core.events import (CT_LM_FETCHES, CT_MOE_DROPPED,
                                   CT_MOE_EXPERTS_HIT, CT_MOE_EXPERTS_READ,
                                   CT_MOE_LAYER_STEPS, CT_MOE_ROUTED,
                                   CT_MOE_ROUTED_HELD)

    agent, cfg = pipe.chat, pipe.models["chat"][0]
    _, calls, counters = _moe_counters(agent)
    assert len(calls) == 2 and counters[CT_LM_FETCHES] == 2
    assert [(s.attrs["role"], s.attrs["rows"], s.attrs["steps"])
            for s in calls] == [("chat", 3, 4), ("chat", 1, 3)]
    n_moe = cfg.num_layers - cfg.moe.first_k_dense
    steps = 4 + 3
    assert counters[CT_MOE_LAYER_STEPS] == n_moe * steps
    # real rows only: 3 rows for 4 steps, then 1 row for 3
    assert counters[CT_MOE_ROUTED] == cfg.moe.top_k * n_moe * (3 * 4 + 3)
    assert 0 <= counters[CT_MOE_ROUTED_HELD] <= counters[CT_MOE_ROUTED]
    assert 0 <= counters[CT_MOE_EXPERTS_HIT] <= n_moe * steps \
        * cfg.moe.num_experts_held
    assert counters[CT_MOE_DROPPED] == 0
    # off the chip the decode step runs the dense-over-held reference,
    # which reads every held expert
    assert counters[CT_MOE_EXPERTS_HIT] <= counters[CT_MOE_EXPERTS_READ] \
        == n_moe * steps * cfg.moe.num_experts_held


def test_the_grouped_kernel_serves_the_same_tokens_reading_fewer_experts(
        monkeypatch):
    """The decode steps on the grouped expert kernel (interpret mode), as
    on a chip, against the dense-over-held reference: the same served
    tokens, one fetch a call, nothing dropped, and the held experts read
    at least those the real rows hit and, at these seeds, fewer than
    every held one (9 and 14)."""
    from repro.core.events import (CT_LM_FETCHES, CT_MOE_DROPPED,
                                   CT_MOE_EXPERTS_HIT, CT_MOE_EXPERTS_READ,
                                   CT_MOE_LAYER_STEPS, CT_MOE_ROUTED,
                                   CT_MOE_ROUTED_HELD)
    from repro.kernels import ops
    from repro.rag.agents import LMAgent

    cfg = small(2, 1)
    params = build_model(cfg).init(jax.random.PRNGKey(4))
    want, _, ref_counters = _moe_counters(
        LMAgent(cfg, params, max_len=64, role="chat"))
    monkeypatch.setattr(ops, "moe_decode", functools.partial(
        ops.moe_decode, use_pallas=True))
    served, calls, counters = _moe_counters(
        LMAgent(cfg, params, max_len=64, role="chat"))
    assert served == want
    assert len(calls) == 2 and counters[CT_LM_FETCHES] == 2
    for name in (CT_MOE_ROUTED, CT_MOE_ROUTED_HELD, CT_MOE_EXPERTS_HIT,
                 CT_MOE_LAYER_STEPS):
        assert counters[name] == ref_counters[name]
    held_reads = counters[CT_MOE_LAYER_STEPS] * cfg.moe.held
    assert ref_counters[CT_MOE_EXPERTS_READ] == held_reads
    assert counters[CT_MOE_EXPERTS_HIT] <= counters[CT_MOE_EXPERTS_READ] \
        < held_reads
    assert counters[CT_MOE_DROPPED] == ref_counters[CT_MOE_DROPPED] == 0
