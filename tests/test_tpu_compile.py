"""v5e compiles of the Pallas kernels at the serving path's real widths.

Each case compiles one kernel for a described (not attached) v5e chip and
checks that the compiled program holds the Mosaic kernel
(``tpu_custom_call``) rather than a fallback.  Nothing runs: these tests
guard lowering, tiling and VMEM limits, not results or times.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and under pytest-xdist every worker imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_family
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.int8_matmul import int8_matmul
from repro.kernels.topk_retrieval import topk_retrieval

CHAT = get_family("qwen3")["chat"]          # Qwen3-4B
EMBED_D = get_family("qwen3")["embed"].d_model


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("q,N", [(1, 128), (1, 4096), (8, 128), (8, 4096)])
def test_topk_retrieval_compiles_for_v5e(one_chip, q, N):
    """The vsearch stage: (q × 1024) queries over an N-row f32 store, k=4,
    with the valid row count traced."""
    _assert_kernel(lambda x, c, n: topk_retrieval(x, c, 4, n),
                   _sds((q, EMBED_D), jnp.float32, one_chip),
                   _sds((N, EMBED_D), jnp.float32, one_chip),
                   _sds((), jnp.int32, one_chip))


def test_decode_attention_compiles_for_v5e(one_chip):
    """One decode token for 8 streams against a 512-position cache at
    Qwen3-4B heads (32 query heads, 8 KV heads, head_dim 128)."""
    b, S, e = 8, 512, CHAT.resolved_head_dim
    _assert_kernel(decode_attention,
                   _sds((b, CHAT.num_heads, e), jnp.bfloat16, one_chip),
                   _sds((b, S, CHAT.num_kv_heads, e), jnp.bfloat16, one_chip),
                   _sds((b, S, CHAT.num_kv_heads, e), jnp.bfloat16, one_chip),
                   _sds((b,), jnp.int32, one_chip))


def test_flash_attention_compiles_for_v5e(one_chip):
    """Causal prefill of 512 tokens at Qwen3-4B heads."""
    s, e = 512, CHAT.resolved_head_dim
    _assert_kernel(flash_attention,
                   _sds((1, s, CHAT.num_heads, e), jnp.bfloat16, one_chip),
                   _sds((1, s, CHAT.num_kv_heads, e), jnp.bfloat16, one_chip),
                   _sds((1, s, CHAT.num_kv_heads, e), jnp.bfloat16, one_chip))


def test_int8_matmul_compiles_for_v5e(one_chip):
    """A Qwen3-4B FFN up-projection (2560 -> 9728) over 256 tokens."""
    M, K, N = 256, CHAT.d_model, CHAT.d_ff
    _assert_kernel(int8_matmul,
                   _sds((M, K), jnp.int8, one_chip),
                   _sds((K, N), jnp.int8, one_chip),
                   _sds((M, 1), jnp.float32, one_chip),
                   _sds((1, N), jnp.float32, one_chip))


@pytest.mark.parametrize("role,max_len", [("chat", 512), ("search", 256)])
def test_lm_programs_copy_no_weight_stack_for_v5e(one_chip, role, max_len):
    """An agent's prefill and decode programs at published widths: each
    runs the layer stack once, and its scratch memory holds no whole q/k/v
    weight stack.  (One program that ran the stack in several loops, a
    prefill and a decode loop, would copy the stacks into another layout
    on every call: 1.13 GB for the 4B.)"""
    from repro.rag.agents import LMAgent
    from repro.rag.embedder import CALL_WIDTH

    agent = LMAgent(get_family("qwen3")[role], None, max_len=max_len)
    params = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip),
                          jax.eval_shape(agent.model.init,
                                         jax.random.PRNGKey(0)))
    cache = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip),
                         jax.eval_shape(lambda: agent.model.init_cache(
                             CALL_WIDTH, max_len)))
    wk = params["blocks"]["attn"]["wk"]
    for program, args in (
            (agent._prefill, (_sds((CALL_WIDTH, 16), "int32", one_chip),)),
            (agent._decode, (_sds((CALL_WIDTH,), "int32", one_chip), cache))):
        memory = program.lower(params, *args).compile().memory_analysis()
        assert memory.temp_size_in_bytes < wk.size * wk.dtype.itemsize


_COPY = re.compile(r"= \w+\[([\d,]*)\]\S* copy\(")


@pytest.mark.parametrize("program", ["_prefill", "_decode"])
@pytest.mark.parametrize("role,max_len", [("chat", 512), ("search", 256)])
def test_lm_programs_copy_no_kv_slice_for_v5e(one_chip, role, max_len,
                                              program):
    """An agent's prefill or decode program at published widths copies no
    layer's KV slice: the cache is heads-major, the order the attention
    einsums read, so no step relays one.  (With the cache in (b, S, n, e)
    order each program held four such copies a layer, K and V into the
    einsums' order and back, 2.42 GB of traffic a 4B decode step.)  A
    copy is a KV slice by its dims, not its element count alone: the
    1.7B's q/k/v weight relayout copies a (2048, 8, 128) slice, as many
    elements as the search agent's (8, 256, 8, 128) cache slice."""
    from repro.rag.agents import LMAgent
    from repro.rag.embedder import CALL_WIDTH

    cfg = get_family("qwen3")[role]
    agent = LMAgent(cfg, None, max_len=max_len)
    params = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip),
                          jax.eval_shape(agent.model.init,
                                         jax.random.PRNGKey(0)))
    if program == "_prefill":
        args = (_sds((CALL_WIDTH, 16), "int32", one_chip),)
    else:
        cache = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip),
                             jax.eval_shape(lambda: agent.model.init_cache(
                                 CALL_WIDTH, max_len)))
        args = (_sds((CALL_WIDTH,), "int32", one_chip), cache)
    text = getattr(agent, program).lower(params, *args).compile().as_text()
    kv_slice = sorted((CALL_WIDTH, max_len, cfg.num_kv_heads,
                       cfg.resolved_head_dim))
    copies = [dims for dims in _COPY.findall(text)
              if sorted(int(d) for d in dims.split(",") if d not in ("", "1"))
              == kv_slice]
    assert not copies


def test_moe_share_programs_compile_for_v5e(one_chip, monkeypatch):
    """DeepSeek-V2-Lite's share (16 of 64 experts a layer) as the chat
    agent: its prefill and decode compile for v5e, and each program's
    scratch memory stays a small part of the 9.8 GB of weights.  The
    decode step takes the grouped expert kernel (as it does on a chip)
    and hands it the expert stacks whole: no copy or slice of one layer's
    16 held experts appears in its program."""
    from repro.kernels import ops
    from repro.rag.agents import LMAgent
    from repro.rag.embedder import CALL_WIDTH

    # the compile targets a described v5e while the process runs on CPU
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = get_family("deepseek-v2-lite")["chat"]
    agent = LMAgent(cfg, None, max_len=512, role="chat")
    params = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip),
                          jax.eval_shape(agent.model.init,
                                         jax.random.PRNGKey(0)))
    cache = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip),
                         jax.eval_shape(lambda: agent.model.init_cache(
                             CALL_WIDTH, 512)))
    held, d, ff = cfg.moe.held, cfg.d_model, cfg.moe.d_ff
    one_layer = [f"bf16[{held},{d},{ff}]", f"bf16[{held},{ff},{d}]"]
    for program, args in (
            (agent._prefill, (_sds((CALL_WIDTH, 16), "int32", one_chip),
                              _sds((), "int32", one_chip))),
            (agent._decode, (_sds((CALL_WIDTH,), "int32", one_chip), cache))):
        compiled = program.lower(params, *args).compile()
        assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not [s for s in one_layer if s in text]
