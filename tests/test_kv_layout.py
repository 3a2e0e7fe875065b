"""The dense KV cache is heads-major, ``(layers, b, n_kv, S, e)``: the order
the cache-path attention einsums read, so a decode or prefill step never
relays a layer's cache slice.  These tests hold the cache path to the
``(b, S, n, e)`` attention it replaced."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_family, reduced
from repro.models import layers as L
from repro.models import lm

B, S, D, H, E = 2, 32, 64, 4, 16
THETA = 10_000.0


def _qkv(p, x, positions):
    q = L.apply_rope(jnp.einsum("bsd,dhe->bshe", x, p["wq"]), positions,
                     THETA)
    k = L.apply_rope(jnp.einsum("bsd,dne->bsne", x, p["wk"]), positions,
                     THETA)
    v = jnp.einsum("bsd,dne->bsne", x, p["wv"])
    return q, k, v


@pytest.mark.parametrize("cache_idx", [0, 11])
@pytest.mark.parametrize("group", [1, 2, 4])
@pytest.mark.parametrize("sq", [1, 16])
def test_cache_attention_matches_mha(sq, group, cache_idx):
    """``layers.attention`` over a heads-major cache (b, n, S, e) equals
    ``layers.mha`` over the same keys and values in (b, S, n, e) order.
    The cache past the written positions holds noise, which the
    ``kv_valid_len`` mask must hide: the reference attends to the valid
    positions alone."""
    n = H // group
    ks = jax.random.split(jax.random.PRNGKey(sq * 100 + group * 10
                                             + cache_idx), 4)
    p = L.init_attention(ks[0], D, H, n, E, False, jnp.float32)
    x = jax.random.normal(ks[1], (B, sq, D), jnp.float32)
    cache = {"k": jax.random.normal(ks[2], (B, n, S, E), jnp.float32),
             "v": jax.random.normal(ks[3], (B, n, S, E), jnp.float32)}
    positions = cache_idx + jnp.arange(sq)

    y, new = L.attention(p, x, positions=positions, theta=THETA,
                         cache=cache, cache_idx=jnp.int32(cache_idx))

    q, k, v = _qkv(p, x, positions)
    k_all = cache["k"].transpose(0, 2, 1, 3).at[
        :, cache_idx:cache_idx + sq].set(k)
    v_all = cache["v"].transpose(0, 2, 1, 3).at[
        :, cache_idx:cache_idx + sq].set(v)
    valid = cache_idx + sq
    out = L.mha(q, k_all[:, :valid], v_all[:, :valid], causal=True,
                q_positions=positions, kv_positions=jnp.arange(valid))
    want = jnp.einsum("bshe,hed->bsd", out, p["wo"])

    np.testing.assert_allclose(y, want, rtol=0, atol=1e-5)
    np.testing.assert_array_equal(new["k"], k_all.transpose(0, 2, 1, 3))
    np.testing.assert_array_equal(new["v"], v_all.transpose(0, 2, 1, 3))


@pytest.mark.parametrize("role", ["search", "chat"])
def test_dense_cache_is_heads_major(role):
    """``lm.init_cache`` lays a dense model's keys and values out as
    (layers, b, n_kv, S, e), at the served configs' published widths."""
    cfg = get_family("qwen3")[role]
    cache = jax.eval_shape(lambda: lm.init_cache(cfg, 8, 256))
    want = (cfg.num_layers, 8, cfg.num_kv_heads, 256, cfg.resolved_head_dim)
    assert cache["layers"]["k"].shape == want
    assert cache["layers"]["v"].shape == want


def test_ring_cache_decode_matches_windowed_attention():
    """The hybrid family's ring cache (heads-major, W slots on the position
    axis): decode steps past the window equal sliding-window attention
    over the whole sequence, position by position."""
    W, T, P = 8, 20, 6
    cfg = reduced(get_config("zamba2-1.2b"))
    n, e = cfg.num_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    p = L.init_attention(ks[0], cfg.d_model, cfg.num_heads, n, e, False,
                         jnp.float32)
    x = jax.random.normal(ks[1], (B, T, cfg.d_model), jnp.float32)
    want, _ = L.attention(p, x, positions=jnp.arange(T),
                          theta=cfg.rope_theta, window=W)

    cache = {"k": jnp.zeros((B, n, W, e), jnp.float32),
             "v": jnp.zeros((B, n, W, e), jnp.float32),
             "pos": jnp.full((W,), lm.NEG_POS, jnp.int32)}
    y, cache = lm._attend(p, cfg, x[:, :P], positions=jnp.arange(P),
                          causal=True, cache=cache, cache_idx=jnp.int32(0),
                          window=W)
    got = [y]
    for t in range(P, T):
        y, cache = lm._attend(p, cfg, x[:, t:t + 1],
                              positions=jnp.arange(t, t + 1), causal=True,
                              cache=cache, cache_idx=jnp.int32(t), window=W)
        got.append(y)
    np.testing.assert_allclose(jnp.concatenate(got, axis=1), want, rtol=0,
                               atol=1e-5)
