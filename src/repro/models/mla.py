"""Multi-head Latent Attention (DeepSeek v2/v3).

Train/prefill use the *naive* form (materialize per-head K/V from the latent)
which is compute-optimal; decode uses the *absorbed* form (scores computed
directly against the cached latent) which is memory-optimal — exactly the KV
reduction MLA was designed for.  Cache = {ckv: (b, S, r), krope: (b, S, e_r)}.

q is projected through a low-rank latent (``q_lora_rank`` > 0: wq_a, q_norm,
wq_b) or directly (``q_lora_rank`` 0: wq, DeepSeek-V2-Lite).  With YaRN
(``MLAConfig.yarn``) the rope part uses NTK-by-parts frequencies and the
softmax scale gains the factor mscale².
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MLAConfig, YarnConfig
from repro.models.layers import apply_rope, dense_init, init_rmsnorm, rmsnorm

Params = Dict[str, Any]


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def rope_inv_freq(m: MLAConfig, theta: float) -> Optional[np.ndarray]:
    """(qk_rope_head_dim // 2,) YaRN frequencies, or None without YaRN:
    pairs below the fast correction bound keep theta's frequency, pairs
    from the slow bound up are divided by ``factor``, a linear ramp
    between."""
    y: YarnConfig = m.yarn
    if not y.enabled:
        return None
    dim = m.qk_rope_head_dim

    def bound(rotations):
        return dim * math.log(y.original_max_position_embeddings
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(bound(y.beta_fast)), 0)
    hi = min(math.ceil(bound(y.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    extra = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    return (extra / y.factor * ramp + extra * (1 - ramp)).astype(np.float32)


def softmax_scale(m: MLAConfig) -> float:
    """qk_head_dim ** -0.5, times mscale² under YaRN."""
    y = m.yarn
    ms = _yarn_mscale(y.factor, y.mscale_all_dim) if y.mscale_all_dim else 1.0
    return m.qk_head_dim ** -0.5 * ms * ms


def _rope_gain(m: MLAConfig) -> float:
    """YaRN's factor on the rotated rope part (1 where mscale ==
    mscale_all_dim, as in DeepSeek-V2)."""
    y = m.yarn
    return _yarn_mscale(y.factor, y.mscale) / _yarn_mscale(y.factor,
                                                           y.mscale_all_dim)


def _rope(x, positions, theta, m: MLAConfig):
    out = apply_rope(x, positions, theta, rope_inv_freq(m, theta))
    gain = _rope_gain(m)
    return out if gain == 1.0 else (out * gain).astype(out.dtype)


def init_mla(key, d: int, n_heads: int, m: MLAConfig, dtype) -> Params:
    ks = jax.random.split(key, 7)
    if m.q_lora_rank:
        q = {"wq_a": dense_init(ks[0], (d, m.q_lora_rank), dtype),
             "q_norm": init_rmsnorm(m.q_lora_rank, dtype),
             "wq_b": dense_init(ks[1], (m.q_lora_rank, n_heads,
                                        m.qk_head_dim), dtype)}
    else:
        q = {"wq": dense_init(ks[0], (d, n_heads, m.qk_head_dim), dtype)}
    return {
        **q,
        "wkv_a": dense_init(ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim), dtype),
        "kv_norm": init_rmsnorm(m.kv_lora_rank, dtype),
        "wk_b": dense_init(ks[3], (m.kv_lora_rank, n_heads, m.qk_nope_head_dim), dtype),
        "wv_b": dense_init(ks[4], (m.kv_lora_rank, n_heads, m.v_head_dim), dtype),
        "wo": dense_init(ks[5], (n_heads, m.v_head_dim, d), dtype,
                         scale=(n_heads * m.v_head_dim) ** -0.5),
    }


def _project_q(p: Params, x: jax.Array, m: MLAConfig, positions, theta):
    """-> q_nope (b,s,h,e_n), q_rope (b,s,h,e_r)."""
    if "wq" in p:
        q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    else:
        ql = rmsnorm(p["q_norm"], jnp.einsum("bsd,dr->bsr", x, p["wq_a"]))
        q = jnp.einsum("bsr,rhe->bshe", ql, p["wq_b"])
    q_nope = q[..., : m.qk_nope_head_dim]
    q_rope = _rope(q[..., m.qk_nope_head_dim:], positions, theta, m)
    return q_nope, q_rope


def _project_kv_latent(p: Params, x: jax.Array, m: MLAConfig, positions, theta):
    """-> ckv (b,s,r), k_rope (b,s,e_r) — what gets cached."""
    kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
    ckv = rmsnorm(p["kv_norm"], kv[..., : m.kv_lora_rank])
    k_rope = _rope(kv[..., None, m.kv_lora_rank:], positions, theta, m)
    return ckv, k_rope[..., 0, :]


def mla_attention(p: Params, x: jax.Array, m: MLAConfig, *,
                  positions: jax.Array, theta: float,
                  cache: Optional[Params] = None,
                  cache_idx: Optional[jax.Array] = None,
                  ) -> Tuple[jax.Array, Optional[Params]]:
    dtype = x.dtype
    b, s, d = x.shape
    scale = jnp.float32(softmax_scale(m))
    q_nope, q_rope = _project_q(p, x, m, positions, theta)
    ckv, k_rope = _project_kv_latent(p, x, m, positions, theta)

    if cache is None:
        # naive (compute-optimal) form for train / prefill
        k_nope = jnp.einsum("bsr,rhe->bshe", ckv, p["wk_b"])
        v = jnp.einsum("bsr,rhe->bshe", ckv, p["wv_b"])
        scores = (jnp.einsum("bqhe,bkhe->bhqk", q_nope, k_nope,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bqhe,bke->bhqk", q_rope, k_rope,
                               preferred_element_type=jnp.float32)) * scale
        mask = positions.reshape(-1, 1) >= positions.reshape(1, -1)
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
        out = jnp.einsum("bhqk,bkhe->bqhe", probs, v)
        new_cache = None
    else:
        # absorbed (memory-optimal) form for decode: fold wk_b into q and
        # wv_b after the latent-space attention — KV reads touch only the
        # (b, S, r + e_r) latent cache.
        S = cache["ckv"].shape[1]
        ckv_c = jax.lax.dynamic_update_slice_in_dim(
            cache["ckv"], ckv.astype(cache["ckv"].dtype), cache_idx, axis=1)
        krope_c = jax.lax.dynamic_update_slice_in_dim(
            cache["krope"], k_rope.astype(cache["krope"].dtype), cache_idx, axis=1)
        new_cache = {"ckv": ckv_c, "krope": krope_c}
        q_lat = jnp.einsum("bqhe,rhe->bqhr", q_nope, p["wk_b"])
        scores = (jnp.einsum("bqhr,bkr->bhqk", q_lat, ckv_c,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bqhe,bke->bhqk", q_rope, krope_c,
                               preferred_element_type=jnp.float32)) * scale
        valid = jnp.arange(S).reshape(1, -1) < (cache_idx + s)
        kv_pos = jnp.arange(S)
        causal = positions.reshape(-1, 1) >= kv_pos.reshape(1, -1)
        mask = causal[None, None] & valid[None, None]
        scores = jnp.where(mask, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
        out_lat = jnp.einsum("bhqk,bkr->bqhr", probs, ckv_c)
        out = jnp.einsum("bqhr,rhe->bqhe", out_lat, p["wv_b"])
    y = jnp.einsum("bqhe,hed->bqd", out.astype(dtype), p["wo"])
    return y, new_cache


def init_cache_mla(batch: int, max_len: int, m: MLAConfig, dtype) -> Params:
    return {"ckv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
            "krope": jnp.zeros((batch, max_len, m.qk_rope_head_dim), dtype)}
