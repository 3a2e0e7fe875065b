"""Core neural-net layers shared by all architectures.

Pure-functional JAX: parameters are nested dicts of arrays, every layer is a
``init_*`` / ``apply_*`` pair.  Einsum dimension names used throughout:
``b`` batch, ``s``/``q``/``k`` sequence, ``d`` d_model, ``h`` heads,
``n`` kv-heads, ``g`` q-heads-per-kv-group, ``e`` head_dim, ``f`` d_ff.

Numerics: matmuls run in the param dtype (bf16 on TPU), softmax / norms in
float32.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.sharding import constrain

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[0]
    scale = scale if scale is not None else fan_in ** -0.5
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * scale).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            ).astype(dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype=jnp.float32)}


def rmsnorm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * p["scale"]).astype(dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """(head_dim//2,) inverse frequencies."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               inv_freq: Optional[jax.Array] = None) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq).
    ``inv_freq`` (head_dim//2,) replaces the plain ``theta`` frequencies
    (YaRN, ``models/mla.py``)."""
    head_dim = x.shape[-1]
    inv = rope_freqs(head_dim, theta) if inv_freq is None else inv_freq
    ang = positions[..., None].astype(jnp.float32) * inv    # (..., s, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[..., None, :]                                  # (..., s, 1, hd/2)
    sin = sin[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Dense (GQA) attention
# ---------------------------------------------------------------------------

def init_attention(key, d: int, n_heads: int, n_kv: int, head_dim: int,
                   bias: bool, dtype) -> Params:
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, n_heads, head_dim), dtype),
        "wk": dense_init(ks[1], (d, n_kv, head_dim), dtype),
        "wv": dense_init(ks[2], (d, n_kv, head_dim), dtype),
        "wo": dense_init(ks[3], (n_heads, head_dim, d), dtype,
                         scale=(n_heads * head_dim) ** -0.5),
    }
    if bias:
        p["bq"] = jnp.zeros((n_heads, head_dim), dtype)
        p["bk"] = jnp.zeros((n_kv, head_dim), dtype)
        p["bv"] = jnp.zeros((n_kv, head_dim), dtype)
    return p


def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q: (b, sq, h, e), k: (b, sk, n, e) -> scores (b, n, g, sq, sk)."""
    b, sq, h, e = q.shape
    n = k.shape[2]
    g = h // n
    q = q.reshape(b, sq, n, g, e)
    return jnp.einsum("bqnge,bkne->bngqk", q, k,
                      preferred_element_type=jnp.float32)


def _gqa_out(probs: jax.Array, v: jax.Array) -> jax.Array:
    """probs: (b, n, g, sq, sk), v: (b, sk, n, e) -> (b, sq, h, e)."""
    b, n, g, sq, sk = probs.shape
    out = jnp.einsum("bngqk,bkne->bqnge", probs, v)
    return out.reshape(b, sq, n * g, out.shape[-1])


def mha(q: jax.Array, k: jax.Array, v: jax.Array, *,
        causal: bool, q_positions: Optional[jax.Array] = None,
        kv_positions: Optional[jax.Array] = None,
        kv_valid_len: Optional[jax.Array] = None,
        window: int = 0,
        bias_extra: Optional[jax.Array] = None) -> jax.Array:
    """Reference multi-head GQA attention (the jnp oracle path; the Pallas
    flash kernels in repro.kernels implement the same contract).

    q (b,sq,h,e), k/v (b,sk,n,e).  ``kv_valid_len`` masks a KV cache tail.
    ``window`` > 0 enables sliding-window attention (sub-quadratic archs).
    """
    b, sq, h, e = q.shape
    sk = k.shape[1]
    scores = _gqa_scores(q, k) / jnp.sqrt(e).astype(jnp.float32)
    if bias_extra is not None:
        scores = scores + bias_extra
    mask = None
    if q_positions is None:
        q_positions = jnp.arange(sq)
    if kv_positions is None:
        kv_positions = jnp.arange(sk)
    qp = q_positions.reshape(-1, 1) if q_positions.ndim == 1 else q_positions
    kp = kv_positions.reshape(1, -1) if kv_positions.ndim == 1 else kv_positions
    if causal:
        mask = qp >= kp                                  # (sq, sk) or (b,...)
    if window > 0:
        wmask = qp - kp < window
        mask = wmask if mask is None else (mask & wmask)
    if kv_valid_len is not None:
        vmask = kv_positions.reshape(1, -1) < kv_valid_len.reshape(-1, 1)
        vmask = vmask[:, None, None, None, :]            # (b,1,1,1,sk)
        scores = jnp.where(vmask, scores, -jnp.inf)
    if mask is not None:
        while mask.ndim < 5:
            mask = mask[None]
        scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    # rows that are fully masked produce NaN; zero them out
    probs = jnp.where(jnp.isnan(probs), 0.0, probs).astype(v.dtype)
    return _gqa_out(probs, v)


def mha_chunked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                causal: bool, block: int = 1024) -> jax.Array:
    """Flash-pattern attention in pure XLA: lax.scan over KV blocks with an
    online softmax.  Materializes (b, n, g, sq, block) instead of the full
    (…, sq, sk) score matrix — the memory-roofline fix for long-sequence
    train/prefill (§Perf iteration 1); exact (not approximate).

    On TPU the Pallas flash kernel replaces this; the XLA form keeps the
    dry-run roofline honest and is the CPU-correct fallback."""
    b, sq, h, e = q.shape
    sk, n = k.shape[1], k.shape[2]
    g = h // n
    blk = min(block, sk)
    nb = -(-sk // blk)
    pad = nb * blk - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qf = q.astype(jnp.float32).reshape(b, sq, n, g, e) / jnp.sqrt(
        jnp.float32(e))
    kb = k.reshape(b, nb, blk, n, e).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nb, blk, n, e).transpose(1, 0, 2, 3, 4)
    qpos = jnp.arange(sq)
    kpos = jnp.arange(nb * blk).reshape(nb, blk)

    def body(carry, inp):
        m, l, acc = carry
        kblk, vblk, kp = inp
        s = jnp.einsum("bqnge,bkne->bngqk", qf, kblk.astype(jnp.float32))
        mask = kp[None, :] < sk
        if causal:
            mask = mask & (qpos[:, None] >= kp[None, :])
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(jnp.isnan(p), 0.0, p)
        alpha = jnp.exp(m - m_new)
        alpha = jnp.where(jnp.isnan(alpha), 0.0, alpha)
        l_new = alpha * l + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bngqk,bkne->bngqe", p, vblk.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, n, g, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, n, g, sq), jnp.float32)
    a0 = jnp.zeros((b, n, g, sq, e), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kb, vb, kpos))
    safe = jnp.where(l == 0.0, 1.0, l)
    out = (acc / safe[..., None]).astype(q.dtype)          # (b,n,g,sq,e)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, e)


def attention(p: Params, x: jax.Array, *, positions: jax.Array,
              theta: float, causal: bool = True,
              cache: Optional[Params] = None,
              cache_idx: Optional[jax.Array] = None,
              window: int = 0,
              kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,
              impl: str = "reference",
              ) -> Tuple[jax.Array, Optional[Params]]:
    """Full attention block: qkv projection + rope + mha + output proj.

    cache: {"k": (b, S, n, e), "v": ...} updated at ``cache_idx``.
    kv_override: precomputed (k, v) for cross-attention (no rope on kv).
    """
    dtype = x.dtype
    q = constrain(jnp.einsum("bsd,dhe->bshe", x, p["wq"]),
                  ("batch", None, "model", None))
    if "bq" in p:
        q = q + p["bq"]
    if kv_override is not None:
        k, v = kv_override
        q = q.astype(dtype)
        out = mha(q, k, v, causal=False)
    else:
        k = jnp.einsum("bsd,dne->bsne", x, p["wk"])
        v = jnp.einsum("bsd,dne->bsne", x, p["wv"])
        if "bk" in p:
            k, v = k + p["bk"], v + p["bv"]
        q = apply_rope(q, positions, theta)
        k = apply_rope(k, positions, theta)
        if cache is None and impl == "chunked" and window == 0:
            out = mha_chunked(q, k, v, causal=causal)
            y = jnp.einsum("bshe,hed->bsd", out.astype(dtype), p["wo"])
            return y, None
        if cache is not None:
            # decode / chunked prefill: write new kv at cache_idx, attend to
            # the whole (valid prefix of the) cache
            S = cache["k"].shape[1]
            sq = q.shape[1]
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), cache_idx, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), cache_idx, axis=1)
            cache = {"k": k_cache, "v": v_cache}
            valid = (cache_idx + sq) * jnp.ones((x.shape[0],), jnp.int32)
            out = mha(q, k_cache, v_cache, causal=True,
                      q_positions=positions,
                      kv_positions=jnp.arange(S), kv_valid_len=valid,
                      window=window)
        else:
            out = mha(q, k, v, causal=causal, q_positions=positions,
                      kv_positions=positions, window=window)
    y = jnp.einsum("bshe,hed->bsd", out.astype(dtype), p["wo"])
    return y, cache


def init_cache_attention(batch: int, max_len: int, n_kv: int, head_dim: int,
                         dtype) -> Params:
    return {"k": jnp.zeros((batch, max_len, n_kv, head_dim), dtype),
            "v": jnp.zeros((batch, max_len, n_kv, head_dim), dtype)}


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def init_mlp(key, d: int, d_ff: int, gated: bool, dtype) -> Params:
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[0], (d, d_ff), dtype),
         "w_down": dense_init(ks[1], (d_ff, d), dtype)}
    if gated:
        p["w_gate"] = dense_init(ks[2], (d, d_ff), dtype)
    return p


def mlp(p: Params, x: jax.Array) -> jax.Array:
    up = jnp.einsum("bsd,df->bsf", x, p["w_up"])
    if "w_gate" in p:
        gate = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
        h = jax.nn.silu(gate) * up
    else:
        h = jax.nn.gelu(up)
    h = constrain(h, ("batch", None, "model"))
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"])
