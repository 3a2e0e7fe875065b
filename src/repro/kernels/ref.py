"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal=True, scale=None):
    """q (b,sq,h,e), k/v (b,sk,n,e) GQA."""
    from repro.models.layers import mha
    b, sq, h, e = q.shape
    scale_ = scale if scale is not None else e ** -0.5
    # mha scales by 1/sqrt(e) internally; rescale if a custom scale is given
    if scale is not None and scale != e ** -0.5:
        q = q * (scale_ * e ** 0.5)
    return mha(q, k, v, causal=causal)


def decode_attention_ref(q, k_cache, v_cache, lengths):
    """q (b,h,e); caches (b,S,n,e); lengths (b,)."""
    from repro.models.layers import mha
    return mha(q[:, None], k_cache, v_cache, causal=False,
               kv_valid_len=lengths)[:, 0]


def int8_matmul_ref(x, w, sx, sw, out_dtype=jnp.bfloat16):
    acc = jnp.einsum("mk,kn->mn", x.astype(jnp.int32), w.astype(jnp.int32))
    return (acc.astype(jnp.float32) * sx.astype(jnp.float32)
            * sw.astype(jnp.float32)).astype(out_dtype)


def topk_retrieval_ref(queries, corpus, k, n_valid=None):
    s = jnp.einsum("qd,nd->qn", queries.astype(jnp.float32),
                   corpus.astype(jnp.float32))
    if n_valid is not None:
        s = jnp.where(jnp.arange(s.shape[1]) < n_valid, s, -1e30)
    vals, idxs = jax.lax.top_k(s, k)
    return vals, idxs.astype(jnp.int32)


def ssd_chunk_ref(x, dt, B, C, dA):
    """Intra-chunk SSD oracle.  Shapes as kernels.mamba2_scan.ssd_chunk."""
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    dtx = xf * dtf[..., None]
    cs = jnp.cumsum(dA.astype(jnp.float32), axis=2)     # (b,nc,Q,H)
    seg = cs[:, :, :, None, :] - cs[:, :, None, :, :]   # (b,nc,Qi,Qj,H)
    Q = x.shape[2]
    mask = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None]
    L = jnp.where(mask, jnp.exp(seg), 0.0)
    scores = jnp.einsum("bcqhn,bckhn->bcqkh", C.astype(jnp.float32),
                        B.astype(jnp.float32))
    y = jnp.einsum("bcqkh,bckhp->bcqhp", scores * L, dtx)
    decay_end = jnp.exp(cs[:, :, -1:, :] - cs)          # (b,nc,Q,H)
    S = jnp.einsum("bcqhn,bcqhp->bchnp",
                   B.astype(jnp.float32) * decay_end[..., None], dtx)
    return y, S
