"""xLSTM blocks: mLSTM (matrix memory, chunkwise-parallel) and sLSTM
(scalar memory, sequential scan), following arXiv:2405.04517.

mLSTM state: (C (b,H,P,P) matrix memory, n (b,H,P) normalizer, m (b,H)
log-space stabilizer).  The chunkwise form processes Q-token chunks with an
intra-chunk masked quadratic term plus the carried inter-chunk state —
sub-quadratic in sequence length, O(1)-state decode.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import SSMConfig
from repro.models.layers import dense_init

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------

def init_mlstm(key, d: int, s: SSMConfig, dtype) -> Params:
    di = s.expand * d
    H = max(di // s.head_dim, 1)
    ks = jax.random.split(key, 6)
    return {
        "wq": dense_init(ks[0], (d, di), dtype),
        "wk": dense_init(ks[1], (d, di), dtype),
        "wv": dense_init(ks[2], (d, di), dtype),
        "wgate": dense_init(ks[3], (d, 2 * H), jnp.float32),  # i,f gate logits
        "gate_bias": jnp.concatenate(
            [jnp.zeros((H,)), 3.0 + jnp.arange(H, dtype=jnp.float32) * 0.5]),
        "conv": dense_init(ks[4], (s.conv_kernel, di), dtype),
        "w_out": dense_init(ks[5], (di, d), dtype, scale=di ** -0.5),
    }


def _mlstm_chunk(q, k, v, ig, fg, state):
    """One chunk of the stabilized chunkwise mLSTM.

    q/k/v (b,Q,H,P); ig/fg (b,Q,H) gate log-values; state (C,n,m).
    Returns (h (b,Q,H,P), new_state).
    """
    b, Q, H, P = q.shape
    C0, n0, m0 = state                                    # (b,H,P,P),(b,H,P),(b,H)
    lf = jax.nn.log_sigmoid(fg)                            # (b,Q,H)
    F = jnp.cumsum(lf, axis=1)                             # inclusive cumsum
    # intra-chunk log decay matrix: D[i,j] = F_i - F_j + ig_j  (j <= i)
    logD = (F[:, :, None, :] - F[:, None, :, :]
            + ig[:, None, :, :])                           # (b,Qi,Qj,H)
    mask = jnp.tril(jnp.ones((Q, Q), bool))[None, :, :, None]
    logD = jnp.where(mask, logD, -jnp.inf)
    # inter-chunk log decay: F_i + m0
    log_inter = F + m0[:, None, :]                         # (b,Q,H)
    m_new = jnp.maximum(jnp.max(logD, axis=2), log_inter)  # (b,Q,H) row max
    m_new = jnp.maximum(m_new, -1e30)                      # guard -inf rows
    D = jnp.exp(logD - m_new[:, :, None, :])               # (b,Qi,Qj,H)
    inter_w = jnp.exp(log_inter - m_new)                   # (b,Q,H)

    qf = q.astype(jnp.float32) / jnp.sqrt(jnp.float32(P))
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    scores = jnp.einsum("bqhp,bkhp->bqkh", qf, kf) * D     # (b,Qi,Qj,H)
    num = (jnp.einsum("bqkh,bkhp->bqhp", scores, vf)
           + inter_w[..., None] * jnp.einsum("bqhp,bhpe->bqhe", qf, C0))
    den = (scores.sum(axis=2)
           + inter_w * jnp.einsum("bqhp,bhp->bqh", qf, n0))
    h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_new))[..., None]

    # chunk-end state update
    Fend = F[:, -1, :]                                     # (b,H)
    m_end = jnp.maximum(Fend + m0, jnp.max(F[:, -1:, :] - F + ig, axis=1))
    w_prev = jnp.exp(Fend + m0 - m_end)                    # (b,H)
    w_tok = jnp.exp(Fend[:, None] - F + ig - m_end[:, None])  # (b,Q,H)
    C1 = (w_prev[..., None, None] * C0
          + jnp.einsum("bqh,bqhp,bqhe->bhpe", w_tok, kf, vf))
    n1 = w_prev[..., None] * n0 + jnp.einsum("bqh,bqhp->bhp", w_tok, kf)
    return h, (C1, n1, m_end)


def mlstm_forward(p: Params, x: jax.Array, s: SSMConfig, *,
                  init_state: Optional[Params] = None,
                  return_state: bool = False
                  ) -> Tuple[jax.Array, Optional[Params]]:
    """x (b,l,d), l a multiple of chunk (or l < chunk)."""
    from repro.models.ssm import _causal_conv
    b, l_real, d = x.shape
    di = s.expand * d
    H, P = max(di // s.head_dim, 1), s.head_dim
    Q = min(s.chunk_size, l_real)
    # pad to a chunk multiple; padded positions made state-neutral:
    # f-gate -> 1 (log 0), i-gate -> 0 (log -inf)
    l = -(-l_real // Q) * Q
    if l != l_real:
        x = jnp.pad(x, ((0, 0), (0, l - l_real), (0, 0)))
    nc = l // Q
    dtype = x.dtype

    conv_s = init_state["conv"] if init_state else None
    gates = (jnp.einsum("bld,dg->blg", x.astype(jnp.float32), p["wgate"])
             + p["gate_bias"])
    ig, fg = gates[..., :H], gates[..., H:]
    if l != l_real:
        valid = (jnp.arange(l) < l_real)[None, :, None]
        ig = jnp.where(valid, ig, -1e30)
        fg = jnp.where(valid, fg, 30.0)   # log_sigmoid(30) ~ 0
    xq, new_conv = _causal_conv(
        jnp.einsum("bld,de->ble", x, p["wq"]), p["conv"], conv_s,
        state_len=l_real)
    k = jnp.einsum("bld,de->ble", x, p["wk"]).reshape(b, l, H, P)
    v = jnp.einsum("bld,de->ble", x, p["wv"]).reshape(b, l, H, P)
    q = xq.reshape(b, l, H, P)

    if init_state is not None:
        st = (init_state["C"].astype(jnp.float32),
              init_state["n"].astype(jnp.float32),
              init_state["m"].astype(jnp.float32))
    else:
        st = (jnp.zeros((b, H, P, P), jnp.float32),
              jnp.zeros((b, H, P), jnp.float32),
              jnp.full((b, H), -1e30, jnp.float32))

    def step(carry, inp):
        qc, kc, vc, igc, fgc = inp
        h, new = _mlstm_chunk(qc, kc, vc, igc, fgc, carry)
        return new, h

    xs = (q.reshape(b, nc, Q, H, P).transpose(1, 0, 2, 3, 4),
          k.reshape(b, nc, Q, H, P).transpose(1, 0, 2, 3, 4),
          v.reshape(b, nc, Q, H, P).transpose(1, 0, 2, 3, 4),
          ig.reshape(b, nc, Q, H).transpose(1, 0, 2, 3),
          fg.reshape(b, nc, Q, H).transpose(1, 0, 2, 3))
    final, hs = jax.lax.scan(step, st, xs)                 # hs (nc,b,Q,H,P)
    h = hs.transpose(1, 0, 2, 3, 4).reshape(b, l, di).astype(dtype)
    out = jnp.einsum("ble,ed->bld", h, p["w_out"])
    if l != l_real:
        out = out[:, :l_real]
    if not return_state:
        return out, None
    C1, n1, m1 = final
    return out, {"C": C1.astype(jnp.float32), "n": n1.astype(jnp.float32),
                 "m": m1, "conv": new_conv}


def init_mlstm_state(batch: int, d: int, s: SSMConfig, dtype) -> Params:
    di = s.expand * d
    H, P = max(di // s.head_dim, 1), s.head_dim
    return {"C": jnp.zeros((batch, H, P, P), jnp.float32),
            "n": jnp.zeros((batch, H, P), jnp.float32),
            "m": jnp.full((batch, H), -1e30, jnp.float32),
            "conv": jnp.zeros((batch, s.conv_kernel - 1, di), dtype)}


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------

def init_slstm(key, d: int, dtype) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "W": dense_init(ks[0], (d, 4 * d), dtype),     # i,f,z,o input weights
        "R": dense_init(ks[1], (d, 4 * d), dtype),     # recurrent weights
        "bias": jnp.zeros((4 * d,), jnp.float32),
        "w_out": dense_init(ks[2], (d, d), dtype),
    }


def slstm_forward(p: Params, x: jax.Array, *,
                  init_state: Optional[Params] = None,
                  return_state: bool = False
                  ) -> Tuple[jax.Array, Optional[Params]]:
    """Sequential scan over time.  x (b,l,d)."""
    b, l, d = x.shape
    dtype = x.dtype
    if init_state is not None:
        st = tuple(init_state[k].astype(jnp.float32) for k in "cnhm")
    else:
        z = jnp.zeros((b, d), jnp.float32)
        st = (z, z, z, jnp.full((b, d), -1e30, jnp.float32))

    wx = jnp.einsum("bld,de->ble", x, p["W"]).astype(jnp.float32) + p["bias"]

    def step(carry, wx_t):
        c, n, h, m = carry
        g = wx_t + jnp.einsum("bd,de->be", h.astype(dtype),
                              p["R"]).astype(jnp.float32)
        gi, gf, gz, go = jnp.split(g, 4, axis=-1)
        m_new = jnp.maximum(gf + m, gi)                 # exp-gate stabilizer
        i = jnp.exp(gi - m_new)
        f = jnp.exp(gf + m - m_new)
        c = f * c + i * jnp.tanh(gz)
        n = f * n + i
        h = jax.nn.sigmoid(go) * c / jnp.maximum(n, 1.0)
        return (c, n, h, m_new), h

    final, hs = jax.lax.scan(step, st, wx.transpose(1, 0, 2))
    h = hs.transpose(1, 0, 2).astype(dtype)
    out = jnp.einsum("bld,de->ble", h, p["w_out"])
    if not return_state:
        return out, None
    c, n, h_l, m = final
    return out, {"c": c, "n": n, "h": h_l, "m": m}


def init_slstm_state(batch: int, d: int) -> Params:
    z = jnp.zeros((batch, d), jnp.float32)
    return {"c": z, "n": z, "h": z, "m": jnp.full((batch, d), -1e30,
                                                  jnp.float32)}
