"""Mamba2 (SSD) block: chunked-scan forward/prefill + O(1) decode step.

Chunked scan follows the SSD formulation (Dao & Gu, 2024): the sequence is
split into chunks of ``Q`` tokens; the intra-chunk term is a masked
quadratic (attention-like) contraction, inter-chunk information flows through
a per-chunk state recurrence of shape (heads, head_dim, state).

Shapes: x (b, l, d); d_inner = expand*d; H = d_inner // P heads;
B/C projections are per-group (G groups, shared across H//G heads).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import SSMConfig
from repro.models.layers import dense_init

Params = Dict[str, Any]


def init_mamba2(key, d: int, s: SSMConfig, dtype) -> Params:
    di = s.expand * d
    H = di // s.head_dim
    gn = s.ngroups * s.state_size
    ks = jax.random.split(key, 8)
    return {
        "wz": dense_init(ks[0], (d, di), dtype),
        "wx": dense_init(ks[1], (d, di), dtype),
        "wB": dense_init(ks[2], (d, gn), dtype),
        "wC": dense_init(ks[3], (d, gn), dtype),
        "wdt": dense_init(ks[4], (d, H), dtype),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H)).astype(jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "conv_x": dense_init(ks[5], (s.conv_kernel, di), dtype),
        "conv_B": dense_init(ks[6], (s.conv_kernel, gn), dtype),
        "conv_C": dense_init(ks[7], (s.conv_kernel, gn), dtype),
        "w_out": dense_init(jax.random.fold_in(key, 9), (di, d), dtype,
                         scale=di ** -0.5),
    }


def _causal_conv(x: jax.Array, w: jax.Array,
                 state: Optional[jax.Array] = None,
                 state_len: Optional[int] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal conv.  x (b,l,c), w (K,c).  state (b,K-1,c) carries
    the last K-1 inputs for streaming decode.  ``state_len`` = number of
    *real* (unpadded) positions; the new state is the last K-1 real inputs.
    Returns (y, new_state)."""
    K = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)                    # (b, l+K-1, c)
    y = sum(xp[:, i:i + x.shape[1], :] * w[i] for i in range(K))
    if K > 1:
        sl = x.shape[1] if state_len is None else state_len
        new_state = jax.lax.dynamic_slice_in_dim(xp, sl, K - 1, axis=1)
    else:
        new_state = state
    return jax.nn.silu(y), new_state


def _segsum(a: jax.Array) -> jax.Array:
    """a (..., q) -> (..., q, q) with out[i,j] = sum_{j<t<=i} a_t (i>=j),
    -inf above the diagonal."""
    q = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((q, q), bool))
    return jnp.where(mask, diff, -jnp.inf)


def mamba2_forward(p: Params, x: jax.Array, s: SSMConfig, *,
                   init_state: Optional[Params] = None,
                   return_state: bool = False
                   ) -> Tuple[jax.Array, Optional[Params]]:
    """Chunked scan.  x (b,l,d); l must be a multiple of chunk (padded by
    caller otherwise).  init_state/new_state: {"ssm": (b,H,P,N), "conv_*"}."""
    b, l_real, d = x.shape
    di = s.expand * d
    H, P, N, G = di // s.head_dim, s.head_dim, s.state_size, s.ngroups
    Q = min(s.chunk_size, l_real)
    # pad to a chunk multiple; padded positions are made state-neutral by
    # forcing dt=0 there (decay=1, zero contribution)
    l = -(-l_real // Q) * Q
    if l != l_real:
        x = jnp.pad(x, ((0, 0), (0, l - l_real), (0, 0)))
    nc = l // Q
    dtype = x.dtype

    z = jnp.einsum("bld,de->ble", x, p["wz"])
    xc = jnp.einsum("bld,de->ble", x, p["wx"])
    Bc = jnp.einsum("bld,de->ble", x, p["wB"])
    Cc = jnp.einsum("bld,de->ble", x, p["wC"])
    dt = jax.nn.softplus(
        jnp.einsum("bld,dh->blh", x, p["wdt"]).astype(jnp.float32)
        + p["dt_bias"])                                           # (b,l,H)
    if l != l_real:
        dt = dt * (jnp.arange(l) < l_real)[None, :, None]

    conv_xs = init_state["conv_x"] if init_state else None
    conv_Bs = init_state["conv_B"] if init_state else None
    conv_Cs = init_state["conv_C"] if init_state else None
    xc, ncx = _causal_conv(xc, p["conv_x"], conv_xs, state_len=l_real)
    Bc, ncB = _causal_conv(Bc, p["conv_B"], conv_Bs, state_len=l_real)
    Cc, ncC = _causal_conv(Cc, p["conv_C"], conv_Cs, state_len=l_real)

    A = -jnp.exp(p["A_log"])                                      # (H,)
    xh = xc.reshape(b, l, H, P)
    Bg = Bc.reshape(b, l, G, N)
    Cg = Cc.reshape(b, l, G, N)
    rep = H // G

    # chunked views
    xh = xh.reshape(b, nc, Q, H, P)
    Bg = Bg.reshape(b, nc, Q, G, N)
    Cg = Cg.reshape(b, nc, Q, G, N)
    dt = dt.reshape(b, nc, Q, H)
    dA = dt * A                                                   # (b,nc,Q,H)
    dtx = (dt[..., None] * xh.astype(jnp.float32))                # dt-weighted x

    # ---- intra-chunk (quadratic within chunk) ----
    L = jnp.exp(_segsum(dA.transpose(0, 1, 3, 2)))                # (b,nc,H,Q,Q)
    scores = jnp.einsum("bcqgn,bckgn->bcgqk", Cg.astype(jnp.float32),
                        Bg.astype(jnp.float32))                   # (b,nc,G,Q,Q)
    scores = jnp.repeat(scores, rep, axis=2)                      # per head
    y_intra = jnp.einsum("bchqk,bckhp->bcqhp", scores * L, dtx)

    # ---- chunk states ----
    dA_cum = jnp.cumsum(dA, axis=2)                               # (b,nc,Q,H)
    decay_to_end = jnp.exp(dA_cum[:, :, -1:, :] - dA_cum)         # (b,nc,Q,H)
    # S_c = sum_j decay_j * B_j ⊗ dtx_j  -> (b,nc,H,N,P)
    Bh = jnp.repeat(Bg, rep, axis=3).astype(jnp.float32)          # (b,nc,Q,H*? )
    S = jnp.einsum("bcqhn,bcqhp->bchnp", Bh * decay_to_end[..., None], dtx)

    # ---- inter-chunk recurrence over nc chunks ----
    chunk_decay = jnp.exp(dA_cum[:, :, -1, :])                    # (b,nc,H)
    s0 = (init_state["ssm"].astype(jnp.float32) if init_state
          else jnp.zeros((b, H, P, N), jnp.float32))

    def step(carry, inp):
        S_c, g = inp                                              # (b,H,N,P),(b,H)
        prev = carry
        new = prev * g[..., None, None] + S_c.transpose(0, 1, 3, 2)
        return new, prev                                          # emit state *before* chunk

    final_state, prev_states = jax.lax.scan(
        step, s0, (S.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)            # (b,nc,H,P,N)

    # ---- inter-chunk output ----
    in_decay = jnp.exp(dA_cum)                                    # (b,nc,Q,H)
    Ch = jnp.repeat(Cg, rep, axis=3).astype(jnp.float32)
    y_inter = jnp.einsum("bcqhn,bchpn->bcqhp", Ch * in_decay[..., None],
                         prev_states)

    y = (y_intra + y_inter).reshape(b, l, H, P)
    y = y + p["D"][:, None] * xc.reshape(b, l, H, P).astype(jnp.float32)
    y = y.reshape(b, l, di).astype(dtype)
    y = y * jax.nn.silu(z)
    out = jnp.einsum("ble,ed->bld", y, p["w_out"])
    if l != l_real:
        out = out[:, :l_real]
    if not return_state:
        return out, None
    return out, {"ssm": final_state.astype(dtype), "conv_x": ncx,
                 "conv_B": ncB, "conv_C": ncC}


def mamba2_step(p: Params, x: jax.Array, s: SSMConfig, state: Params
                ) -> Tuple[jax.Array, Params]:
    """Single-token decode.  x (b,1,d).  O(1) in context length."""
    out, new_state = mamba2_forward(
        p, x, s, init_state=state, return_state=True)
    return out, new_state


def init_mamba2_state(batch: int, d: int, s: SSMConfig, dtype) -> Params:
    di = s.expand * d
    H, P, N = di // s.head_dim, s.head_dim, s.state_size
    gn = s.ngroups * s.state_size
    K = s.conv_kernel
    return {"ssm": jnp.zeros((batch, H, P, N), dtype),
            "conv_x": jnp.zeros((batch, K - 1, di), dtype),
            "conv_B": jnp.zeros((batch, K - 1, gn), dtype),
            "conv_C": jnp.zeros((batch, K - 1, gn), dtype)}
