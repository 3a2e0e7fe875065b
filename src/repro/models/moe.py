"""Mixture-of-Experts layer (DeepSeek-style), dropless, over the routed
experts this device holds.

The router spans all ``num_experts`` routed experts: softmax over its
logits, greedy top-k, the gates renormalised only where
``norm_topk_prob``, then scaled by ``routed_scaling_factor``.  The device
holds experts ``[first_expert, first_expert + held)`` (expert parallelism;
all of them by default) and computes their part of the result: each
output is weighted by the token's gate for that expert, 0 where the token
did not route to it.  In a decode step (given the layer stacks and the
layer's index) only the held experts some token routes to are read
(``kernels.ops.moe_decode``, a grouped expert kernel on TPU); otherwise
every held expert runs on every token.  No assignment is ever dropped, at
any token count.  The other devices' part is left out; on one device the
layer runs without the exchange.  Shared experts (always on) are added on
every device.

Expert ``e``'s weights are drawn from ``fold_in(<layer's expert key>, e)``,
so a device that holds a share draws exactly the experts the whole layer
would hold.  The layer returns the switch-style auxiliary load-balance
loss and, given a mask of the tokens to count, what the router did
(``route_stats``).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.kernels import ops, ref
from repro.models.layers import dense_init

Params = Dict[str, Any]

# route_stats entries, in order: assignments over all experts, those on
# held experts, held experts with at least one assignment, held
# assignments whose expert the layer read, and held experts whose weights
# the layer read (for every row, the uncounted ones too)
ROUTE_STATS = ("routed", "routed_held", "experts_hit", "computed",
               "experts_read")
# a held expert's weights, stacked over the held experts
EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def init_moe(key, d: int, cfg: MoEConfig, dtype) -> Params:
    ff = cfg.d_ff
    k_router, k_experts, k_shared = jax.random.split(key, 3)

    def expert(e):
        kg, ku, kd = jax.random.split(jax.random.fold_in(k_experts, e), 3)
        return {"w_gate": dense_init(kg, (d, ff), dtype),
                "w_up": dense_init(ku, (d, ff), dtype),
                "w_down": dense_init(kd, (ff, d), dtype)}

    p = {"router": dense_init(k_router, (d, cfg.num_experts), jnp.float32),
         **jax.vmap(expert)(cfg.first_expert + jnp.arange(cfg.held))}
    if cfg.num_shared_experts:
        sff = ff * cfg.num_shared_experts
        ks2 = jax.random.split(k_shared, 3)
        p["shared"] = {
            "w_gate": dense_init(ks2[0], (d, sff), dtype),
            "w_up": dense_init(ks2[1], (d, sff), dtype),
            "w_down": dense_init(ks2[2], (sff, d), dtype),
        }
    return p


def _router(p: Params, x2: jax.Array, cfg: MoEConfig
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x2: (T, d) -> gates (T, k) float32, idx (T, k), aux_loss (scalar)."""
    logits = jnp.einsum("td,de->te", x2.astype(jnp.float32), p["router"],
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    gates = gates * cfg.routed_scaling_factor
    # switch-style load-balance auxiliary loss
    E = cfg.num_experts
    me = jnp.mean(probs, axis=0)                                   # (E,)
    ce = jnp.mean(jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(me * ce) * cfg.router_aux_loss
    return gates, idx, aux


def moe_ffn(p: Params, x: jax.Array, cfg: MoEConfig, *,
            token_mask: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """x: (b, s, d) -> (y, aux_loss, route_stats).  ``token_mask`` (b, s)
    bool selects the tokens ``route_stats`` counts (int32, ordered as
    ``ROUTE_STATS``); without it the stats are None.  Where ``p`` holds a
    ``layer`` index, its ``EXPERT_WEIGHTS`` are the whole stacks of the
    MoE layers (``[L, held, ...]``) and this is that layer of them: only
    the held experts some token routes to are read."""
    b, s, d = x.shape
    T = b * s
    x2 = x.reshape(T, d)
    gates, idx, aux = _router(p, x2, cfg)                          # (T,k)
    local = idx - cfg.first_expert
    on_held = (local >= 0) & (local < cfg.held)
    # (T, k, held): which held expert each assignment goes to, if any
    assign = jax.nn.one_hot(jnp.where(on_held, local, -1), cfg.held,
                            dtype=jnp.float32)
    combine = jnp.einsum("tk,tkh->th", gates, assign)              # (T,held)

    w = [p[k] for k in EXPERT_WEIGHTS]
    if "layer" in p:
        y, read = ops.moe_decode(x2, combine, *w, p["layer"])
    else:
        # every held expert on every token (dropless)
        y = ref.moe_decode_ref(x2, combine, *w)
        read = jnp.ones((cfg.held,), bool)

    if cfg.num_shared_experts:
        sp = p["shared"]
        h = jax.nn.silu(jnp.einsum("td,df->tf", x2, sp["w_gate"])) \
            * jnp.einsum("td,df->tf", x2, sp["w_up"])
        y = y + jnp.einsum("tf,fd->td", h, sp["w_down"],
                           preferred_element_type=jnp.float32)
    y = y.astype(x.dtype)

    stats = None
    if token_mask is not None:
        real = token_mask.reshape(T, 1).astype(jnp.int32)
        held_real = assign * real[:, :, None]                       # (T,k,h)
        per_expert = held_real.sum(axis=(0, 1))                     # (held,)
        stats = jnp.stack([
            real.sum() * cfg.top_k,
            (on_held * real).sum(),
            (per_expert > 0).sum(),
            # the assignments whose gate reached an expert that was read
            ((combine > 0) * read * real).sum(),
            read.sum(),
        ]).astype(jnp.int32)
    return y.reshape(b, s, d), aux, stats
