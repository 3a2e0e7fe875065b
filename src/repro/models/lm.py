"""Generic language model assembling the layer zoo per ModelConfig.

Design notes:
- Pure functional: ``init_params`` builds a pytree, ``apply`` runs it.
- Homogeneous layer stacks are **scanned** (stacked params with a leading
  layer dim) — O(1) HLO size in depth, which keeps deep models' compiles
  tractable and is what production JAX frameworks do.
- One code path serves train / prefill / decode, switched by whether a
  cache pytree is provided.  Caches for scanned stacks are stacked arrays
  fed through ``lax.scan`` xs/ys.
- Sliding-window ring caches (bounded memory) activate for sub-quadratic
  archs at long context (Zamba2 long_500k).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models import xlstm as XL

Params = Dict[str, Any]

NEG_POS = -(1 << 30)  # ring-buffer "empty slot" position

# the cache's ``moe_stats`` entries: moe.ROUTE_STATS summed over the MoE
# layers of every decode step, then the MoE layer-steps counted
MOE_STATS = MOE.ROUTE_STATS + ("layer_steps",)


def _stack_init(fn, key, n: int):
    return jax.vmap(fn)(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# dense transformer block (attn + mlp) — used by dense/vlm/audio/hybrid-shared
# ---------------------------------------------------------------------------

def init_dense_block(key, cfg: ModelConfig, *, d_ff: Optional[int] = None,
                     cross: bool = False) -> Params:
    d = cfg.d_model
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    p = {
        "ln1": L.init_rmsnorm(d, dtype),
        "attn": L.init_attention(ks[0], d, cfg.num_heads, cfg.num_kv_heads,
                                 cfg.resolved_head_dim, cfg.qkv_bias, dtype),
        "ln2": L.init_rmsnorm(d, dtype),
        "mlp": L.init_mlp(ks[1], d, d_ff or cfg.d_ff, cfg.gated_mlp, dtype),
    }
    if cross:
        p["ln_x"] = L.init_rmsnorm(d, dtype)
        p["xattn"] = L.init_attention(ks[2], d, cfg.num_heads,
                                      cfg.num_kv_heads, cfg.resolved_head_dim,
                                      False, dtype)
        p["xgate"] = jnp.zeros((), jnp.float32)
    return p


def dense_block(p: Params, cfg: ModelConfig, x, *, positions, causal=True,
                cache=None, cache_idx=None, window=0, cross_kv=None,
                cross_cache=None):
    """Returns (x, new_cache, new_cross_cache)."""
    h, new_cache = _attend(p["attn"], cfg, L.rmsnorm(p["ln1"], x, cfg.norm_eps),
                           positions=positions, causal=causal, cache=cache,
                           cache_idx=cache_idx, window=window)
    x = x + h
    new_cross = None
    if "xattn" in p and (cross_kv is not None or cross_cache is not None):
        if cross_cache is not None:
            kv = (cross_cache["k"], cross_cache["v"])
            new_cross = cross_cache
        else:
            k = jnp.einsum("bsd,dne->bsne", cross_kv, p["xattn"]["wk"])
            v = jnp.einsum("bsd,dne->bsne", cross_kv, p["xattn"]["wv"])
            kv = (k, v)
            new_cross = {"k": k, "v": v}
        h, _ = L.attention(p["xattn"], L.rmsnorm(p["ln_x"], x, cfg.norm_eps),
                           positions=positions, theta=cfg.rope_theta,
                           kv_override=kv)
        x = x + jnp.tanh(p["xgate"]).astype(x.dtype) * h
    x = x + L.mlp(p["mlp"], L.rmsnorm(p["ln2"], x, cfg.norm_eps))
    return x, new_cache, new_cross


def _attend(p, cfg: ModelConfig, x, *, positions, causal, cache, cache_idx,
            window):
    """Dense attention with optional ring (windowed) cache."""
    if cache is not None and "pos" in cache:
        # ring buffer: write at idx % W
        W = cache["k"].shape[2]
        s = x.shape[1]
        slots = (cache_idx + jnp.arange(s)) % W
        q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
        if "bq" in p:
            q = q + p["bq"]
        k = jnp.einsum("bsd,dne->bsne", x, p["wk"])
        v = jnp.einsum("bsd,dne->bsne", x, p["wv"])
        if "bk" in p:
            k, v = k + p["bk"], v + p["bv"]
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
        kc = cache["k"].at[:, :, slots].set(
            k.transpose(0, 2, 1, 3).astype(cache["k"].dtype))
        vc = cache["v"].at[:, :, slots].set(
            v.transpose(0, 2, 1, 3).astype(cache["v"].dtype))
        pc = cache["pos"].at[slots].set(positions.astype(jnp.int32))
        out = L.mha(q, kc, vc, causal=True, q_positions=positions,
                    kv_positions=pc, window=window, kv_heads_major=True)
        y = jnp.einsum("bshe,hed->bsd", out.astype(x.dtype), p["wo"])
        return y, {"k": kc, "v": vc, "pos": pc}
    return L.attention(p, x, positions=positions, theta=cfg.rope_theta,
                       causal=causal, cache=cache, cache_idx=cache_idx,
                       window=window, impl=cfg.attn_impl)


# ---------------------------------------------------------------------------
# MoE (DeepSeek) block
# ---------------------------------------------------------------------------

def init_moe_block(key, cfg: ModelConfig, *, dense_ffn: bool) -> Params:
    d = cfg.d_model
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 2)
    p = {
        "ln1": L.init_rmsnorm(d, dtype),
        "mla": MLA.init_mla(ks[0], d, cfg.num_heads, cfg.mla, dtype),
        "ln2": L.init_rmsnorm(d, dtype),
    }
    if dense_ffn:
        p["mlp"] = L.init_mlp(ks[1], d, cfg.moe.dense_d_ff, True, dtype)
    else:
        p["moe"] = MOE.init_moe(ks[1], d, cfg.moe, dtype)
    return p


def moe_block(p: Params, cfg: ModelConfig, x, *, positions, cache=None,
              cache_idx=None, token_mask=None):
    """Returns (x, aux, new_cache, route_stats); the stats (the MoE
    layer's ``moe.ROUTE_STATS`` over ``token_mask``) are None for a dense
    layer or without a mask."""
    h, new_cache = MLA.mla_attention(
        p["mla"], L.rmsnorm(p["ln1"], x, cfg.norm_eps), cfg.mla,
        positions=positions, theta=cfg.rope_theta, cache=cache,
        cache_idx=cache_idx)
    x = x + h
    aux = jnp.zeros((), jnp.float32)
    stats = None
    h2 = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if "moe" in p:
        y, aux, stats = MOE.moe_ffn(p["moe"], h2, cfg.moe,
                                    token_mask=token_mask)
    else:
        y = L.mlp(p["mlp"], h2)
    return x + y, aux, new_cache, stats


# ---------------------------------------------------------------------------
# parameter init (per family)
# ---------------------------------------------------------------------------

def init_params(key, cfg: ModelConfig) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    d, V = cfg.d_model, cfg.vocab_size
    keys = jax.random.split(key, 8)
    params: Params = {
        "embed": L.embed_init(keys[0], (V, d), dtype),
        "final_norm": L.init_rmsnorm(d, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(keys[1], (d, V), dtype)

    fam = cfg.family
    if fam in ("dense",):
        params["blocks"] = _stack_init(
            lambda k: init_dense_block(k, cfg), keys[2], cfg.num_layers)
    elif fam == "vlm":
        n_groups = cfg.num_layers // (cfg.vlm.cross_attn_every)
        per_group = cfg.vlm.cross_attn_every - 1  # 1 cross + (N-1) self
        params["groups"] = _stack_init(
            lambda k: {
                "cross": init_dense_block(jax.random.fold_in(k, 0), cfg,
                                          cross=True),
                "selfs": _stack_init(
                    lambda k2: init_dense_block(k2, cfg),
                    jax.random.fold_in(k, 1), per_group),
            }, keys[2], n_groups)
    elif fam == "moe":
        nk = cfg.moe.first_k_dense
        params["dense_blocks"] = _stack_init(
            lambda k: init_moe_block(k, cfg, dense_ffn=True), keys[2], nk)
        params["moe_blocks"] = _stack_init(
            lambda k: init_moe_block(k, cfg, dense_ffn=False), keys[3],
            cfg.num_layers - nk)
    elif fam == "hybrid":
        params["blocks"] = _stack_init(
            lambda k: {"ln": L.init_rmsnorm(d, dtype),
                       "mamba": SSM.init_mamba2(k, d, cfg.ssm, dtype)},
            keys[2], cfg.num_layers)
        params["shared"] = init_dense_block(keys[3], cfg)  # ONE shared block
    elif fam == "ssm":
        blocks = []
        for i in range(cfg.num_layers):
            k = jax.random.fold_in(keys[2], i)
            if i in cfg.ssm.slstm_layers:
                blocks.append({"ln": L.init_rmsnorm(d, dtype),
                               "slstm": XL.init_slstm(k, d, dtype)})
            else:
                blocks.append({"ln": L.init_rmsnorm(d, dtype),
                               "mlstm": XL.init_mlstm(k, d, cfg.ssm, dtype)})
        params["blocks_list"] = blocks
    elif fam == "audio":
        params["encoder"] = {
            "blocks": _stack_init(lambda k: init_dense_block(k, cfg),
                                  keys[2], cfg.encdec.encoder_layers),
            "final_norm": L.init_rmsnorm(d, dtype),
        }
        params["blocks"] = _stack_init(
            lambda k: init_dense_block(k, cfg, cross=True), keys[3],
            cfg.num_layers)
    else:
        raise ValueError(f"unknown family {fam!r}")
    return params


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def _window_for(cfg: ModelConfig, max_len: int) -> int:
    """Sliding window for sub-quadratic archs at long context."""
    if cfg.subquadratic and cfg.family == "hybrid" and max_len > 32768:
        return 4096
    return 0


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    d = cfg.d_model
    hd, nkv = cfg.resolved_head_dim, cfg.num_kv_heads
    cache: Params = {"idx": jnp.zeros((), jnp.int32)}
    fam = cfg.family

    # keys and values heads-major, (layer, b, n, S, e): the order the
    # attention einsums read, so no step relays a layer's slice
    def attn_cache(n_layers, length, ring=False):
        c = {"k": jnp.zeros((n_layers, batch, nkv, length, hd), dtype),
             "v": jnp.zeros((n_layers, batch, nkv, length, hd), dtype)}
        if ring:
            c["pos"] = jnp.full((n_layers, length), NEG_POS, jnp.int32)
        return c

    if fam == "dense":
        cache["layers"] = attn_cache(cfg.num_layers, max_len)
    elif fam == "vlm":
        every = cfg.vlm.cross_attn_every
        n_groups = cfg.num_layers // every
        cache["cross_layers"] = attn_cache(n_groups, max_len)
        cache["self_layers"] = attn_cache(n_groups * (every - 1), max_len)
        cache["cross_kv"] = {
            "k": jnp.zeros((n_groups, batch, cfg.vlm.vision_tokens, nkv, hd),
                           dtype),
            "v": jnp.zeros((n_groups, batch, cfg.vlm.vision_tokens, nkv, hd),
                           dtype)}
    elif fam == "moe":
        m = cfg.mla
        cache["layers"] = {
            "ckv": jnp.zeros((cfg.num_layers, batch, max_len, m.kv_lora_rank),
                             dtype),
            "krope": jnp.zeros(
                (cfg.num_layers, batch, max_len, m.qk_rope_head_dim), dtype)}
        # what the MoE layers' routers did over the decode steps, summed
        # over layers and steps (MOE_STATS), counting the rows set in
        # ``rows`` (all of them unless the caller says otherwise)
        cache["moe_stats"] = jnp.zeros((len(MOE_STATS),), jnp.int32)
        cache["rows"] = jnp.ones((batch,), bool)
    elif fam == "hybrid":
        W = _window_for(cfg, max_len)
        n_attn = cfg.num_layers // cfg.ssm.attn_every
        cache["mamba"] = jax.vmap(
            lambda _: SSM.init_mamba2_state(batch, d, cfg.ssm, dtype))(
                jnp.arange(cfg.num_layers))
        cache["attn"] = attn_cache(n_attn, W or max_len, ring=bool(W))
    elif fam == "ssm":
        mstates, sstates = [], []
        for i in range(cfg.num_layers):
            if i in cfg.ssm.slstm_layers:
                sstates.append(XL.init_slstm_state(batch, d))
            else:
                mstates.append(XL.init_mlstm_state(batch, d, cfg.ssm, dtype))
        cache["mlstm"] = jax.tree.map(lambda *xs: jnp.stack(xs), *mstates)
        if sstates:
            cache["slstm"] = jax.tree.map(lambda *xs: jnp.stack(xs), *sstates)
    elif fam == "audio":
        cache["layers"] = attn_cache(cfg.num_layers, max_len)
        cache["cross_kv"] = {
            "k": jnp.zeros((cfg.num_layers, batch,
                            cfg.encdec.source_positions, nkv, hd), dtype),
            "v": jnp.zeros((cfg.num_layers, batch,
                            cfg.encdec.source_positions, nkv, hd), dtype)}
    return cache


# ---------------------------------------------------------------------------
# forward (per family)
# ---------------------------------------------------------------------------

def apply(params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array], *,
          mode: str = "train", cache: Optional[Params] = None
          ) -> Tuple[jax.Array, jax.Array, Optional[Params]]:
    """Returns (logits, aux_loss, new_cache).

    batch: tokens (b, s) [+ vision_embeds / audio_frames].
    mode: "train" (no cache) | "prefill" (fills cache) | "decode".
    """
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    cache_idx = cache["idx"] if cache is not None else None
    positions = (jnp.arange(s) if cache is None
                 else cache_idx + jnp.arange(s))
    aux = jnp.zeros((), jnp.float32)

    fam = cfg.family
    new_cache: Optional[Params] = dict(cache) if cache is not None else None

    if fam == "dense":
        x, lc = _run_dense_stack(
            params["blocks"], cfg, x, positions,
            None if cache is None else cache["layers"], cache_idx, mode)
        if new_cache is not None:
            new_cache["layers"] = lc
    elif fam == "vlm":
        x, new_cache = _run_vlm(params, cfg, batch, x, positions, cache,
                                cache_idx, mode, new_cache)
    elif fam == "moe":
        x, aux, new_cache = _run_moe(params, cfg, x, positions, cache,
                                     cache_idx, mode, new_cache)
    elif fam == "hybrid":
        x, new_cache = _run_hybrid(params, cfg, x, positions, cache,
                                   cache_idx, new_cache)
    elif fam == "ssm":
        x, new_cache = _run_xlstm(params, cfg, x, cache, mode, new_cache)
    elif fam == "audio":
        x, new_cache = _run_audio(params, cfg, batch, x, positions, cache,
                                  cache_idx, mode, new_cache)

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = _logits(params, x)
    if new_cache is not None:
        new_cache["idx"] = cache_idx + s
    return logits, aux, new_cache


def _logits(params: Params, x: jax.Array) -> jax.Array:
    if "lm_head" in params:
        return jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    # tied embeddings: scale logits by 1/sqrt(d) (Gemma-style) since the
    # embedding table is unit-scale
    d = x.shape[-1]
    return jnp.einsum("bsd,vd->bsv", x, params["embed"]) * (d ** -0.5)


def _run_dense_stack(stacked: Params, cfg: ModelConfig, x, positions,
                     caches, cache_idx, mode, *, causal=True, window=0):
    """lax.scan over a stacked homogeneous dense-block stack.

    The stacked cache (None without one) rides the loop carry and each
    layer writes its slice back, so XLA updates a donated cache in place
    instead of stacking a second full copy as the scan's output."""
    def body(carry, xs):
        h, cs = carry
        p, i = xs
        h, nc, _ = dense_block(
            p, cfg, h, positions=positions, causal=causal,
            cache=jax.tree.map(lambda a: a[i], cs), cache_idx=cache_idx,
            window=window)
        cs = jax.tree.map(
            lambda a, u: jax.lax.dynamic_update_index_in_dim(a, u, i, 0),
            cs, nc)
        return (h, cs), None

    n_layers = jax.tree.leaves(stacked)[0].shape[0]
    (x, caches), _ = jax.lax.scan(body, (x, caches),
                                  (stacked, jnp.arange(n_layers)))
    return x, caches


def _run_vlm(params, cfg, batch, x, positions, cache, cache_idx, mode,
             new_cache):
    every = cfg.vlm.cross_attn_every
    per_group = every - 1
    vision = batch.get("vision_embeds")
    b = x.shape[0]
    if vision is None and cache is None:
        vision = jnp.zeros((b, cfg.vlm.vision_tokens, cfg.vlm.vision_dim),
                           x.dtype)

    sc = None if cache is None else cache["self_layers"]
    cc = None if cache is None else cache["cross_layers"]
    xkv = None if (cache is None or mode == "prefill") else cache["cross_kv"]

    def body(carry, xs):
        h = carry
        g, c_cross, c_selfs, c_xkv = xs
        h, ncc, nxkv = dense_block(
            g["cross"], cfg, h, positions=positions, cache=c_cross,
            cache_idx=cache_idx, cross_kv=vision, cross_cache=c_xkv)

        def inner(carry2, xs2):
            p2, c2 = xs2
            h2, nc2, _ = dense_block(p2, cfg, carry2, positions=positions,
                                     cache=c2, cache_idx=cache_idx)
            return h2, nc2

        h, nsc = jax.lax.scan(inner, h, (g["selfs"], c_selfs))
        return h, (ncc, nsc, nxkv)

    n_groups = cfg.num_layers // every
    # reshape self caches (n_groups*per_group, ...) -> (n_groups, per_group,...)
    sc_g = (None if sc is None else
            jax.tree.map(lambda a: a.reshape((n_groups, per_group) +
                                             a.shape[1:]), sc))
    x, (ncc, nsc, nxkv) = jax.lax.scan(body, x, (params["groups"], cc, sc_g,
                                                 xkv))
    if new_cache is not None:
        new_cache["cross_layers"] = ncc
        new_cache["self_layers"] = jax.tree.map(
            lambda a: a.reshape((n_groups * per_group,) + a.shape[2:]), nsc)
        if mode == "prefill":
            new_cache["cross_kv"] = nxkv
    return x, new_cache


def _run_moe(params, cfg, x, positions, cache, cache_idx, mode, new_cache):
    nk = cfg.moe.first_k_dense
    aux_total = jnp.zeros((), jnp.float32)
    # a decode step counts what its MoE layers' routers did
    mask = None
    if mode == "decode" and cache is not None and "moe_stats" in cache:
        mask = jnp.broadcast_to(cache["rows"][:, None], x.shape[:2])
    n_route = len(MOE.ROUTE_STATS)
    # a decode step (one new position a row) reads only the held experts
    # its rows route to: each MoE layer gets the expert stacks whole and
    # its index (moe.moe_ffn), so the scan never slices (and so copies)
    # them
    moe_blocks, experts = params["moe_blocks"], None
    if mode == "decode" and x.shape[1] == 1:
        moe_p = dict(moe_blocks["moe"])
        experts = {k: moe_p.pop(k) for k in MOE.EXPERT_WEIGHTS}
        moe_p["layer"] = jnp.arange(cfg.num_layers - nk)
        moe_blocks = dict(moe_blocks, moe=moe_p)

    def mk_body(dense_ffn):
        def body(carry, xs):
            h, aux, st = carry
            p, c = xs
            if experts is not None and not dense_ffn:
                p = dict(p, moe=dict(p["moe"], **experts))
            h, a, nc, s = moe_block(p, cfg, h, positions=positions, cache=c,
                                    cache_idx=cache_idx,
                                    token_mask=None if dense_ffn else mask)
            return (h, aux + a, st if s is None else st + s), nc
        return body

    lc = None if cache is None else cache["layers"]
    lc_d = None if lc is None else jax.tree.map(lambda a: a[:nk], lc)
    lc_m = None if lc is None else jax.tree.map(lambda a: a[nk:], lc)

    st = jnp.zeros((n_route,), jnp.int32)
    (x, aux_total, st), ncd = jax.lax.scan(
        mk_body(True), (x, aux_total, st), (params["dense_blocks"], lc_d))
    (x, aux_total, st), ncm = jax.lax.scan(
        mk_body(False), (x, aux_total, st), (moe_blocks, lc_m))
    if new_cache is not None:
        new_cache["layers"] = jax.tree.map(
            lambda a, b2: jnp.concatenate([a, b2], axis=0), ncd, ncm)
        if mask is not None:
            n_moe = cfg.num_layers - nk
            new_cache["moe_stats"] = cache["moe_stats"] + jnp.concatenate(
                [st, jnp.array([n_moe], jnp.int32)])
    return x, aux_total, new_cache


def _run_hybrid(params, cfg, x, positions, cache, cache_idx, new_cache):
    every = cfg.ssm.attn_every
    n_attn = cfg.num_layers // every
    # ring caches are allocated at exactly the window size
    W = cache["attn"]["k"].shape[3] if (
        cache is not None and "pos" in cache["attn"]) else 0

    mc = None if cache is None else cache["mamba"]
    ac = None if cache is None else cache["attn"]

    def mamba_body(carry, xs):
        h = carry
        p, st = xs
        y, nst = SSM.mamba2_forward(
            p["mamba"], L.rmsnorm(p["ln"], h, cfg.norm_eps), cfg.ssm,
            init_state=st, return_state=st is not None)
        return h + y, nst

    # scan groups of `every` mamba layers, then the weight-shared attn block
    n_groups = cfg.num_layers // every
    rem = cfg.num_layers - n_groups * every

    def group_body(carry, xs):
        h = carry
        g_params, g_state, a_cache = xs
        h, n_states = jax.lax.scan(mamba_body, h, (g_params, g_state))
        h, na, _ = dense_block(params["shared"], cfg, h, positions=positions,
                               cache=a_cache, cache_idx=cache_idx, window=W)
        return h, (n_states, na)

    def split_groups(tree, n, size):
        return jax.tree.map(
            lambda a: a[: n * size].reshape((n, size) + a.shape[1:]), tree)

    gp = split_groups(params["blocks"], n_groups, every)
    gs = None if mc is None else split_groups(mc, n_groups, every)
    x, (nms, nac) = jax.lax.scan(group_body, x, (gp, gs, ac))

    nmc_tail = None
    if rem:
        tail_p = jax.tree.map(lambda a: a[n_groups * every:], params["blocks"])
        tail_s = None if mc is None else jax.tree.map(
            lambda a: a[n_groups * every:], mc)
        x, nmc_tail = jax.lax.scan(mamba_body, x, (tail_p, tail_s))

    if new_cache is not None:
        flat = jax.tree.map(
            lambda a: a.reshape((n_groups * every,) + a.shape[2:]), nms)
        if rem:
            flat = jax.tree.map(lambda a, t: jnp.concatenate([a, t], 0),
                                flat, nmc_tail)
        new_cache["mamba"] = flat
        new_cache["attn"] = nac
    return x, new_cache


def _run_xlstm(params, cfg, x, cache, mode, new_cache):
    mi, si = 0, 0
    nm_states, ns_states = [], []
    for i, p in enumerate(params["blocks_list"]):
        h = L.rmsnorm(p["ln"], x, cfg.norm_eps)
        if "slstm" in p:
            st = (None if cache is None else
                  jax.tree.map(lambda a: a[si], cache["slstm"]))
            y, nst = XL.slstm_forward(p["slstm"], h, init_state=st,
                                      return_state=st is not None)
            if nst is not None:
                ns_states.append(nst)
            si += 1
        else:
            st = (None if cache is None else
                  jax.tree.map(lambda a: a[mi], cache["mlstm"]))
            y, nst = XL.mlstm_forward(p["mlstm"], h, cfg.ssm, init_state=st,
                                      return_state=st is not None)
            if nst is not None:
                nm_states.append(nst)
            mi += 1
        x = x + y
    if new_cache is not None:
        new_cache["mlstm"] = jax.tree.map(lambda *xs: jnp.stack(xs),
                                          *nm_states)
        if ns_states:
            new_cache["slstm"] = jax.tree.map(lambda *xs: jnp.stack(xs),
                                              *ns_states)
    return x, new_cache


def _run_audio(params, cfg, batch, x, positions, cache, cache_idx, mode,
               new_cache):
    frames = batch.get("audio_frames")
    b = x.shape[0]
    if frames is None and cache is None:
        frames = jnp.zeros((b, cfg.encdec.source_positions, cfg.d_model),
                           x.dtype)

    # encoder (train, or prefill when frames are given)
    memory = None
    if frames is not None:
        mem = frames
        enc_pos = jnp.arange(frames.shape[1])

        def enc_body(carry, p):
            h, _, _ = dense_block(p, cfg, carry, positions=enc_pos,
                                  causal=False)
            return h, None

        mem, _ = jax.lax.scan(enc_body, mem, params["encoder"]["blocks"])
        memory = L.rmsnorm(params["encoder"]["final_norm"], mem, cfg.norm_eps)

    lc = None if cache is None else cache["layers"]
    xkv = None
    if cache is not None and mode == "decode":
        xkv = cache["cross_kv"]

    def body(carry, xs):
        h = carry
        p, c, xc = xs
        h, nc, nxkv = dense_block(p, cfg, h, positions=positions, cache=c,
                                  cache_idx=cache_idx, cross_kv=memory,
                                  cross_cache=xc)
        return h, (nc, nxkv)

    x, (nlc, nxkv) = jax.lax.scan(body, x, (params["blocks"], lc, xkv))
    if new_cache is not None:
        new_cache["layers"] = nlc
        if mode == "prefill":
            new_cache["cross_kv"] = nxkv
    return x, new_cache

