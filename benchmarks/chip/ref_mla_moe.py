"""Plain reference of a DeepSeek-V2 generator (multi-head latent attention
and a mixture of experts) in float32 at ``highest`` matmul precision,
written from the configuration's widths, for the chip's share of an
expert-parallel deployment.

It imports nothing of the program and takes nothing the program made.
The weights are drawn again here from the run's seed, layer by layer, by
the recipe the configuration file states (``init``): role ``i`` of the
family is seeded ``fold_in(PRNGKey(seed), i)``; its key splits into 8:
key 0 draws the embedding (unscaled), key 1 the output head, key 2 splits
into one key per leading dense layer and key 3 into one per MoE layer.  A
layer's key splits into 2, attention and feed-forward.  Attention's key
splits into 7: 0 the q projection (d, heads, qk head), 2 the latent down
projection (d, kv_lora_rank + rope head), 3 and 4 the key and value up
projections, 5 the output projection.  A dense feed-forward's key splits
into 3 (up, down, gate).  A MoE feed-forward's key splits into 3: the
router (float32, not rounded), the experts, and the shared experts (split
again into gate, up, down, of width d_ff x shared); expert ``e`` is drawn
from ``fold_in(<experts key>, e)`` split into gate, up, down.  Each
weight is a truncated normal on [-2, 2] times ``fan_in ** -0.5`` (the
output projection ``(heads x v head) ** -0.5``), rounded to the stated
dtype.  Norm scales are ones.

The block: pre-norm RMSNorm; MLA in its plain form (q projected directly,
no q-LoRA; the latent RMS-normed, keys and values expanded from it, a
rope part shared by the heads), rotary embeddings on halves of the rope
part with YaRN's frequencies, softmax scale ``qk_head ** -0.5 x mscale²``,
causal softmax; then a SiLU-gated MLP (leading dense layers) or the MoE
layer: softmax over every routed expert's logit, greedy top-k, gates
renormalised only where ``norm_topk_prob``, times ``routed_scaling_factor``;
the experts held here (``num_experts_held`` from ``first_expert``) each
weighted by the token's gate, plus the shared experts.  Final RMSNorm, an
untied output head.

``quant="fp8"`` is the control, the same model computed in fp8 (e4m3):
every weight matrix rounded per output channel and every matmul input
(activation) per token, each scaled so its largest magnitude is e4m3's
448, then the same float32 arithmetic.  Attention's score and value
products, the softmaxes and the norms stay float32.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

PAD_TO = 32          # sequence lengths are padded to a multiple of this
ROWS = 32            # sequences per device batch


def _tn(key, shape, scale, dtype):
    w = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    return (w * scale).astype(dtype).astype(jnp.float32)


def fp8(w, axes):
    """Round to float8 e4m3 with one scale per slice over ``axes``."""
    s = jnp.max(jnp.abs(w), axis=axes, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


# -- YaRN ----------------------------------------------------------------------

def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(mla: dict, theta: float) -> np.ndarray:
    """Rope frequencies of the rope part: theta's where ``yarn`` is absent
    or has factor 1, else NTK-by-parts (DeepSeek-V2's
    ``yarn_find_correction_range`` and linear ramp)."""
    dim = mla["qk_rope_head_dim"]
    base = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    y = mla.get("yarn") or {}
    factor = y.get("factor", 1.0)
    if factor <= 1:
        return base.astype(np.float32)
    orig = y["original_max_position_embeddings"]

    def dim_of(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(y["beta_fast"])), 0)
    high = min(math.ceil(dim_of(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return (base / factor * (1.0 - keep) + base * keep).astype(np.float32)


def yarn_scales(mla: dict):
    """(softmax scale, factor on the rotated rope part)."""
    y = mla.get("yarn") or {}
    factor = y.get("factor", 1.0)
    qk = mla["qk_nope_head_dim"] + mla["qk_rope_head_dim"]
    scale = qk ** -0.5
    all_dim = y.get("mscale_all_dim", 0.0)
    if factor > 1 and all_dim:
        scale *= _yarn_mscale(factor, all_dim) ** 2
    gain = (_yarn_mscale(factor, y.get("mscale", 1.0))
            / _yarn_mscale(factor, all_dim))
    return scale, gain


def _rope(x, pos, inv, gain):
    """x (..., s, [heads,] e): rotation on the two halves of ``e``."""
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if x.ndim == 4:
        cos, sin = cos[:, None, :], sin[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1) * gain


# -- weights -------------------------------------------------------------------

def draw_mla(key, m: dict, dtype) -> Dict[str, jax.Array]:
    d, H, a = m["d_model"], m["num_heads"], m["mla"]
    r, er, en, ev = (a["kv_lora_rank"], a["qk_rope_head_dim"],
                     a["qk_nope_head_dim"], a["v_head_dim"])
    if a.get("q_lora_rank"):
        raise ValueError("the reference knows the direct q projection only")
    k = jax.random.split(key, 7)
    return {"wq": _tn(k[0], (d, H, en + er), d ** -0.5, dtype),
            "wkv_a": _tn(k[2], (d, r + er), d ** -0.5, dtype),
            "wk_b": _tn(k[3], (r, H, en), r ** -0.5, dtype),
            "wv_b": _tn(k[4], (r, H, ev), r ** -0.5, dtype),
            "wo": _tn(k[5], (H, ev, d), (H * ev) ** -0.5, dtype)}


def draw_mlp(key, d: int, f: int, dtype) -> Dict[str, jax.Array]:
    k = jax.random.split(key, 3)
    return {"up": _tn(k[0], (d, f), d ** -0.5, dtype),
            "down": _tn(k[1], (f, d), f ** -0.5, dtype),
            "gate": _tn(k[2], (d, f), d ** -0.5, dtype)}


def draw_moe(key, m: dict, experts: Sequence[int], dtype):
    """The router, the given routed experts (stacked) and the shared
    experts of one MoE feed-forward."""
    d, e = m["d_model"], m["moe"]
    k_router, k_experts, k_shared = jax.random.split(key, 3)
    p = {"router": _tn(k_router, (d, e["num_experts"]), d ** -0.5,
                       jnp.float32),
         "experts": [draw_mlp_folded(k_experts, int(i), d, e["d_ff"], dtype)
                     for i in experts]}
    if e["num_shared_experts"]:
        sff = e["d_ff"] * e["num_shared_experts"]
        k = jax.random.split(k_shared, 3)
        p["shared"] = {"gate": _tn(k[0], (d, sff), d ** -0.5, dtype),
                       "up": _tn(k[1], (d, sff), d ** -0.5, dtype),
                       "down": _tn(k[2], (sff, d), sff ** -0.5, dtype)}
    return p


def draw_mlp_folded(key, i: int, d: int, f: int, dtype):
    """Routed expert ``i``: gate, up, down from ``fold_in(key, i)``."""
    k = jax.random.split(jax.random.fold_in(key, i), 3)
    return {"gate": _tn(k[0], (d, f), d ** -0.5, dtype),
            "up": _tn(k[1], (d, f), d ** -0.5, dtype),
            "down": _tn(k[2], (f, d), f ** -0.5, dtype)}


def held_experts(m: dict) -> range:
    e = m["moe"]
    first = e.get("first_expert", 0)
    return range(first, first + (e.get("num_experts_held")
                                 or e["num_experts"]))


# -- the block -------------------------------------------------------------------

def _exact(x, axes=(-1,)):
    return x


def _mlp(p, h, act):
    g = jax.nn.silu(act(h) @ p["gate"]) * (act(h) @ p["up"])
    return act(g) @ p["down"]


def moe(p, h, m: dict, experts: Sequence[int], act=None):
    """h (..., d) -> the MoE layer's output: the ``experts`` held (whose
    weights ``p["experts"]`` holds in that order), each weighted by the
    token's gate, plus the shared experts."""
    act = act or _exact
    e = m["moe"]
    probs = jax.nn.softmax(act(h) @ p["router"], axis=-1)
    top, idx = jax.lax.top_k(probs, e["top_k"])
    if e.get("norm_topk_prob", True):
        top = top / top.sum(-1, keepdims=True)
    top = top * e.get("routed_scaling_factor", 1.0)
    y = jnp.zeros_like(h)
    for i, w in zip(experts, p["experts"]):
        gate = jnp.sum(jnp.where(idx == i, top, 0.0), axis=-1)
        y = y + gate[..., None] * _mlp(w, h, act)
    if "shared" in p:
        y = y + _mlp(p["shared"], h, act)
    return y


def mla(p, h, m: dict, inv, scale, gain, act=None):
    """Causal MLA over h (b, s, d), plain (naive) form."""
    act = act or _exact
    a = m["mla"]
    r, en = a["kv_lora_rank"], a["qk_nope_head_dim"]
    pos = jnp.arange(h.shape[1])
    q = jnp.einsum("bsd,dhe->bshe", act(h), p["wq"])
    q_nope, q_pe = q[..., :en], _rope(q[..., en:], pos, inv, gain)
    kv = act(h) @ p["wkv_a"]
    ckv = _rmsnorm(kv[..., :r], m["norm_eps"])
    k_pe = _rope(kv[..., r:], pos, inv, gain)
    k_nope = jnp.einsum("bsr,rhe->bshe", act(ckv), p["wk_b"])
    v = jnp.einsum("bsr,rhe->bshe", act(ckv), p["wv_b"])
    sc = (jnp.einsum("bqhe,bkhe->bhqk", q_nope, k_nope)
          + jnp.einsum("bqhe,bke->bhqk", q_pe, k_pe)) * scale
    s = h.shape[1]
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhe->bqhe", pr, v)
    return jnp.einsum("bqhe,hed->bqd", act(o, (-2, -1)), p["wo"])


class Reference:
    """The generator of the configuration, drawn from ``seed``."""

    def __init__(self, m: dict, seed: int, quant: Optional[str] = None):
        if m.get("init") != "truncated_normal_fan_in":
            raise ValueError(f"unknown init recipe {m.get('init')!r}")
        if quant not in (None, "fp8"):
            raise ValueError(f"quant {quant!r}; pick None or 'fp8'")
        if m["tie_embeddings"]:
            raise ValueError("the reference knows an untied head only")
        self.m = m
        dtype = jnp.dtype(m["dtype"])
        d, V, eps = m["d_model"], m["vocab_size"], m["norm_eps"]
        nk = m["moe"]["first_k_dense"]
        low = quant == "fp8"
        key = jax.random.fold_in(jax.random.PRNGKey(seed), m["role_index"])
        keys = jax.random.split(key, 8)
        self._keys = keys
        self._layer_keys = (
            [("dense", k) for k in jax.random.split(keys[2], nk)]
            + [("moe", k) for k in
               jax.random.split(keys[3], m["num_layers"] - nk)])
        self._experts = list(held_experts(m))
        inv = jnp.asarray(yarn_inv_freq(m["mla"], m["rope_theta"]))
        scale, gain = yarn_scales(m["mla"])

        def q(p):
            """fp8 weights per output channel (over the input axis)."""
            if not low:
                return p
            return jax.tree_util.tree_map_with_path(
                lambda path, w: fp8(w, (0, 1) if path[-1].key == "wo"
                                    else (0,)), p)

        def act(x, axes=(-1,)):
            return fp8(x, axes) if low else x

        @jax.jit
        def embed_table(k):
            return jax.random.truncated_normal(
                k, -2.0, 2.0, (V, d), jnp.float32).astype(dtype).astype(
                    jnp.float32)

        @jax.jit
        def head(k):
            w = _tn(k, (d, V), d ** -0.5, dtype)
            return fp8(w, (0,)) if low else w

        @jax.jit
        def dense_params(k):
            ka, kf = jax.random.split(k, 2)
            return q({"mla": draw_mla(ka, m, dtype),
                      "mlp": draw_mlp(kf, d, m["moe"]["dense_d_ff"], dtype)})

        @jax.jit
        def moe_params(k):
            ka, kf = jax.random.split(k, 2)
            return q({"mla": draw_mla(ka, m, dtype),
                      "moe": draw_moe(kf, m, self._experts, dtype)})

        @jax.jit
        def layer(p, x):
            x = x + mla(p["mla"], _rmsnorm(x, eps), m, inv, scale, gain,
                        act)
            h = _rmsnorm(x, eps)
            if "mlp" in p:
                return x + _mlp(p["mlp"], h, act)
            return x + moe(p["moe"], h, m, self._experts, act)

        self._embed_table = embed_table
        self._head = head
        self._params = {"dense": dense_params, "moe": moe_params}
        self._layer = layer
        self._logits = jax.jit(lambda h, W: act(_rmsnorm(h, eps)) @ W)

    def logits(self, seqs: Sequence[Sequence[int]]) -> List[np.ndarray]:
        """(len, vocab) float32 logits per sequence."""
        with jax.default_matmul_precision("highest"):
            E = self._embed_table(self._keys[0])
            batches = []
            for i in range(0, len(seqs), ROWS):
                part = seqs[i:i + ROWS]
                s = -(-max(len(t) for t in part) // PAD_TO) * PAD_TO
                tok = np.zeros((len(part), s), np.int32)
                for j, t in enumerate(part):
                    tok[j, :len(t)] = t
                batches.append(jnp.take(E, jnp.asarray(tok), axis=0))
            del E
            for kind, k in self._layer_keys:
                p = self._params[kind](k)
                batches = [self._layer(p, x) for x in batches]
            W = self._head(self._keys[1])
            out = [np.asarray(self._logits(x, W)) for x in batches]
        return [out[i // ROWS][i % ROWS, :len(t)] for i, t in enumerate(seqs)]


# -- counts from the widths ------------------------------------------------------

def _parts(m: dict) -> dict:
    """Parameters by part, from the entry's widths: one layer's attention
    with its two norms, the leading dense feed-forward, one routed expert,
    the shared experts and the router of a MoE layer, the output head with
    the final norm, the embedding."""
    d, H, a, e = m["d_model"], m["num_heads"], m["mla"], m["moe"]
    r, er, en, ev = (a["kv_lora_rank"], a["qk_rope_head_dim"],
                     a["qk_nope_head_dim"], a["v_head_dim"])
    return {"attn": (d * H * (en + er) + d * (r + er) + r + r * H * (en + ev)
                     + H * ev * d + 2 * d),
            "dense_ffn": 3 * d * e["dense_d_ff"],
            "expert": 3 * d * e["d_ff"],
            "shared": 3 * d * e["d_ff"] * e["num_shared_experts"],
            "router": d * e["num_experts"],
            "head": m["vocab_size"] * d + d,
            "embed": m["vocab_size"] * d}


def param_count(m: dict) -> int:
    """Parameters this chip holds: every layer's attention, the dense
    layers, the held routed experts, the shared experts and router of
    each MoE layer, the embedding and the head."""
    p, e = _parts(m), m["moe"]
    n_moe = m["num_layers"] - e["first_k_dense"]
    held = len(held_experts(m))
    return (m["num_layers"] * p["attn"] + e["first_k_dense"] * p["dense_ffn"]
            + n_moe * (held * p["expert"] + p["shared"] + p["router"])
            + p["head"] + p["embed"])


def decode_flops(m: dict, tokens: int) -> float:
    """Model FLOPs of ``tokens`` generated tokens on this chip: 2 per
    active parameter per token, the active ones being attention, the dense
    layers, the shared experts, the router, the head, and the routed
    experts a token reaches here on average (top_k x held / experts per
    MoE layer); the embedding gather counts for nothing."""
    p, e = _parts(m), m["moe"]
    n_moe = m["num_layers"] - e["first_k_dense"]
    routed = e["top_k"] * len(held_experts(m)) / e["num_experts"]
    active = (m["num_layers"] * p["attn"]
              + e["first_k_dense"] * p["dense_ffn"]
              + n_moe * (routed * p["expert"] + p["shared"] + p["router"])
              + p["head"])
    return 2.0 * active * tokens


def cache_slot_bytes(m: dict) -> int:
    """Latent cache bytes of one row at one position, over the layers."""
    a = m["mla"]
    return (m["num_layers"] * (a["kv_lora_rank"] + a["qk_rope_head_dim"])
            * jnp.dtype(m["dtype"]).itemsize)


def decode_step_bytes(m: dict, experts_hit: float, positions: float) -> float:
    """Least bytes one decode step reads: every weight but the embedding
    and the routed experts (the routers in float32, the rest in the
    stated dtype), ``experts_hit`` held experts in each MoE layer, and
    ``positions`` latent cache positions (rows x positions in use)."""
    p, e = _parts(m), m["moe"]
    n_moe = m["num_layers"] - e["first_k_dense"]
    size = jnp.dtype(m["dtype"]).itemsize
    fixed = (m["num_layers"] * p["attn"]
             + e["first_k_dense"] * p["dense_ffn"]
             + n_moe * p["shared"] + p["head"])
    return (size * (fixed + experts_hit * n_moe * p["expert"])
            + 4 * n_moe * p["router"]
            + positions * cache_slot_bytes(m))
