"""Plain reference of the stage models: a dense decoder in float32 at
``highest`` matmul precision, written from the configuration's widths.

It imports nothing of the program and takes nothing the program made.
The weights are drawn again here from the run's seed, layer by layer, by
the recipe the configuration file states (``init``): role ``i`` of the
family is seeded ``fold_in(PRNGKey(seed), i)``; its key splits into 8,
key 0 draws the embedding and key 2 splits into one key per layer; a
layer's key splits into 4, the first for attention (split again into
q, k, v, o) and the second for the MLP (up, down, gate).  Each weight is a
truncated normal on [-2, 2] times ``fan_in ** -0.5`` (the embedding
unscaled), rounded to the stated dtype.  Norm scales are ones.

The block: pre-norm RMSNorm, grouped-query attention with rotary
embeddings on halves of each head, causal softmax; a SiLU-gated MLP;
final RMSNorm; logits against the tied embedding times ``d ** -0.5``.

``quant="fp8"`` is the control, the same model computed in fp8 (e4m3):
every weight matrix rounded per output channel and every matmul input
(activation) per token, each scaled so its largest magnitude is e4m3's
448, then the same float32 arithmetic.  Attention's score and value
products and the norms stay float32.
"""
from __future__ import annotations

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


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


class Reference:
    """One stage model of the configuration, drawn from ``seed``."""

    def __init__(self, m: dict, seed: int, quant: Optional[str] = None):
        if m.get("init") != "truncated_normal_fan_in":
            raise ValueError(f"unknown init recipe {m.get('init')!r}")
        if quant not in (None, "fp8"):
            raise ValueError(f"quant {quant!r}; pick None or 'fp8'")
        self.m = m
        dtype = jnp.dtype(m["dtype"])
        d, H, n, e, f = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                         m["head_dim"], m["d_ff"])
        V, eps, theta = m["vocab_size"], m["norm_eps"], m["rope_theta"]
        if not m["tie_embeddings"]:
            raise ValueError("the reference knows tied embeddings only")
        low = quant == "fp8"
        key = jax.random.fold_in(jax.random.PRNGKey(seed), m["role_index"])
        keys = jax.random.split(key, 8)
        self._embed_key = keys[0]
        self._layer_keys = jax.random.split(keys[2], m["num_layers"])

        @jax.jit
        def embed_table(k):
            w = _tn(k, (V, d), 1.0, dtype)
            return fp8(w, (1,)) if low else w

        @jax.jit
        def layer_params(k):
            ks = jax.random.split(k, 4)
            ka, km = jax.random.split(ks[0], 4), jax.random.split(ks[1], 3)
            p = {"wq": _tn(ka[0], (d, H, e), d ** -0.5, dtype),
                 "wk": _tn(ka[1], (d, n, e), d ** -0.5, dtype),
                 "wv": _tn(ka[2], (d, n, e), d ** -0.5, dtype),
                 "wo": _tn(ka[3], (H, e, d), (H * e) ** -0.5, dtype),
                 "w_up": _tn(km[0], (d, f), d ** -0.5, dtype),
                 "w_down": _tn(km[1], (f, d), f ** -0.5, dtype),
                 "w_gate": _tn(km[2], (d, f), d ** -0.5, dtype)}
            if low:
                p = {k: fp8(w, (0, 1) if k == "wo" else (0,))
                     for k, w in p.items()}
            return p

        def act(x, axes=(-1,)):
            return fp8(x, axes) if low else x

        @jax.jit
        def layer(p, x):
            b, s, _ = x.shape
            pos = jnp.arange(s)
            h = act(_rmsnorm(x, eps))
            q = _rope(jnp.einsum("bsd,dhe->bshe", h, p["wq"]), pos, theta)
            k = _rope(jnp.einsum("bsd,dne->bsne", h, p["wk"]), pos, theta)
            v = jnp.einsum("bsd,dne->bsne", h, p["wv"])
            q = q.reshape(b, s, n, H // n, e)
            sc = jnp.einsum("bqnge,bkne->bngqk", q, k) / jnp.sqrt(
                jnp.float32(e))
            causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
            pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
            o = jnp.einsum("bngqk,bkne->bqnge", pr, v).reshape(b, s, H, e)
            x = x + jnp.einsum("bshe,hed->bsd", act(o, (-2, -1)), p["wo"])
            h = act(_rmsnorm(x, eps))
            g = jnp.einsum("bsd,df->bsf", h, p["w_gate"])
            u = jnp.einsum("bsd,df->bsf", h, p["w_up"])
            return x + jnp.einsum("bsf,fd->bsd", act(jax.nn.silu(g) * u),
                                  p["w_down"])

        self._embed_table = embed_table
        self._layer_params = layer_params
        self._layer = layer
        self._final = jax.jit(lambda x: _rmsnorm(x, eps))
        self._logits = jax.jit(lambda h, E: jnp.einsum(
            "bsd,vd->bsv", act(h), E) * (d ** -0.5))

    def hidden(self, seqs: Sequence[Sequence[int]]):
        """Final-norm hidden states: one (len, d) device array per
        sequence, and the float32 embedding table."""
        with jax.default_matmul_precision("highest"):
            E = self._embed_table(self._embed_key)
            batches = []
            for i in range(0, len(seqs), ROWS):
                part = seqs[i:i + ROWS]
                s = -(-max(len(t) for t in part) // PAD_TO) * PAD_TO
                tok = np.zeros((len(part), s), np.int32)
                for j, t in enumerate(part):
                    tok[j, :len(t)] = t
                batches.append(jnp.take(E, jnp.asarray(tok), axis=0))
            for k in self._layer_keys:
                p = self._layer_params(k)
                batches = [self._layer(p, x) for x in batches]
            hs = [self._final(x) for x in batches]
        out = [hs[i // ROWS][i % ROWS, :len(t)] for i, t in enumerate(seqs)]
        return out, E

    def logits(self, seqs: Sequence[Sequence[int]]) -> List[np.ndarray]:
        """(len, vocab) float32 logits per sequence."""
        hs, E = self.hidden(seqs)
        with jax.default_matmul_precision("highest"):
            return [np.asarray(self._logits(h[None], E)[0]) for h in hs]

    def embed(self, seqs: Sequence[Sequence[int]]) -> np.ndarray:
        """Mean-pooled, L2-normalized final hidden states (the embedder)."""
        hs, _ = self.hidden(seqs)
        out = []
        for h in hs:
            v = np.asarray(h, np.float64).mean(axis=0)
            out.append(v / max(np.linalg.norm(v), 1e-6))
        return np.stack(out)

    def rerank(self, seqs: Sequence[Sequence[int]], head_token: int):
        """(scores, scales): the first position's final hidden state dotted
        with the embedding row ``head_token`` (the cross-encoder head), and
        |h| * |w| per pair, the scale its error is measured against."""
        hs, E = self.hidden(seqs)
        w = np.asarray(E[head_token], np.float64)
        h0 = np.stack([np.asarray(h[0], np.float64) for h in hs])
        return h0 @ w, np.linalg.norm(h0, axis=1) * np.linalg.norm(w)


def build(models: Dict[str, dict], seed: int, quant: Optional[str] = None
          ) -> Dict[str, Reference]:
    return {role: Reference(m, seed, quant) for role, m in models.items()}
