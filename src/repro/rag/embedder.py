"""Embedding + reranking stages backed by the model zoo.

Embedder: mean-pooled final hidden states, L2-normalized (bge / qwen3-
embedding style).  Reranker: cross-encoder — scores [query SEP chunk]
pairs via a scalar head on the first position's hidden state.
Both batch over items, which is exactly the batchable workload HeRo's
partitioner (Eq. 3) optimizes.  Items run in micro-batches of
``CALL_WIDTH`` (the last one zero-padded), so a dispatch of any size
reuses one compiled program.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.events import SP_EMBED_CALL, SP_RERANK_CALL
from repro.models import layers as L
from repro.models import lm
from repro.rag.tokenizer import SEP
from repro.serving import spans

# batch width of every model call in the live pipeline: embed and rerank
# micro-batches, LMAgent prefill/decode (agents.py) and the scheduler's
# decode_batch_cap on the serving path (launch/serve.py)
CALL_WIDTH = 8


def _micro_batches(token_lists: Sequence[Sequence[int]], pad_to: int,
                   vocab: int):
    """Yield (n_items, tokens (CALL_WIDTH, pad_to) int32) host arrays."""
    for s in range(0, len(token_lists), CALL_WIDTH):
        part = token_lists[s:s + CALL_WIDTH]
        out = np.zeros((CALL_WIDTH, pad_to), np.int32)
        for i, ids in enumerate(part):
            ids = list(ids)[:pad_to]
            out[i, : len(ids)] = np.clip(ids, 0, vocab - 1)
        yield len(part), out


def hidden_states(params, cfg: ModelConfig, tokens) -> jax.Array:
    """Final-layer hidden states (pre-logits).  Dense-family models only
    (the paper's embed/rerank models are all dense)."""
    if cfg.family != "dense":
        raise NotImplementedError(cfg.family)
    x = jnp.take(params["embed"], tokens, axis=0)
    positions = jnp.arange(tokens.shape[1])
    x, _ = lm._run_dense_stack(params["blocks"], cfg, x, positions,
                               None, None, "eval")
    return L.rmsnorm(params["final_norm"], x, cfg.norm_eps)


class Embedder:
    def __init__(self, cfg: ModelConfig, params, max_tokens: int = 128):
        self.cfg = cfg
        self.params = params
        self.max_tokens = max_tokens

        @jax.jit
        def _embed(params, tokens, mask):
            h = hidden_states(params, cfg, tokens).astype(jnp.float32)
            s = jnp.sum(h * mask[..., None], axis=1)
            emb = s / jnp.maximum(mask.sum(-1, keepdims=True), 1.0)
            return emb / jnp.maximum(
                jnp.linalg.norm(emb, axis=-1, keepdims=True), 1e-6)

        self._fn = _embed

    def embed(self, token_lists: Sequence[Sequence[int]]) -> np.ndarray:
        """-> (len(token_lists), d_model) L2-normalized embeddings."""
        with spans.span(SP_EMBED_CALL, items=len(token_lists)):
            outs = [np.asarray(self._fn(self.params, tokens,
                                        (tokens != 0).astype(np.float32)))[:n]
                    for n, tokens in _micro_batches(
                        token_lists, self.max_tokens, self.cfg.vocab_size)]
            return np.concatenate(outs)


class Reranker:
    def __init__(self, cfg: ModelConfig, params, max_tokens: int = 192):
        self.cfg = cfg
        self.params = params
        self.max_tokens = max_tokens

        @jax.jit
        def _score(params, tokens):
            h = hidden_states(params, cfg, tokens)
            w = params["embed"][SEP]          # reuse a row as the head
            return jnp.einsum("bd,d->b", h[:, 0], w,
                              preferred_element_type=jnp.float32)

        self._fn = _score

    def score(self, query_ids: Sequence[int],
              chunk_ids_list: Sequence[Sequence[int]]) -> np.ndarray:
        pairs = [list(query_ids) + [SEP] + list(c) for c in chunk_ids_list]
        with spans.span(SP_RERANK_CALL, items=len(pairs)):
            return np.concatenate([
                np.asarray(self._fn(self.params, tokens))[:n]
                for n, tokens in _micro_batches(pairs, self.max_tokens,
                                                self.cfg.vocab_size)])
