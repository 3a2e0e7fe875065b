"""Executable agents for the real (JAX) pipeline: query rewriter, search
planner, context refiner, chat — thin generation loops over the model zoo.

The serving entry point runs them at published widths; tests and examples
pass reduced configs.  Semantics match the simulator's workflow builders
so the two paths stay in lockstep.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.events import (CT_LM_FETCHES, CT_LM_KV_BYTES_RESERVED,
                               CT_LM_KV_BYTES_USED, CT_LM_PAD_ROWS,
                               CT_LM_REAL_TOKENS, CT_LM_STEPS, CT_MOE_DROPPED,
                               CT_MOE_EXPERTS_HIT, CT_MOE_EXPERTS_READ,
                               CT_MOE_LAYER_STEPS, CT_MOE_ROUTED,
                               CT_MOE_ROUTED_HELD, SP_LM_CALL, SP_LM_FETCH,
                               SP_LM_STEP)
from repro.models import Model, build_model
from repro.models.lm import MOE_STATS
from repro.rag.embedder import CALL_WIDTH
from repro.rag.tokenizer import EOS
from repro.serving import spans


@dataclass
class GenResult:
    token_ids: List[int]
    steps: int


class LMAgent:
    """Greedy decoding agent: jitted prefill, then jitted stepwise decode
    through the KV cache, every step enqueued before the host reads a
    token, so a call syncs with the device once, at its end.

    Every call runs at one batch width (``CALL_WIDTH`` streams): fewer
    streams are padded with copies of the first prompt, more are split
    into groups that run one after another.  So each agent compiles
    exactly one prefill and one decode program per prompt length, and a
    warm agent compiles nothing.  The serving path caps decode rounds at
    ``CALL_WIDTH`` members (``decode_batch_cap``), so one fused dispatch
    is one call there.

    The steps are separate programs, not one on-device loop: a program
    that runs the layer stack more than once (a prefill and a decode
    loop) makes the TPU compiler copy the q/k/v weight stacks into
    another layout on every call, 1.13 GB for the 4B chat model.

    A MoE model's decode steps also count, on the device, what their
    routers did for the call's real rows (``lm.MOE_STATS``); the counts
    come back in the call's one fetch.  ``role`` names the pipeline role
    the agent serves in its ``lm.call`` spans."""

    def __init__(self, cfg: ModelConfig, params, max_len: int = 512,
                 role: str = ""):
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.role = role or cfg.name
        self.model: Model = build_model(cfg)
        model = self.model
        # bytes of one row's cache at one position, summed over the
        # layers, as the model builds it (keys and values, or MLA's latent)
        layers = jax.eval_shape(lambda: model.init_cache(1, 1))["layers"]
        self._kv_slot_bytes = sum(a.size * a.dtype.itemsize
                                  for a in jax.tree.leaves(layers))
        self._moe_stats = cfg.moe.enabled

        def prefill(params, tokens, n_real=None):
            cache = model.init_cache(tokens.shape[0], max_len)
            if n_real is not None:
                cache["rows"] = jnp.arange(tokens.shape[0]) < n_real
            logits, cache = model.prefill(params, {"tokens": tokens}, cache)
            return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32), cache

        def decode(params, tok, cache):
            logits, cache = model.decode_step(params, tok[:, None], cache)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(decode, donate_argnums=2)

    def _run(self, prompts: Sequence[Sequence[int]], max_new: int,
             stop_at_eos: bool) -> List[List[int]]:
        """One width-``CALL_WIDTH`` prefill + ``max_new - 1`` decode steps
        over at most ``CALL_WIDTH`` prompts, then one fetch; when
        ``stop_at_eos``, every stream ends where the first one emits EOS
        (the steps after it run on the device and are dropped)."""
        n = len(prompts)
        rows = list(prompts) + [prompts[0]] * (CALL_WIDTH - n)
        tokens = jnp.asarray(rows, jnp.int32)
        with spans.span(SP_LM_CALL, role=self.role, width=CALL_WIDTH,
                        rows=n, prompt=len(rows[0]), steps=max_new - 1):
            with spans.span(SP_LM_STEP):
                tok, cache = (self._prefill(self.params, tokens, n)
                              if self._moe_stats
                              else self._prefill(self.params, tokens))
                toks = [tok]
                for _ in range(max_new - 1):
                    tok, cache = self._decode(self.params, tok, cache)
                    toks.append(tok)
                moe = cache["moe_stats"] if self._moe_stats else None
                with spans.span(SP_LM_FETCH):
                    got, moe = jax.device_get((toks, moe))
                    host = np.stack(got, axis=1)[:n]
        if stop_at_eos:
            eos = np.flatnonzero(host[0] == EOS)
            if eos.size:
                host = host[:, :eos[0] + 1]
        steps = host.shape[1] - 1
        if spans.enabled():
            spans.count(CT_LM_FETCHES)
            spans.count(CT_LM_STEPS, steps)
            spans.count(CT_LM_REAL_TOKENS, n * (steps + 1))
            spans.count(CT_LM_PAD_ROWS, CALL_WIDTH - n)
            spans.count(CT_LM_KV_BYTES_RESERVED,
                        self._kv_slot_bytes * CALL_WIDTH * self.max_len)
            # the prefill writes the prompt's positions, each step one more
            spans.count(CT_LM_KV_BYTES_USED,
                        self._kv_slot_bytes * n * (len(rows[0]) + steps))
            if moe is not None:
                got = dict(zip(MOE_STATS, moe.tolist()))
                spans.count(CT_MOE_ROUTED, got["routed"])
                spans.count(CT_MOE_ROUTED_HELD, got["routed_held"])
                spans.count(CT_MOE_EXPERTS_HIT, got["experts_hit"])
                spans.count(CT_MOE_EXPERTS_READ, got["experts_read"])
                spans.count(CT_MOE_LAYER_STEPS, got["layer_steps"])
                spans.count(CT_MOE_DROPPED,
                            got["routed_held"] - got["computed"])
        return host.tolist()

    def generate(self, prompt_ids: Sequence[int], max_new: int = 32,
                 stop_at_eos: bool = True) -> GenResult:
        out = self._run([list(prompt_ids)], max_new, stop_at_eos)[0]
        return GenResult(out, len(out))

    def generate_batch(self, prompts: Sequence[Sequence[int]],
                       max_new: int = 32) -> List[GenResult]:
        """Batched prefill + stepwise decode over ``len(prompts)``
        concurrent streams — the continuous-batching serving path: a fused
        decode dispatch runs a single width-B JAX call per token step
        instead of B sequential single-stream loops.  The model applies no
        padding mask, so ragged prompts are LEFT-CROPPED to the shortest
        length (keeping each stream's most recent context) rather than
        padded — pad tokens would leak into attention at real positions."""
        assert prompts and all(len(p) > 0 for p in prompts), \
            "empty prompt in batch"
        length = min(len(p) for p in prompts)
        cropped = [list(p)[-length:] for p in prompts]
        outs: List[List[int]] = []
        for i in range(0, len(cropped), CALL_WIDTH):
            outs += self._run(cropped[i:i + CALL_WIDTH], max_new, False)
        return [GenResult(seq, len(seq)) for seq in outs]


class QueryRewriter(LMAgent):
    """Emits n sub-queries; token groups release downstream retrieval early
    (the real-pipeline analogue of the workflow expander)."""

    def rewrite(self, query_ids: Sequence[int], n_subqueries: int,
                tokens_each: int = 12) -> List[List[int]]:
        g = self.generate(query_ids, max_new=n_subqueries * tokens_each,
                          stop_at_eos=False)
        toks = g.token_ids
        return [toks[i * tokens_each:(i + 1) * tokens_each]
                for i in range(n_subqueries)]


class SearchPlanner(LMAgent):
    def plan(self, query_ids: Sequence[int], n_requests: int
             ) -> List[List[int]]:
        g = self.generate(query_ids, max_new=n_requests * 8,
                          stop_at_eos=False)
        return [g.token_ids[i * 8:(i + 1) * 8] for i in range(n_requests)]


class ContextRefiner(LMAgent):
    def refine(self, context_ids: Sequence[int], budget: int
               ) -> List[int]:
        g = self.generate(list(context_ids)[:self.max_len - budget - 1],
                          max_new=budget, stop_at_eos=False)
        return g.token_ids
