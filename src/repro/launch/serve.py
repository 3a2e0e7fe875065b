"""Serving launcher: HeRo-orchestrated agentic RAG over real executors.

    PYTHONPATH=src python -m repro.launch.serve --serve --queries 4

Runs the full executable pipeline — chunker, embedder, vector DB, reranker,
rewriter/planner agents, chat generation — on heterogeneous PU-group
executors under the HeRo scheduler.  The stage models are a family of
``configs.get_family`` (by default Qwen3, ``configs/qwen3_family.py``) at
their published widths in bf16, with seeded random weights; ``--reduced``
builds tiny float32 configs for CPU runs.  Every PU executor shares the
default device.
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import numpy as np

from repro.api import HeroSession, SessionOptions
from repro.configs import ModelConfig, get_family, reduced
from repro.core.spec_decode import DEFAULT_DRAFT_MODEL, is_draft_stage
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.rag import (HashTokenizer, VectorDB, chunk_documents,
                       default_means, sample_traces, shared_corpus_traces,
                       synth_documents, synth_query)
from repro.rag.agents import LMAgent
from repro.rag.embedder import CALL_WIDTH, Embedder, Reranker
from repro.rag.stages import DRAFT_MODELS

SEARCH_K = 4          # chunks each vector search retrieves
PROMPT_TOKENS = 16    # prompt length of every agent call
MAX_NEW_TOKENS = 8    # most tokens one agent call generates
# a decode round never holds more streams than one agent call runs, so a
# fused decode dispatch is one LMAgent call, not several in a row
SERVE_OVERRIDES = {"decode_batch_cap": CALL_WIDTH}


@dataclass
class Pipeline:
    """The built stage models, tokenizer and vector store."""

    models: Dict[str, Tuple[ModelConfig, dict]]   # role -> (config, params)
    tok: HashTokenizer
    embedder: Embedder
    reranker: Reranker
    rewriter: LMAgent
    chat: LMAgent
    draft: Optional[LMAgent]
    db: VectorDB


def build_pipeline(seed: int = 0, *, family: str = "qwen3",
                   reduced_widths: bool = False,
                   spec_decode: bool = False) -> Pipeline:
    """Build the stage models of ``family`` (plus the draft model when
    ``spec_decode``) with weights drawn from ``seed``: role i of the family
    is seeded with ``fold_in(seed, i)``.  Roles that share one config
    object share one model, built once from the first such role's seed:
    ``models[a] is models[b]``."""
    cfgs = dict(get_family(family))
    if spec_decode:
        cfgs["draft"] = DRAFT_MODELS[DEFAULT_DRAFT_MODEL]
    key = jax.random.PRNGKey(seed)
    built: Dict[int, Tuple[ModelConfig, dict]] = {}
    models = {}
    for i, (role, cfg) in enumerate(cfgs.items()):
        if id(cfg) not in built:
            small = reduced(cfg) if reduced_widths else cfg
            built[id(cfg)] = (small, jax.jit(build_model(small).init)(
                jax.random.fold_in(key, i)))
        models[role] = built[id(cfg)]
    return Pipeline(
        models=models,
        tok=HashTokenizer(models["embed"][0].vocab_size),
        embedder=Embedder(*models["embed"]),
        reranker=Reranker(*models["rerank"]),
        rewriter=LMAgent(*models["search"], max_len=256, role="search"),
        chat=LMAgent(*models["chat"], max_len=512, role="chat"),
        draft=(LMAgent(*models["draft"], max_len=256, role="draft")
               if spec_decode else None),
        db=VectorDB(dim=models["embed"][0].d_model))


def stage_fns(pipe: Pipeline):
    """Wire a built pipeline into perf-stage callables — the ``stage_fns``
    a live-backend :class:`HeroSession` dispatches to.  Every model program
    the fns run is compiled here, once, so no dispatch waits on a compile
    (the straggler heartbeat would read one as a slow PU)."""
    tok, embedder, reranker = pipe.tok, pipe.embedder, pipe.reranker
    rewriter, chat, draft, db = pipe.rewriter, pipe.chat, pipe.draft, pipe.db

    docs = synth_documents(4, 400, seed=7)
    chunks = chunk_documents(docs, tok)
    query = synth_query(seed=3)
    q_ids = tok.encode(query)
    q_vec = embedder.embed([q_ids])

    def prompt_of(agent):
        """The query's first tokens in the agent's own vocabulary (the
        retrieval tokenizer's ids where they fit it)."""
        vocab = agent.cfg.vocab_size
        ids = q_ids if vocab >= tok.vocab_size \
            else HashTokenizer(vocab).encode(query)
        return ids[:PROMPT_TOKENS]

    prompts = {id(a): prompt_of(a) for a in (rewriter, chat, draft)
               if a is not None}

    def fn_embed(node, batch):
        ids = [m.id for m in node.payload.get("members", ())] or [node.id]
        if any("embed_chunks" in i for i in ids):
            take = chunks[: max(batch, 1)]
            db.add(embedder.embed([c.token_ids for c in take]))
            return len(take)
        return embedder.embed([q_ids])

    def fn_vsearch(node, batch):
        # the query vector is embed_query's output (a dependency of every
        # vsearch node); this stage is the search itself
        return db.search(q_vec, k=SEARCH_K)

    def fn_rerank(node, batch):
        n = min(batch, CALL_WIDTH)
        scores = reranker.score(q_ids, [chunks[i % len(chunks)].token_ids
                                        for i in range(n)])
        return scores.tolist()

    def need_draft():
        if draft is None:
            raise RuntimeError("speculative decoding needs "
                               "build_pipeline(spec_decode=True)")
        return draft

    def fn_llm(node, batch):
        if is_draft_stage(node.stage):
            # speculative draft sub-dispatch: the small draft model
            # streams spec_width candidate tokens per verify pass
            # (workload = passes × width); candidates are greedy, so the
            # verify fn reproduces them for its acceptance comparison
            return need_draft().generate(
                prompts[id(draft)], max_new=min(node.workload, 16)).token_ids
        agent = rewriter if node.stage.startswith(("rewrite", "plan")) \
            else chat
        prompt = prompts[id(agent)]
        if node.kind == "stream_prefill":
            return "prefill"
        members = node.payload.get("members")
        if members:
            # resident continuous-batching decode round: ONE width-B JAX
            # call serves every member's token group; results slice back
            # per query (member id -> tokens)
            group = max(1, min(batch, MAX_NEW_TOKENS))
            outs = agent.generate_batch([prompt] * len(members),
                                        max_new=group)
            if node.payload.get("spec_width"):
                # speculative verify pass: accept the drafted prefix that
                # matches the target's own greedy tokens (both models are
                # deterministic, so regenerating the draft's candidates
                # here is exact) and stamp the per-member accept counts
                # the round boundary folds into the accept-rate EWMA
                douts = need_draft().generate_batch(
                    [prompts[id(draft)]] * len(members), max_new=group)
                node.payload["spec_accepts"] = {
                    m.id: sum(1 for a, b in zip(g.token_ids, dg.token_ids)
                              if a == b)
                    for m, g, dg in zip(members, outs, douts)}
            return {m.id: g.token_ids for m, g in zip(members, outs)}
        return agent.generate(prompt,
                              max_new=min(batch, MAX_NEW_TOKENS)).token_ids

    reranker.score(q_ids, [chunks[0].token_ids])
    for agent in (rewriter, chat, draft):
        if agent is not None:
            agent.generate(prompts[id(agent)], max_new=2, stop_at_eos=False)

    fns = {s: fn_llm for s in
           ("rewrite_prefill", "rewrite_decode", "plan_prefill",
            "plan_decode", "refine_prefill", "refine_decode",
            "chat_prefill", "chat_decode", "rewrite_draft",
            "plan_draft", "refine_draft", "chat_draft")}
    fns.update(embed=fn_embed, vsearch=fn_vsearch, rerank=fn_rerank,
               __io__=lambda n, b: time.sleep(0.05))
    return fns


def build_stage_fns(seed: int = 0, *, family: str = "qwen3",
                    reduced_widths: bool = False, spec_decode: bool = False):
    """``stage_fns(build_pipeline(...))`` — published widths by default."""
    return stage_fns(build_pipeline(seed, family=family,
                                    reduced_widths=reduced_widths,
                                    spec_decode=spec_decode))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workflow", type=int, default=2, choices=[1, 2, 3])
    ap.add_argument("--queries", type=int, default=2)
    ap.add_argument("--dataset", default="finqabench")
    ap.add_argument("--serve", action="store_true",
                    help="continuous serving mode: staggered admission into "
                         "one shared DAG with cross-query coalescing and "
                         "continuous decode batching (default: the paper's "
                         "isolated single-query latency protocol)")
    ap.add_argument("--inter-arrival", type=float, default=0.5,
                    help="seconds between arrivals in --serve mode")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="turn on the paged-KV subsystem and point every "
                         "query at one shared retrieved corpus, so later "
                         "prefills hit the cross-query prefix cache "
                         "(implies --serve admission)")
    ap.add_argument("--spec-decode", action="store_true",
                    help="speculative decoding: decode rounds dispatch as "
                         "coupled (draft, verify) pairs — the real draft "
                         "model streams candidates the target verifies in "
                         "one sweep (implies --serve admission)")
    ap.add_argument("--family", default="qwen3",
                    help="the stage-model family (configs.get_family)")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny float32 stage models (CPU runs) instead of "
                         "the published widths")
    args = ap.parse_args()
    enable_compile_cache()

    if args.prefix_cache or args.spec_decode:
        args.serve = True
    if args.prefix_cache:
        traces = shared_corpus_traces(args.dataset, args.queries, seed=1)
    else:
        traces = sample_traces(args.dataset, args.queries, seed=1)
    sess = HeroSession(world="sd8gen4", family=args.family, backend="live",
                       means=default_means(traces),
                       options=SessionOptions(
                           coalesce=bool(args.serve),
                           kv_pages=bool(args.prefix_cache),
                           spec_decode=bool(args.spec_decode),
                           cfg_overrides=SERVE_OVERRIDES),
                       stage_fns=build_stage_fns(
                           family=args.family, reduced_widths=args.reduced,
                           spec_decode=args.spec_decode))
    for qi, tr in enumerate(traces):
        sess.submit(tr, wf=args.workflow,
                    arrival_time=qi * args.inter_arrival if args.serve
                    else 0.0)
    results = sess.run(mode="shared" if args.serve else "isolated",
                       timeout=600)
    for res in results:
        extra = (f", {res.decode_rounds} batched decode rounds"
                 if res.decode_rounds else "")
        if res.kv_page_hits:
            extra += (f", {res.kv_page_hits} KV page hits "
                      f"({res.kv_hit_tokens} prefill tokens skipped)")
        print(f"query {res.qid}: {res.n_nodes} sub-stages in "
              f"{res.makespan:.2f}s wall{extra}")
    print(f"mean wall latency: {np.mean([r.makespan for r in results]):.2f}s "
          f"over {len(results)} queries")
    run = sess.last_run
    if args.prefix_cache and run is not None:
        print(f"prefix cache: {run.kv_page_hits} page hits, "
              f"{run.kv_hit_tokens} tokens skipped, "
              f"{run.kv_evictions} evictions")
    if args.spec_decode and run is not None:
        rate = (run.accepted_tokens / run.drafted_tokens
                if run.drafted_tokens else 0.0)
        print(f"spec decode: {run.spec_rounds} speculative rounds, "
              f"{run.drafted_tokens} drafted / {run.accepted_tokens} "
              f"accepted tokens (rate {rate:.2f})")


if __name__ == "__main__":
    main()
