"""Chip smoke: the served HeRo path once on one TPU chip, at published widths.

    python chip_smoke.py

Builds the Qwen3 stage family (``src/repro/configs/qwen3_family.py``:
embed and rerank 0.6B, rewrite Qwen3-1.7B, chat Qwen3-4B) in bf16 with
weights from seed 0, then drives ``HeroSession(backend="live")`` with
``build_pipeline``/``stage_fns`` in shared mode with coalescing on — the
path ``python -m repro.launch.serve --serve`` runs:

1. build: the models, then every stage program compiled once;
2. warm-up: one W2 query end to end;
3. measured: 4 W2 queries arriving 0.5 s apart;
4. checks: the vector search against a float64 host reference, the
   embeddings, the rerank scores, and the chat model's cached greedy
   decode against a no-cache forward of the same tokens.

It runs in this one process and fails without a TPU, before it builds any
model.  The lines before the last are diagnostics; the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""
from __future__ import annotations

import json
import sys
import time
from collections import Counter
from pathlib import Path

SEED = 0
QUERIES = 4
INTER_ARRIVAL_S = 0.5
WORKFLOW = 2
DECODE_CHECK_TOKENS = 8
# bf16 logits: the cached decode's greedy token must sit within this
# fraction of the row's largest |logit| of the no-cache forward's maximum
LOGIT_SLACK = 0.05


def fail(msg: str):
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def say(msg: str):
    print(msg, flush=True)


def main():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"needs a TPU; JAX found {dev.platform!r} "
             f"({len(jax.devices())} device(s))")
    say(f"device: {dev.device_kind} x{len(jax.devices())} ({dev.platform})")

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    try:
        import numpy as np
        from jax import monitoring

        from repro.api import HeroSession, SessionOptions
        from repro.core.events import EV_RETRY, EV_STRAGGLER
        from repro.launch import serve
        from repro.launch.compile_cache import enable_compile_cache
        from repro.models import lm
        from repro.rag import default_means, sample_traces, synth_query
    except ImportError as e:
        fail(f"the repro package is not next to this script: {e}")

    say(f"compile cache: {enable_compile_cache()}")
    compiles = Counter()

    def on_event(event, secs, **kw):
        compiles[event] += 1

    monitoring.register_event_duration_secs_listener(on_event)

    def n_compiles():
        return compiles["/jax/core/compile/backend_compile_duration"]

    # -- 1. build ------------------------------------------------------------
    t0 = time.perf_counter()
    pipe = serve.build_pipeline(seed=SEED)
    jax.block_until_ready(pipe.models)
    say(f"build models: {time.perf_counter() - t0:.3f} s")
    for role, (cfg, params) in pipe.models.items():
        n = sum(x.size for x in jax.tree.leaves(params))
        want = cfg.param_count()
        say(f"model {role}: {cfg.name} {n:,} params "
            f"(ModelConfig.param_count {want:,}), {cfg.num_layers} layers, "
            f"d_model {cfg.d_model}, {cfg.dtype}")
        if abs(n - want) > 1e-3 * want:
            fail(f"{cfg.name}: built {n:,} params, config says {want:,}")
    t0 = time.perf_counter()
    fns = serve.stage_fns(pipe)
    jax.block_until_ready(pipe.models)
    say(f"compile stage programs: {time.perf_counter() - t0:.3f} s "
        f"({n_compiles()} backend compiles so far)")

    calls: Counter = Counter()
    outputs = {}

    def counted(stage, fn):
        def run(node, batch):
            calls[stage] += 1
            out = fn(node, batch)
            outputs.setdefault(stage, []).append(out)
            return out
        return run

    fns = {s: (f if s == "__io__" else counted(s, f)) for s, f in fns.items()}

    def session(n_queries):
        traces = sample_traces("finqabench", n_queries, seed=1)
        sess = HeroSession(world="sd8gen4", family="qwen3", backend="live",
                           means=default_means(traces),
                           options=SessionOptions(
                               coalesce=True,
                               cfg_overrides=serve.SERVE_OVERRIDES),
                           stage_fns=fns)
        done = set()
        for qi, tr in enumerate(traces):
            sess.submit(tr, wf=WORKFLOW, arrival_time=qi * INTER_ARRIVAL_S,
                        on_stage_done=lambda h, node, t: done.add(
                            (node.stage, node.kind)))
        return sess, done

    # -- 2. warm-up ----------------------------------------------------------
    sess, _ = session(1)
    t0 = time.perf_counter()
    sess.run(mode="shared", timeout=1200)
    jax.block_until_ready(pipe.models)
    say(f"warm-up (1 query): {time.perf_counter() - t0:.3f} s, "
        f"{n_compiles()} backend compiles so far")

    vsearch_text = pipe.db.lowered_search(1, serve.SEARCH_K).compile() \
        .as_text()
    kernel = "tpu_custom_call" in vsearch_text
    say(f"vsearch program has tpu_custom_call: {kernel}")
    if not kernel:
        fail("vsearch does not run the Pallas top-k kernel")

    # -- 3. measured run -----------------------------------------------------
    calls.clear()
    outputs.clear()
    sess, done = session(QUERIES)
    c0 = n_compiles()
    t0 = time.perf_counter()
    results = sess.run(mode="shared", timeout=1200)
    jax.block_until_ready(pipe.models)
    wall = time.perf_counter() - t0
    new_compiles = n_compiles() - c0
    events = Counter(e[1] for e in sess.last_run.events)
    answered = sum(1 for r in results if "chat_decode" in r.stage_latency)
    say(f"measured ({QUERIES} W2 queries, {INTER_ARRIVAL_S} s apart): "
        f"{wall:.3f} s wall")
    say(f"queries answered: {answered}/{QUERIES}")
    for stage in sorted(calls):
        note = (" (no device work: live prefill is a no-op)"
                if stage.endswith("_prefill") else "")
        say(f"  dispatches {stage}: {calls[stage]}{note}")
    say(f"backend compiles in measured run: {new_compiles}")
    say(f"straggler re-dispatches: {events[EV_STRAGGLER]}, "
        f"retries: {events[EV_RETRY]}")
    unserved = sorted({s for s, kind in done
                       if kind != "io" and s not in fns})
    if unserved:
        fail(f"stages dispatched with no stage fn: {unserved}")
    if events[EV_RETRY]:
        fail(f"{events[EV_RETRY]} retried stage failures")
    if events[EV_STRAGGLER]:
        fail(f"{events[EV_STRAGGLER]} straggler re-dispatches")
    if new_compiles:
        fail(f"{new_compiles} backend compiles inside the measured run")
    if answered != QUERIES:
        fail(f"only {answered} of {QUERIES} queries answered")

    # -- 4. output checks ----------------------------------------------------
    q_ids = pipe.tok.encode(synth_query(seed=3))
    q_vec = pipe.embedder.embed([q_ids])
    norm = float(np.linalg.norm(q_vec))
    say(f"query embedding: shape {q_vec.shape}, norm {norm:.6f}")
    if q_vec.shape != (1, pipe.embedder.cfg.d_model) or abs(norm - 1) > 1e-3:
        fail("query embedding is not one unit vector of d_model")

    vals, ids = pipe.db.search(q_vec, k=serve.SEARCH_K)
    store = np.asarray(pipe.db._vecs[:len(pipe.db)], np.float64)
    host = store @ q_vec[0].astype(np.float64)
    best = np.sort(host)[::-1][:serve.SEARCH_K]
    err = float(np.max(np.abs(np.sort(host[ids[0]])[::-1] - best)))
    score_err = float(np.max(np.abs(vals[0] - host[ids[0]])))
    say(f"vsearch vs float64 host top-{serve.SEARCH_K} over {len(pipe.db)} "
        f"rows: rank-score gap {err:.2e}, score error {score_err:.2e}")
    if err > 2e-3 or score_err > 2e-3:
        fail("vsearch disagrees with the host reference")

    scores = np.concatenate([np.asarray(s, np.float32)
                             for s in outputs.get("rerank", [])])
    say(f"rerank scores: {scores.size}, all finite: "
        f"{bool(np.isfinite(scores).all())}")
    if scores.size == 0 or not np.isfinite(scores).all():
        fail("rerank produced no scores or non-finite scores")

    chat = pipe.chat
    vocab = chat.cfg.vocab_size
    answers = [t for out in outputs.get("chat_decode", [])
               for t in (sum(out.values(), []) if isinstance(out, dict)
                         else out)]
    if not answers or not all(0 <= t < vocab for t in answers):
        fail("chat decode produced no tokens or tokens outside the vocab")
    prompt = q_ids[:serve.PROMPT_TOKENS]
    gen = chat.generate(prompt, max_new=DECODE_CHECK_TOKENS,
                        stop_at_eos=False).token_ids
    toks = np.asarray([prompt + gen[:-1]], np.int32)
    full = jax.jit(lambda p, t: lm.apply(p, chat.cfg, {"tokens": t},
                                         mode="train")[0])(chat.params, toks)
    rows = np.asarray(full[0, len(prompt) - 1:], np.float32)
    slack = rows.max(axis=1) - rows[np.arange(len(gen)), gen]
    bound = LOGIT_SLACK * np.abs(rows).max(axis=1)
    say(f"chat cached decode vs no-cache forward, {len(gen)} tokens: "
        f"max logit gap {float(slack.max()):.4f} "
        f"(bound {float(bound.min()):.4f}), exact argmax "
        f"{int((slack == 0).sum())}/{len(gen)}")
    if (slack > bound).any():
        fail("chat cached decode disagrees with the no-cache forward")

    stats = dev.memory_stats() or {}
    peak, limit = stats.get("peak_bytes_in_use"), stats.get("bytes_limit")
    say(f"device memory: peak_bytes_in_use {peak:,} of bytes_limit "
        f"{limit:,}" if peak and limit else "device memory: not reported")
    if peak and limit and peak >= limit:
        fail("peak device memory reached the limit")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
