"""Document QA end-to-end driver: the REAL executable pipeline.

Runs the paper's Workflow 2 with actual JAX models (reduced configs, so
it runs on CPU): hash tokenizer -> chunker (128/10) -> embedding model ->
vector DB (fused top-k kernel) -> cross-encoder reranker -> query-rewriter agent ->
chat generation with KV cache — dispatched by a live-backend
``HeroSession`` over heterogeneous PU executors with wall-clock dispatch.

    PYTHONPATH=src python examples/document_qa.py
"""
from repro.api import HeroSession
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import build_stage_fns
from repro.rag import default_means, sample_traces


def main():
    enable_compile_cache()
    traces = sample_traces("finqabench", 2, seed=1)
    sess = HeroSession(world="sd8gen4", family="qwen3", backend="live",
                       means=default_means(traces),
                       stage_fns=build_stage_fns(reduced_widths=True))
    done = []
    for tr in traces:
        sess.submit(tr, wf=2,
                    on_stage_done=lambda h, node, t: done.append(node.stage))
    results = sess.run(mode="isolated", timeout=600)
    for res in results:
        top = sorted(res.stage_latency.items(), key=lambda kv: -kv[1])[:3]
        hot = ", ".join(f"{s}={v:.2f}s" for s, v in top)
        print(f"query {res.qid}: {res.n_nodes} sub-stages, "
              f"{res.makespan:.2f}s wall, hottest: {hot}")
    print(f"{len(done)} stage completions streamed via on_stage_done")


if __name__ == "__main__":
    main()
