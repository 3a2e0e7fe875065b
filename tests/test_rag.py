"""RAG substrate tests: tokenizer/chunker/vectordb/embedder + end-to-end
retrieval sanity, plus workflow-builder structure checks."""
import jax
import numpy as np
import pytest

from repro.configs import get_family, reduced
from repro.models import build_model
from repro.rag import (HashTokenizer, VectorDB, build_workflow,
                       chunk_documents, sample_traces, synth_documents)
from repro.rag.embedder import Embedder, Reranker


def test_tokenizer_deterministic_and_bounded():
    tok = HashTokenizer(1000)
    ids = tok.encode("the quick brown fox", bos=True, eos=True)
    assert ids == tok.encode("the quick brown fox", bos=True, eos=True)
    assert all(0 <= i < 1000 for i in ids)
    assert ids[0] == 1 and ids[-1] == 2


def test_chunker_paper_defaults():
    tok = HashTokenizer(32000)
    docs = synth_documents(3, 400, seed=0)
    chunks = chunk_documents(docs, tok, chunk_size=128, overlap=10)
    assert all(len(c.token_ids) <= 128 for c in chunks)
    # 400 tokens -> ceil((400-10)/118) ~ 4 chunks per doc
    per_doc = {}
    for c in chunks:
        per_doc[c.doc_id] = per_doc.get(c.doc_id, 0) + 1
    assert all(3 <= n <= 5 for n in per_doc.values())


def test_vectordb_exact_search():
    db = VectorDB(dim=16, capacity=1024)
    rng = np.random.default_rng(0)
    vecs = rng.normal(size=(300, 16)).astype(np.float32)
    vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
    db.add(jax.numpy.asarray(vecs))
    q = vecs[[5, 17]]
    vals, ids = db.search(jax.numpy.asarray(q), k=3)
    assert ids[0, 0] == 5 and ids[1, 0] == 17        # self-match first
    assert vals[0, 0] == pytest.approx(1.0, abs=1e-3)


def test_vectordb_incremental_add_consistency():
    db = VectorDB(dim=8, capacity=512)
    rng = np.random.default_rng(1)
    vecs = rng.normal(size=(100, 8)).astype(np.float32)
    for i in range(0, 100, 10):                      # indexing sub-stages
        db.add(jax.numpy.asarray(vecs[i:i + 10]))
    vals, ids = db.search(jax.numpy.asarray(vecs[[42]]), k=1)
    assert ids[0, 0] == 42


def test_vectordb_growth_reuses_search_program():
    """Adds of any size and a growing store keep one write and one search
    program: the row count is traced, not baked into the shapes."""
    from repro.kernels import ops
    db = VectorDB(dim=8, capacity=256)
    rng = np.random.default_rng(2)
    vecs = rng.normal(size=(120, 8)).astype(np.float32)
    db.add(vecs[:5])
    db.search(jax.numpy.asarray(vecs[:1]), k=3)
    before = ops.topk_retrieval._cache_size()
    for lo, hi in ((5, 6), (6, 40), (40, 120)):
        db.add(vecs[lo:hi])
        vals, ids = db.search(jax.numpy.asarray(vecs[[lo]]), k=3)
        assert ids[0, 0] == lo
    assert ops.topk_retrieval._cache_size() == before


def test_vectordb_concurrent_adds_lose_nothing():
    """Embed dispatches on several PUs add to one store at once: every row
    lands exactly once and stays findable."""
    import sys
    import threading
    db = VectorDB(dim=8, capacity=512)
    rng = np.random.default_rng(3)
    vecs = rng.normal(size=(16 * 12, 8)).astype(np.float32)
    vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(
            target=db.add, args=(vecs[i * 12:(i + 1) * 12],),
            kwargs={"ids": list(range(i * 12, (i + 1) * 12))})
            for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert len(db) == len(vecs)
    _, ids = db.search(jax.numpy.asarray(vecs), k=1)
    assert (ids[:, 0] == np.arange(len(vecs))).all()


def test_embedder_reranker_pipeline(rng):
    fam = {k: reduced(v) for k, v in get_family("qwen3").items()}
    e_cfg = fam["embed"]
    params = build_model(e_cfg).init(rng)
    emb = Embedder(e_cfg, params, max_tokens=32)
    tok = HashTokenizer(e_cfg.vocab_size)
    texts = ["market revenue growth", "neural retrieval system",
             "market revenue growth quarter"]
    vecs = np.asarray(emb.embed([tok.encode(t) for t in texts]))
    assert vecs.shape == (3, e_cfg.d_model)
    np.testing.assert_allclose(np.linalg.norm(vecs, axis=1), 1.0, atol=1e-3)
    # near-duplicate texts embed closer than unrelated ones
    assert vecs[0] @ vecs[2] > vecs[0] @ vecs[1]

    r_cfg = fam["rerank"]
    rr = Reranker(r_cfg, build_model(r_cfg).init(rng), max_tokens=48)
    scores = rr.score(tok.encode(texts[0]),
                      [tok.encode(t) for t in texts])
    assert scores.shape == (3,)
    assert np.isfinite(scores).all()


@pytest.mark.parametrize("wf", [1, 2, 3])
@pytest.mark.parametrize("fine", [True, False])
def test_workflow_structure(wf, fine):
    tr = sample_traces("hotpotqa", 1, seed=5)[0]
    dag = build_workflow(wf, tr, fine_grained=fine)
    names = set(dag.nodes)
    assert "embed_chunks" in names and "chat_decode" in names
    if wf >= 2:
        assert "rewrite_decode" in names
    if wf >= 3:
        assert "plan_decode" in names
    # graph is a DAG
    order = dag.topo_order()
    assert len(order) == len(dag.nodes)


def test_dynamic_expansion_spawns_branches():
    tr = sample_traces("2wikimqa", 1, seed=2)[0]
    dag = build_workflow(3, tr, fine_grained=True)
    n_before = len(dag.nodes)
    # manually complete the rewrite chain to fire the expander
    for nid in ["embed_chunks", "embed_query", "rewrite_prefill"]:
        dag.nodes[nid].status = "done"
    dag.mark_done("rewrite_decode", 1.0)
    assert len(dag.nodes) > n_before          # sub-query branches appeared
    assert any(n.startswith("vsearch_sq") for n in dag.nodes)
