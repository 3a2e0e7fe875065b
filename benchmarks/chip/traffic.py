"""Traffic generation: query shapes drawn like the paper's four datasets
and the arrival schedule of a traffic mix file (``traffic/<name>.json``).

The dataset table and the per-query draw are copied from the program's
``rag/datasets.py`` so that the yardstick stays fixed when the program
changes.  The schedule is drawn once from the mix's ``pool_seed`` and is
the same for every ``--seed`` (which draws the weights and the store):
with the order of the arrival gaps left to the seed, where the bursts fell
decided the tail (one seed read a 90th percentile of 17.8 and 16.8 s in
two runs, the others 4.2 to 8.8 s).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

# name: (query_tok, ctx_tok, doc_tok, n_docs, subq, web, answer_tok),
# each an inclusive (lo, hi) range
DATASETS: Dict[str, Tuple[tuple, ...]] = {
    "finqabench": ((16, 70), (120, 240), (300, 900), (2, 5), (1, 3),
                   (1, 2), (24, 72)),
    "truthfulqa": ((10, 48), (100, 220), (200, 600), (1, 4), (1, 3),
                   (1, 2), (16, 56)),
    "hotpotqa": ((18, 90), (400, 1000), (500, 1600), (4, 10), (2, 4),
                 (1, 3), (32, 96)),
    "2wikimqa": ((16, 80), (400, 1000), (500, 1800), (4, 10), (2, 5),
                 (2, 4), (32, 96)),
}
CHUNK_SIZE, OVERLAP = 128, 10


def draw_query(dataset: str, rng: np.random.Generator) -> dict:
    """One query's shape: the fields of the program's ``QueryTrace``."""
    q, ctx, doc, ndocs, subq, web, ans = DATASETS[dataset]

    def u(lohi):
        return int(rng.integers(lohi[0], lohi[1] + 1))

    n_docs = u(ndocs)
    doc_tokens = [u(doc) for _ in range(n_docs)]
    step = CHUNK_SIZE - OVERLAP
    n_chunks = sum(max(1, -(-max(t - OVERLAP, 1) // step))
                   for t in doc_tokens)
    return dict(dataset=dataset, query_tokens=u(q), context_tokens=u(ctx),
                n_docs=n_docs, n_chunks=n_chunks,
                rerank_candidates=min(max(8, n_chunks // 2), 32),
                n_subqueries=u(subq), rewrite_tokens=u((16, 48)),
                n_web_searches=u(web), plan_tokens=u((16, 40)),
                refine_tokens=u((24, 64)), answer_tokens=u(ans))


def _pool(mix: dict, n: int, rng: np.random.Generator) -> List[dict]:
    names = mix["datasets"]
    return [draw_query(names[int(rng.integers(len(names)))], rng)
            for _ in range(n)]


def warmup_queries(mix: dict) -> List[dict]:
    """The set-up's warm-up queries: fixed, the same for every seed."""
    rng = np.random.default_rng([mix["pool_seed"], 1])
    return _pool(mix, mix["warmup_queries"], rng)


def schedule(mix: dict, seconds: float) -> Tuple[List[dict], List[float]]:
    """(queries, arrival offsets in seconds) of one run.

    open loop: ``round(rate_qps * seconds)`` arrivals whose gaps are an
    exponential draw rescaled to span exactly ``seconds``, so the offered
    rate is the mix's rate in every run; closed loop: ``max_queries``
    queries, each due when the previous one returns (offsets are None)."""
    rng = np.random.default_rng([mix["pool_seed"], 0])
    if mix["loop"] == "open":
        n = max(1, int(round(mix["rate_qps"] * seconds)))
        queries = _pool(mix, n, rng)
        gaps = rng.exponential(1.0, n)
        gaps *= seconds / gaps.sum()
        arrivals = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        return queries, [float(a) for a in arrivals]
    if mix["loop"] == "closed":
        n = mix["max_queries"]
        return _pool(mix, n, rng), [None] * n
    raise ValueError(f"loop {mix['loop']!r}; pick 'open' or 'closed'")


def chunk_rows(queries: List[dict]) -> int:
    """Most store rows these queries' chunk embedding can add: each
    ``embed_chunks`` dispatch writes at most its batch of chunks, and a
    query's batches sum to its ``n_chunks``."""
    return sum(q["n_chunks"] for q in queries)
