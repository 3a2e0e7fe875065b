"""In-memory vector database (the FAISS role) — exact inner-product search
backed by the fused ``topk_retrieval`` Pallas kernel (jnp reference on CPU).
The store is one fixed-size device array, so adds and searches keep their
compiled programs as the corpus grows.

Supports incremental adds (chunk-indexing sub-stages append batches — the
partitioner's unit of work) and sharded corpora: at pod scale the corpus is
sharded row-wise across the ``data`` mesh axis; exact search is a sharded
matmul + per-shard top-k + global merge, expressed with pjit-compatible ops.
"""
from __future__ import annotations

import threading
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.events import (SP_VDB_ADD, SP_VDB_FETCH, SP_VDB_IDS,
                               SP_VDB_KERNEL, SP_VDB_SEARCH)
from repro.kernels import ops
from repro.serving import spans


_ADD_ROWS = 32     # rows per write program (one compile for any add size)


@jax.jit
def _write_rows(vecs, rows, start, n_rows):
    """vecs[start:start + n_rows] = rows[:n_rows]; the rest of ``rows`` is
    padding and is dropped."""
    r = jnp.arange(rows.shape[0])
    idx = jnp.where(r < n_rows, start + r, vecs.shape[0])
    return vecs.at[idx].set(rows.astype(vecs.dtype), mode="drop")


class VectorDB:
    def __init__(self, dim: int, capacity: int = 65536,
                 dtype=jnp.float32):
        self.dim = dim
        self.capacity = capacity
        self._vecs = jnp.zeros((capacity, dim), dtype)
        self._n = 0
        self._ids: List[int] = []
        self._add_lock = threading.Lock()   # adds come from several PUs

    def __len__(self) -> int:
        return self._n

    def add(self, vectors, ids: Optional[List[int]] = None):
        """vectors (m, dim), L2-normalized by caller for cosine metric."""
        vectors = np.asarray(vectors, np.float32)
        m = vectors.shape[0]
        with spans.span(SP_VDB_ADD, rows=m), self._add_lock:
            if self._n + m > self.capacity:
                raise RuntimeError("vector db capacity exceeded")
            vecs = self._vecs
            for s in range(0, m, _ADD_ROWS):
                part = vectors[s:s + _ADD_ROWS]
                rows = np.zeros((_ADD_ROWS, self.dim), np.float32)
                rows[:len(part)] = part
                vecs = _write_rows(vecs, rows, self._n + s, len(part))
            # rows land before the count grows, so a concurrent search
            # that reads the count first never scans an unwritten row
            self._vecs = vecs
            self._ids.extend(ids if ids is not None
                             else range(self._n, self._n + m))
            self._n += m

    def search(self, queries: jax.Array, k: int,
               use_pallas: Optional[bool] = None
               ) -> Tuple[np.ndarray, np.ndarray]:
        """queries (q, dim) -> (scores (q,k), ids (q,k)).  Exact IP search
        over the valid prefix: the kernel scans the whole fixed-size store
        and masks rows past the count itself, so a growing store reuses
        one compiled program."""
        n = self._n
        if n == 0:
            raise RuntimeError("search on empty db")
        with spans.span(SP_VDB_SEARCH, rows=n):
            with spans.span(SP_VDB_KERNEL):
                vals, idxs = ops.topk_retrieval(queries, self._vecs,
                                                min(k, n), n,
                                                use_pallas=use_pallas)
            with spans.span(SP_VDB_FETCH):
                vals, idxs = np.asarray(vals), np.asarray(idxs)
            with spans.span(SP_VDB_IDS):
                return vals, np.asarray(self._ids)[idxs]

    def lowered_search(self, n_queries: int, k: int):
        """The search program for ``n_queries`` queries as it is lowered
        for the current device (``.compile().as_text()`` shows what runs
        there)."""
        q = jax.ShapeDtypeStruct((n_queries, self.dim), jnp.float32)
        return ops.topk_retrieval.lower(q, self._vecs, min(k, self._n),
                                        self._n)
