"""Fused vector-search scoring + top-k — Pallas TPU kernel.

The vector-DB retrieval stage (FAISS in the paper) reduces to a
(queries × d) · (corpus × d)ᵀ matmul followed by per-query top-k.  Fusing
the two means corpus blocks stream HBM→VMEM once; the running top-k
(values + indices) lives in the resident output block across corpus
blocks, merged with each block's scores by k rounds of max + lowest-index
argmax (k is small; ``lax.top_k`` has no Mosaic lowering).

Only the first ``n_valid`` corpus rows are searched.  The count is a
scalar-prefetch operand, so one compiled program serves a growing corpus:
blocks past the valid prefix re-map to the last valid block (no new DMA)
and skip their compute.

Grid (q_blocks, corpus_blocks), corpus innermost.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _topk_kernel(n_ref, q_ref, c_ref, val_ref, idx_ref, *, k: int, bn: int):
    ic = pl.program_id(1)
    n_valid = n_ref[0]

    @pl.when(ic == 0)
    def _init():
        val_ref[...] = jnp.full_like(val_ref, NEG_INF)
        idx_ref[...] = jnp.full_like(idx_ref, -1)

    @pl.when(ic * bn < n_valid)
    def _merge():
        q = q_ref[...].astype(jnp.float32)              # (bq, d)
        c = c_ref[...].astype(jnp.float32)              # (bn, d)
        s = jax.lax.dot_general(q, c, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        lane_n = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(ic * bn + lane_n < n_valid, s, NEG_INF)

        # candidates = running top-k (lower corpus positions) then this
        # block; each round takes the max, ties to the earliest candidate,
        # which is lax.top_k's order over the whole corpus
        cur_v, cur_i = val_ref[...], idx_ref[...]        # (bq, k)
        lane_k = jax.lax.broadcasted_iota(jnp.int32, cur_v.shape, 1)
        new_v = jnp.zeros_like(cur_v)
        new_i = jnp.zeros_like(cur_i)
        for j in range(k):
            m = jnp.maximum(jnp.max(cur_v, axis=1, keepdims=True),
                            jnp.max(s, axis=1, keepdims=True))
            p_cur = jnp.min(jnp.where(cur_v == m, lane_k, k), axis=1,
                            keepdims=True)
            p_blk = jnp.min(jnp.where(s == m, lane_n, bn), axis=1,
                            keepdims=True)
            from_cur = p_cur < k
            i_cur = jnp.sum(jnp.where(lane_k == p_cur, cur_i, 0), axis=1,
                            keepdims=True)
            pick_i = jnp.where(from_cur, i_cur, ic * bn + p_blk)
            new_v = jnp.where(lane_k == j, m, new_v)
            new_i = jnp.where(lane_k == j, pick_i, new_i)
            cur_v = jnp.where(from_cur & (lane_k == p_cur), -jnp.inf, cur_v)
            s = jnp.where(~from_cur & (lane_n == p_blk), -jnp.inf, s)
        val_ref[...] = new_v
        idx_ref[...] = new_i


def topk_retrieval(queries: jax.Array, corpus: jax.Array, k: int,
                   n_valid: Optional[jax.Array] = None, *,
                   block_q: int = 128, block_n: int = 1024,
                   interpret: bool = False):
    """queries (nq, d), corpus (N, d) -> (scores (nq, k), ids (nq, k)),
    inner-product metric (callers pre-normalize for cosine).  ``n_valid``
    (traced int, default N) limits the search to the first rows."""
    nq, d = queries.shape
    N = corpus.shape[0]
    bq, bn = min(block_q, nq), min(block_n, N)
    nqb, nnb = pl.cdiv(nq, bq), pl.cdiv(N, bn)
    n = jnp.reshape(jnp.asarray(N if n_valid is None else n_valid,
                                jnp.int32), (1,))

    def corpus_block(iq, ic, n_ref):
        # past the valid prefix: repeat the last valid block (no re-fetch)
        return jnp.minimum(ic, jnp.maximum(n_ref[0] - 1, 0) // bn), 0

    vals, idxs = pl.pallas_call(
        functools.partial(_topk_kernel, k=k, bn=bn),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nqb, nnb),
            in_specs=[
                pl.BlockSpec((bq, d), lambda iq, ic, n_ref: (iq, 0)),
                pl.BlockSpec((bn, d), corpus_block),
            ],
            out_specs=[
                pl.BlockSpec((bq, k), lambda iq, ic, n_ref: (iq, 0)),
                pl.BlockSpec((bq, k), lambda iq, ic, n_ref: (iq, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((nq, k), jnp.float32),
            jax.ShapeDtypeStruct((nq, k), jnp.int32),
        ],
        interpret=interpret,
    )(n, queries, corpus)
    return vals, idxs
