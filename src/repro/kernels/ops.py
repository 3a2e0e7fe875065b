"""jit'd public wrappers for the Pallas kernels.

``use_pallas`` dispatch: on TPU backends the Pallas kernels run natively;
on CPU (this container) they run via interpret mode when explicitly
requested, otherwise the jnp reference executes.  The dry-run lowers the
reference path so cost_analysis() sees the real FLOPs.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as _decode
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.int8_matmul import int8_matmul as _int8
from repro.kernels.int8_matmul import quantize_int8  # noqa: F401 (re-export)
from repro.kernels.mamba2_scan import ssd_chunk as _ssd
from repro.kernels.topk_retrieval import topk_retrieval as _topk


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _mode(use_pallas: Optional[bool]):
    """-> (run_kernel, interpret)."""
    if use_pallas is None:
        return _on_tpu(), False
    return use_pallas, not _on_tpu()


@functools.partial(jax.jit, static_argnames=("causal", "use_pallas",
                                             "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True,
                    use_pallas: Optional[bool] = None,
                    block_q: int = 256, block_k: int = 256):
    run, interp = _mode(use_pallas)
    if run:
        return _flash(q, k, v, causal=causal, block_q=block_q,
                      block_k=block_k, interpret=interp)
    return ref.flash_attention_ref(q, k, v, causal=causal)


@functools.partial(jax.jit, static_argnames=("use_pallas", "block_k"))
def decode_attention(q, k_cache, v_cache, lengths, *,
                     use_pallas: Optional[bool] = None, block_k: int = 512):
    run, interp = _mode(use_pallas)
    if run:
        return _decode(q, k_cache, v_cache, lengths, block_k=block_k,
                       interpret=interp)
    return ref.decode_attention_ref(q, k_cache, v_cache, lengths)


@functools.partial(jax.jit, static_argnames=("use_pallas", "out_dtype"))
def int8_matmul(x, w, sx, sw, *, use_pallas: Optional[bool] = None,
                out_dtype=jnp.bfloat16):
    run, interp = _mode(use_pallas)
    if run:
        return _int8(x, w, sx, sw, out_dtype=out_dtype, interpret=interp)
    return ref.int8_matmul_ref(x, w, sx, sw, out_dtype=out_dtype)


@functools.partial(jax.jit, static_argnames=("k", "use_pallas"))
def topk_retrieval(queries, corpus, k: int, n_valid=None, *,
                   use_pallas: Optional[bool] = None):
    run, interp = _mode(use_pallas)
    if run:
        return _topk(queries, corpus, k, n_valid, interpret=interp)
    return ref.topk_retrieval_ref(queries, corpus, k, n_valid)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def ssd_chunk(x, dt, B, C, dA, *, use_pallas: Optional[bool] = None):
    run, interp = _mode(use_pallas)
    if run:
        return _ssd(x, dt, B, C, dA, interpret=interp)
    return ref.ssd_chunk_ref(x, dt, B, C, dA)
