"""Per-kernel validation: shape/dtype sweeps, interpret=True vs the pure-jnp
oracles in kernels/ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.int8_matmul import int8_matmul, quantize_int8
from repro.kernels.mamba2_scan import ssd_chunk
from repro.kernels.topk_retrieval import topk_retrieval


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("sq,sk,h,n,e", [
    (128, 128, 8, 4, 64),
    (256, 128, 4, 4, 128),
    (64, 192, 8, 2, 64),
    (128, 128, 8, 8, 128),   # MHA (no grouping)
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(sq, sk, h, n, e, causal, dtype):
    if causal and sq > sk:
        pytest.skip("causal needs sq <= sk alignment in this sweep")
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (2, sq, h, e), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, sk, n, e), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, sk, n, e), dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("S,h,n,e,bk", [
    (256, 8, 4, 64, 64),
    (512, 16, 2, 128, 128),
    (128, 4, 4, 64, 128),    # bk > S
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(S, h, n, e, bk, dtype):
    b = 3
    key = jax.random.PRNGKey(1)
    q = jax.random.normal(key, (b, h, e), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, S, n, e), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, S, n, e), dtype)
    lengths = jnp.array([S, S // 2, 7], jnp.int32)
    out = decode_attention(q, k, v, lengths, block_k=bk, interpret=True)
    want = ref.decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("M,K,N,bm,bn,bk", [
    (128, 256, 192, 64, 64, 64),
    (64, 64, 64, 64, 64, 64),
    (256, 128, 512, 128, 256, 128),
])
def test_int8_matmul_sweep(M, K, N, bm, bn, bk):
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (M, K))
    w = jax.random.normal(jax.random.fold_in(key, 1), (K, N))
    xq, sx = quantize_int8(x, axis=1)
    wq, sw = quantize_int8(w, axis=0)
    out = int8_matmul(xq, wq, sx, sw, block_m=bm, block_n=bn, block_k=bk,
                      interpret=True)
    want = ref.int8_matmul_ref(xq, wq, sx, sw)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=1e-2)
    # int8 quantized matmul approximates the f32 product
    dense = x @ w
    rel = float(jnp.abs(out.astype(jnp.float32) - dense).mean()
                / jnp.abs(dense).mean())
    assert rel < 0.05


@pytest.mark.parametrize("nq,N,d,k,bq,bn", [
    (16, 1000, 64, 8, 8, 256),
    (8, 512, 128, 16, 8, 128),
    (32, 300, 32, 4, 16, 512),   # bn > N
])
def test_topk_sweep(nq, N, d, k, bq, bn):
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (nq, d))
    c = jax.random.normal(jax.random.fold_in(key, 1), (N, d))
    vals, idxs = topk_retrieval(q, c, k, block_q=bq, block_n=bn,
                                interpret=True)
    wv, wi = ref.topk_retrieval_ref(q, c, k)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(wv), atol=1e-4)
    assert (np.asarray(idxs) == np.asarray(wi)).all()


@pytest.mark.parametrize("b,nc,Q,H,P,N", [
    (2, 3, 32, 4, 16, 8),
    (1, 2, 64, 8, 32, 16),
    (2, 1, 16, 2, 8, 8),
])
def test_ssd_chunk_sweep(b, nc, Q, H, P, N):
    key = jax.random.PRNGKey(4)
    x = jax.random.normal(key, (b, nc, Q, H, P))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1),
                                           (b, nc, Q, H)))
    B = jax.random.normal(jax.random.fold_in(key, 2), (b, nc, Q, H, N))
    C = jax.random.normal(jax.random.fold_in(key, 3), (b, nc, Q, H, N))
    dA = -dt * 0.5
    y, S = ssd_chunk(x, dt, B, C, dA, interpret=True)
    wy, wS = ref.ssd_chunk_ref(x, dt, B, C, dA)
    np.testing.assert_allclose(np.asarray(y), np.asarray(wy), atol=2e-4,
                               rtol=2e-4)
    np.testing.assert_allclose(np.asarray(S), np.asarray(wS), atol=2e-4,
                               rtol=2e-4)


@pytest.mark.parametrize("N,n_valid,bn,dups", [
    (1024, 300, 256, False),   # valid prefix ends inside a block
    (1024, 3, 256, False),     # fewer valid rows than one block's worth
    (512, 512, 128, False),    # whole corpus valid
    (512, 200, 128, True),     # repeated rows: ties go to the lowest index
])
def test_topk_valid_prefix(N, n_valid, bn, dups):
    key = jax.random.PRNGKey(5)
    q = jax.random.normal(key, (8, 64))
    c = jax.random.normal(jax.random.fold_in(key, 1), (N, 64))
    if dups:
        c = jnp.tile(c[:16], (N // 16, 1))
    k = min(4, n_valid)
    vals, idxs = topk_retrieval(q, c, k, jnp.int32(n_valid), block_q=8,
                                block_n=bn, interpret=True)
    wv, wi = ref.topk_retrieval_ref(q, c[:n_valid], k)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(wv), atol=1e-4)
    assert (np.asarray(idxs) == np.asarray(wi)).all()
