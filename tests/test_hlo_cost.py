"""HLO cost-model validation: the roofline's FLOP/byte/collective walker
against analytically-known programs (see EXPERIMENTS.md §Dry-run)."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_cost import analyze
from repro.launch.mesh import auto_mesh


@pytest.fixture(scope="module")
def mesh():
    n = len(jax.devices())
    return auto_mesh((1, n), ("data", "model"))


def test_matmul_flops_exact(mesh):
    M, K, N = 256, 128, 512
    comp = jax.jit(lambda x, w: x @ w).lower(
        jax.ShapeDtypeStruct((M, K), jnp.float32),
        jax.ShapeDtypeStruct((K, N), jnp.float32)).compile()
    r = analyze(comp.as_text())
    assert r["flops"] == pytest.approx(2 * M * K * N, rel=0.01)


def test_scan_trip_count_multiplies(mesh):
    L, D = 12, 64

    def f(x, ws):
        def body(c, w):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, ws)
        return y

    comp = jax.jit(f).lower(
        jax.ShapeDtypeStruct((D, D), jnp.float32),
        jax.ShapeDtypeStruct((L, D, D), jnp.float32)).compile()
    r = analyze(comp.as_text())
    want = 2 * D * D * D * L
    assert want <= r["flops"] <= want * 1.1
    # XLA's own analysis undercounts by ~L (the documented failure mode)
    xla = float(comp.cost_analysis().get("flops", 0.0))
    assert xla < r["flops"] / 2


def test_bytes_positive_and_bounded(mesh):
    M = 512

    def f(x, w):
        return jax.nn.relu(x @ w).sum()

    comp = jax.jit(f).lower(
        jax.ShapeDtypeStruct((M, M), jnp.float32),
        jax.ShapeDtypeStruct((M, M), jnp.float32)).compile()
    r = analyze(comp.as_text())
    lower = 2 * M * M * 4            # must at least read both operands
    upper = 20 * M * M * 4           # and not blow up by orders of magnitude
    assert lower <= r["bytes"] <= upper
