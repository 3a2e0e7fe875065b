"""The grouped expert kernel of a MoE layer's decode step (interpret mode)
against the dense-over-held layer it replaces there: the same outputs,
only the routed held experts read, no assignment dropped."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import MoEConfig
from repro.kernels import ops
from repro.models import moe as MOE

E, HELD, TOP_K, D, FF, LAYERS = 8, 4, 2, 128, 256, 3


def routes(kind: str, held: list, other: list) -> list:
    """Each distinct row's experts (global ids): none, one, several or
    all of the held experts reached over the rows."""
    return {
        "none": [(other[0], other[1]), (other[1], other[2]),
                 (other[0], other[3])],
        "one": [(held[1], other[0]), (held[1], other[2]),
                (other[1], other[3])],
        "several": [(held[0], held[3]), (held[0], other[0]),
                    (held[3], other[1])],
        "all": [(held[0], held[1]), (held[2], held[3]),
                (held[3], other[0])],
    }[kind]


@pytest.mark.parametrize("kind,T,first,shared,layer", [
    ("none", 1, 0, 0, 0),
    ("none", 8, 4, 2, 2),
    ("one", 1, 2, 2, 1),
    ("one", 8, 0, 0, 2),
    ("several", 1, 4, 0, 1),
    ("several", 8, 2, 2, 1),
    ("all", 8, 0, 2, 2),
    ("all", 8, 4, 0, 0),
])
def test_kernel_reads_only_the_routed_held_experts(monkeypatch, kind, T,
                                                   first, shared, layer):
    """Rows past the third (T = 8) copy row 0, as a call's pad rows do;
    only the first three count in the stats.  The kernel path takes the
    stacks of every layer and reads ``layer`` of them."""
    cfg = MoEConfig(num_experts=E, num_shared_experts=shared, top_k=TOP_K,
                    d_ff=FF, norm_topk_prob=shared == 2,
                    routed_scaling_factor=1.5, num_experts_held=HELD,
                    first_expert=first)
    stack = jax.vmap(lambda k: MOE.init_moe(k, D, cfg, jnp.float32))(
        jax.random.split(jax.random.PRNGKey(layer), LAYERS))
    held = list(range(first, first + HELD))
    other = [e for e in range(E) if e not in held]
    real = routes(kind, held, other)[:T]
    # row r carries a large component along axis r, which the router
    # sends to row r's experts
    router = 0.01 * jax.random.normal(jax.random.PRNGKey(9), (D, E))
    x = 0.3 * jax.random.normal(jax.random.PRNGKey(7), (T, D))
    for r, experts in enumerate(real):
        x = x.at[r, r].set(4.0)
        for e in experts:
            router = router.at[r, e].set(2.0 + 0.1 * e)
    x = x.at[len(real):].set(x[0])[None]                    # (1, T, D)
    mask = jnp.arange(T)[None] < len(real)
    p = jax.tree.map(lambda a: a[layer], stack)
    p["router"] = router
    kernel_p = dict(p, layer=jnp.int32(layer),
                    **{k: stack[k] for k in MOE.EXPERT_WEIGHTS})

    monkeypatch.setattr(ops, "moe_decode", functools.partial(
        ops.moe_decode, use_pallas=True))
    with jax.default_matmul_precision("highest"):
        y, aux, stats = jax.jit(MOE.moe_ffn, static_argnums=2)(
            kernel_p, x, cfg, token_mask=mask)
        want, want_aux, want_stats = jax.jit(MOE.moe_ffn, static_argnums=2)(
            p, x, cfg, token_mask=mask)
    np.testing.assert_allclose(y, want, atol=2e-5, rtol=1e-5)
    assert aux == want_aux

    got = dict(zip(MOE.ROUTE_STATS, np.asarray(stats).tolist()))
    ref = dict(zip(MOE.ROUTE_STATS, np.asarray(want_stats).tolist()))
    hit = {e for experts in real for e in experts if e in held}
    assert got["experts_hit"] == ref["experts_hit"] == len(hit)
    assert got["experts_read"] == len(hit) and ref["experts_read"] == HELD
    assert got["computed"] == got["routed_held"] == ref["computed"]
    assert {k: got[k] for k in ("routed", "routed_held")} == \
        {k: ref[k] for k in ("routed", "routed_held")}

