"""Grouped expert FFN for a MoE layer's decode step — Pallas TPU kernel.

A decode step brings one new position per stream to a layer whose device
holds ``held`` routed experts, and those few tokens route to only a few
of them.  The kernel reads and multiplies just those: the hit list (the
held experts with a nonzero gate in any row, in order, and their number)
comes in as scalar prefetch, and a loop over the hit experts copies each
one's ``w_gate`` / ``w_up`` / ``w_down`` from HBM into VMEM, ``BLOCK_FF``
of its ``ff`` columns (of ``w_down``, rows) at a time, and accumulates
``gates[:, e] * expert_e(x)`` in float32.  The copies are double-buffered:
the next block's copy is in flight while this one computes, so the
matmuls of the last block are all that trails the last copy.  An expert
no row routes to is never read, and a step that hits none reads no
expert weights at all.

The weights are the MoE layers' whole stacks ``[L, held, ...]``, left in
HBM, and the layer index is a prefetched scalar.  Handed a per-layer
slice instead, XLA would materialise a copy of every held expert of the
layer before the call, which reads more than the dense layer does.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# ff columns per copy.  At DeepSeek-V2-Lite's widths (2048 x 1408) one
# v5e chip reads a held expert in ~23 us in blocks of 128 or whole, but
# a layer that reads one expert takes ~1.3 us less in blocks: less
# compute trails the last copy
BLOCK_FF = 128

def _kernel(meta_ref, ids_ref, x_ref, g_ref, wg_hbm, wu_hbm, wd_hbm, o_ref,
            wg_buf, wu_buf, wd_buf, sem, *, bf: int, nf: int):
    layer, n_hit = meta_ref[0], meta_ref[1]
    n = n_hit * nf          # (hit expert, ff block) pairs, expert-major

    def copies(j):
        e, buf = ids_ref[j // nf], j % 2
        f = pl.multiple_of((j % nf) * bf, bf)
        return (pltpu.make_async_copy(wg_hbm.at[layer, e, :, pl.ds(f, bf)],
                                      wg_buf.at[buf], sem.at[0, buf]),
                pltpu.make_async_copy(wu_hbm.at[layer, e, :, pl.ds(f, bf)],
                                      wu_buf.at[buf], sem.at[1, buf]),
                pltpu.make_async_copy(wd_hbm.at[layer, e, pl.ds(f, bf), :],
                                      wd_buf.at[buf], sem.at[2, buf]))

    def start(j):
        @pl.when(j < n)
        def _():
            for cp in copies(j):
                cp.start()

    start(0)
    o_ref[...] = jnp.zeros_like(o_ref)
    x = x_ref[...]
    expert_of = jax.lax.broadcasted_iota(jnp.int32, g_ref.shape, 1)

    def body(j, carry):
        start(j + 1)        # into the buffers block j - 1 is done with
        buf = j % 2
        wg, wu, wd = copies(j)
        wg.wait()
        wu.wait()
        g = jnp.dot(x, wg_buf[buf], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_buf[buf], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(x.dtype)
        wd.wait()
        out = jnp.dot(h, wd_buf[buf], preferred_element_type=jnp.float32)
        # this expert's gate for each row, (T, 1)
        gate = jnp.sum(jnp.where(expert_of == ids_ref[j // nf], g_ref[...],
                                 0.0), axis=1, keepdims=True)
        o_ref[...] += gate * out
        return carry

    jax.lax.fori_loop(0, n, body, 0)


def _hit_list(gates: jax.Array):
    """gates (T, held) -> (ids (held,), n_hit): the held experts with a
    nonzero gate in some row first, in order, then the others."""
    hit = jnp.any(gates != 0, axis=0)
    ids = jnp.argsort(jnp.logical_not(hit), stable=True).astype(jnp.int32)
    return ids, hit.sum(dtype=jnp.int32)


def moe_decode(x: jax.Array, gates: jax.Array, w_gate: jax.Array,
               w_up: jax.Array, w_down: jax.Array, layer, *,
               interpret: bool = False):
    """x (T, d); gates (T, held) float32, each row's gate for each held
    expert (0 where it did not route there); w_gate, w_up (L, held, d, ff)
    and w_down (L, held, ff, d), the stacks of every MoE layer; layer the
    index of this one.  Returns (y (T, d) float32, the gate-weighted sum
    of the held experts' outputs; read (held,) bool, the experts whose
    weights the kernel read)."""
    T, d = x.shape
    held, ff = w_gate.shape[1], w_gate.shape[3]
    bf = BLOCK_FF if ff % BLOCK_FF == 0 else ff
    ids, n_hit = _hit_list(gates)
    meta = jnp.stack([jnp.asarray(layer, jnp.int32), n_hit])

    whole = lambda shape: pl.BlockSpec(shape, lambda i, *_: (0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[whole((T, d)), whole((T, held)),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=whole((T, d)),
        scratch_shapes=[
            pltpu.VMEM((2, d, bf), w_gate.dtype),
            pltpu.VMEM((2, d, bf), w_up.dtype),
            pltpu.VMEM((2, bf, d), w_down.dtype),
            pltpu.SemaphoreType.DMA((3, 2)),
        ],
    )
    y = pl.pallas_call(
        functools.partial(_kernel, bf=bf, nf=ff // bf),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, d), jnp.float32),
        interpret=interpret,
    )(meta, ids, x, gates.astype(jnp.float32), w_gate, w_up, w_down)
    read = jnp.zeros((held,), bool).at[ids].max(jnp.arange(held) < n_hit)
    return y, read
