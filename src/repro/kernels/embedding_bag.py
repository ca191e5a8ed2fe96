"""Pallas TPU kernel: fused EmbeddingBag (gather + weighted segment-sum).

RecSys hot path (kernel_taxonomy §B.6 / §B.11): JAX has no native
EmbeddingBag, and the jnp path (``table[ids]`` then einsum) materialises
the gathered ``(B, L, d)`` rows in HBM.  This kernel drives the row
gather from *scalar-prefetched* bag ids through the BlockSpec index_map —
each table row streams HBM→VMEM once and is accumulated directly into
the output tile, so the op runs at gather-bandwidth with zero
intermediate traffic.

Grid: ``(B, L)`` — L sequential (running accumulation per bag).
Production note: one row per step keeps the index_map exact for
arbitrary vocab sizes; rows are d ≤ 256 floats, and the MXU is idle here
anyway (pure bandwidth op).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(ids_ref, w_ref, table_ref, o_ref, acc, *, bag: int):
    b = pl.program_id(0)
    l = pl.program_id(1)

    @pl.when(l == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    idx = ids_ref[b, l]
    w = w_ref[b, l]
    valid = (idx >= 0).astype(jnp.float32) * w
    row = table_ref[...].astype(jnp.float32)        # (1, d)
    acc[...] = acc[...] + row * valid

    @pl.when(l == bag - 1)
    def _finalize():
        o_ref[...] = acc[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def embedding_bag(table: jax.Array, ids: jax.Array,
                  weights: Optional[jax.Array] = None, *,
                  interpret: bool = False) -> jax.Array:
    """Sum-mode bag lookup. table (V, d), ids (B, L) int32 (-1 pad),
    weights (B, L) or None. Returns (B, d) in table dtype.

    Mean mode / normalisation is applied by ``ops.embedding_bag``.
    """
    v, d = table.shape
    b, bag = ids.shape
    if weights is None:
        weights = jnp.ones((b, bag), jnp.float32)

    kern = functools.partial(_kernel, bag=bag)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, bag),
            in_specs=[
                pl.BlockSpec(
                    (1, d),
                    lambda bi, l, ids_ref, w_ref: (
                        jnp.maximum(ids_ref[bi, l], 0), 0)),
            ],
            out_specs=pl.BlockSpec((1, d),
                                   lambda bi, l, ids_ref, w_ref: (bi, 0)),
            scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, d), table.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(ids, weights.astype(jnp.float32), table)
    return out
