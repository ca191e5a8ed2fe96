"""Pallas TPU kernel: fused query x centroid matmul + streaming exact top-k.

TopLoc hot spot #1 (DESIGN.md §2): every conversational turn scores the
query batch against a centroid set — the full ``(p, d)`` set on turn 0 /
refresh, the cached ``(h, d)`` set otherwise — and selects the top-np.

The naive path materialises the full ``(B, p)`` score matrix in HBM and
runs XLA top-k over it.  This kernel streams centroid tiles HBM→VMEM,
feeds the MXU with a ``(B, d) x (d, blk_p)`` matmul per tile, and keeps a
running descending ``(B, k)`` register tile merged with each tile's
bitonic-network top-k — scores never round-trip to HBM, so the op is
centroid-read bandwidth-bound (its roofline floor).

Grid: ``(p // blk_p,)`` — sequential ("arbitrary") so the running tile
carries across steps in VMEM scratch.

VMEM budget per step (defaults blk_p=512, d≤1024, B≤64, f32):
  centroid tile 2 MB + queries 0.25 MB + scores (B, blk_p) 128 KB
  + 2×(B, k) scratch — comfortably under the ~16 MB/core budget.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


from repro.kernels import sorting


def _kernel(q_ref, c_ref, out_v_ref, out_i_ref, run_v, run_i, *, k: int,
            blk_p: int, nblk: int):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        run_v[...] = jnp.full_like(run_v, -jnp.inf)
        run_i[...] = jnp.full_like(run_i, -1)

    q = q_ref[...].astype(jnp.float32)            # (B, d)
    c = c_ref[...].astype(jnp.float32)            # (blk_p, d)
    scores = jax.lax.dot_general(
        q, c, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)       # (B, blk_p)
    ids = (j * blk_p
           + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1))

    blk_v, blk_i = sorting.block_topk_desc(scores, ids, k)
    mv, mi = sorting.merge_topk_desc(run_v[...], run_i[...], blk_v, blk_i)
    run_v[...] = mv
    run_i[...] = mi

    @pl.when(j == nblk - 1)
    def _finalize():
        out_v_ref[...] = run_v[...]
        out_i_ref[...] = run_i[...]


@functools.partial(jax.jit,
                   static_argnames=("k", "blk_p", "interpret"))
def centroid_topk(queries: jax.Array, centroids: jax.Array, k: int, *,
                  blk_p: int = 512, interpret: bool = False
                  ) -> Tuple[jax.Array, jax.Array]:
    """Fused top-k centroid selection. queries (B,d), centroids (p,d).

    Returns (values (B,k) f32 desc-sorted, ids (B,k) int32).
    Padding contract: handled by ``ops.centroid_topk`` (p → multiple of
    blk_p with -inf fill, k → power of two).  Call through ops.py.
    """
    b, d = queries.shape
    p = centroids.shape[0]
    assert p % blk_p == 0, (p, blk_p)
    assert sorting._is_pow2(k) and sorting._is_pow2(blk_p) and k <= blk_p
    nblk = p // blk_p

    kern = functools.partial(_kernel, k=k, blk_p=blk_p, nblk=nblk)
    out_v, out_i = pl.pallas_call(
        kern,
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((b, d), lambda j: (0, 0)),          # queries
            pl.BlockSpec((blk_p, d), lambda j: (j, 0)),      # centroid tile
        ],
        out_specs=[
            pl.BlockSpec((b, k), lambda j: (0, 0)),
            pl.BlockSpec((b, k), lambda j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, k), jnp.float32),
            jax.ShapeDtypeStruct((b, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((b, k), jnp.float32),
            pltpu.VMEM((b, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(queries, centroids)
    return out_v, out_i
