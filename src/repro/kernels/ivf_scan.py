"""Pallas TPU kernel: fused posting-list gather + dot + masked top-k.

TopLoc hot spot #2 (DESIGN.md §2): after centroid selection, IVF scans the
``nprobe`` selected posting lists exhaustively.  The naive XLA path
materialises the gathered ``(B, np, Lmax, d)`` list tensor in HBM (a full
extra round trip).  Here the *scalar-prefetched* selection indices drive
the BlockSpec index_map directly, so each selected list tile is DMA'd
HBM→VMEM exactly once, scored on the MXU against the query, masked
(padding lanes → -inf) and folded into a running per-query top-k register
tile via the bitonic merge network.  This is the classic
``PrefetchScalarGridSpec`` data-dependent-gather pattern.

Grid: ``(B, nprobe)`` — the nprobe axis is sequential ("arbitrary") so
the running tile carries across a query's lists; the batch axis is
parallel.

VMEM per step (Lmax≤2048, d≤1024, f32): list tile ≤ 8 MB — for larger
(Lmax·d) the ops wrapper splits lists into sub-tiles by lowering blk_l.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


from repro.kernels import sorting


def _kernel(sel_ref, q_ref, lv_ref, li_ref, out_v_ref, out_i_ref,
            run_v, run_i, *, k: int, nprobe: int, nsub: int):
    j = pl.program_id(1)          # probe-tile index (sequential)

    @pl.when(j == 0)
    def _init():
        run_v[...] = jnp.full_like(run_v, -jnp.inf)
        run_i[...] = jnp.full_like(run_i, -1)

    q = q_ref[...].astype(jnp.float32)                    # (1, d)
    lv = lv_ref[...].astype(jnp.float32)                  # (1, blk_l, d)
    li = li_ref[...]                                      # (1, blk_l)
    scores = jax.lax.dot_general(
        lv[0], q[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # (blk_l,)
    scores = jnp.where(li[0] >= 0, scores, -jnp.inf)[None]  # (1, blk_l)

    blk_v, blk_i = sorting.block_topk_desc(scores, li, k)
    mv, mi = sorting.merge_topk_desc(run_v[...], run_i[...], blk_v, blk_i)
    run_v[...] = mv
    run_i[...] = mi

    @pl.when(j == nprobe * nsub - 1)
    def _finalize():
        out_v_ref[...] = run_v[...]
        out_i_ref[...] = run_i[...]


@functools.partial(jax.jit, static_argnames=("k", "blk_l", "interpret"))
def ivf_scan(queries: jax.Array, list_vecs: jax.Array, list_ids: jax.Array,
             sel: jax.Array, k: int, *, blk_l: int = 0,
             interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Fused IVF list scan.

    queries (B, d); list_vecs (p, Lmax, d); list_ids (p, Lmax) int32
    (-1 pad); sel (B, nprobe) int32 — per-query selected partitions.

    Returns (values (B, k) f32 desc, doc_ids (B, k) int32).
    Padding contract (ops.py): Lmax multiple of blk_l, blk_l & k pow2,
    k ≤ blk_l.
    """
    b, d = queries.shape
    p, lmax, _ = list_vecs.shape
    nprobe = sel.shape[1]
    if blk_l == 0:
        blk_l = lmax
    assert lmax % blk_l == 0, (lmax, blk_l)
    nsub = lmax // blk_l
    assert sorting._is_pow2(k) and sorting._is_pow2(blk_l) and k <= blk_l

    kern = functools.partial(_kernel, k=k, nprobe=nprobe, nsub=nsub)
    grid = (b, nprobe * nsub)

    def lv_map(bi, j, sel_ref):
        return (sel_ref[bi, j // nsub], j % nsub, 0)

    def li_map(bi, j, sel_ref):
        return (sel_ref[bi, j // nsub], j % nsub)

    out_v, out_i = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, d), lambda bi, j, sel_ref: (bi, 0)),
                pl.BlockSpec((1, blk_l, d), lv_map),
                pl.BlockSpec((1, blk_l), li_map),
            ],
            out_specs=[
                pl.BlockSpec((1, k), lambda bi, j, sel_ref: (bi, 0)),
                pl.BlockSpec((1, k), lambda bi, j, sel_ref: (bi, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((1, k), jnp.float32),
                pltpu.VMEM((1, k), jnp.int32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, k), jnp.float32),
            jax.ShapeDtypeStruct((b, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(sel, queries, list_vecs, list_ids)
    return out_v, out_i
