"""Pallas TPU kernel: PQ asymmetric-distance scan of selected posting lists.

The IVF-PQ hot loop: after TopLoc centroid selection, the ``nprobe``
selected posting lists are scanned *compressed* — each doc is ``m``
uint8 codes, and its approximate score is the sum of ``m`` lookups into
the query's ``(m, n_codes)`` ADC table.  Compared to ``ivf_scan`` this
moves 4·d/m fewer bytes HBM→VMEM per doc (16x at d=128, m=32), which is
the memory-roofline term that dominates list scanning.

Layout mirrors ``ivf_scan``: scalar-prefetched selection indices drive
the code-tile index_map (data-dependent gather), the LUT tile stays
VMEM-resident across a query's probes, and a running per-query top-k
register tile is folded with the bitonic merge network.

The in-kernel "gather" is expressed as m one-hot matmuls
(``(blk_l, n_codes) @ (n_codes,)`` per subquantizer): Mosaic has no
general VMEM gather along lanes, but compare-against-iota + MXU dot is
exactly the accumulate-subquantizer-partial-sums schedule and keeps
every op lane-parallel.  Codes are loaded as uint8 (the compression is
the point) and widened in-register.

Grid: ``(B, nprobe·nsub)`` — probe axis sequential so the running tile
carries; batch axis parallel.  VMEM per step: LUT (m·n_codes·4 ≤ 64 KB
at m=64) + code tile (blk_l·m bytes) — tiny next to ivf_scan's float
tiles.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import sorting


def _kernel(sel_ref, tbl_ref, codes_ref, ids_ref, out_v_ref, out_i_ref,
            run_v, run_i, *, k: int, nprobe: int, nsub: int):
    j = pl.program_id(1)          # probe-tile index (sequential)

    @pl.when(j == 0)
    def _init():
        run_v[...] = jnp.full_like(run_v, -jnp.inf)
        run_i[...] = jnp.full_like(run_i, -1)

    table = tbl_ref[...][0].astype(jnp.float32)           # (m, n_codes)
    codes = codes_ref[...][0].astype(jnp.int32)           # (blk_l, m)
    li = ids_ref[0]                                       # (1, blk_l)
    blk_l, m = codes.shape
    n_codes = table.shape[1]

    # ADC: scores[l] = sum_j table[j, codes[l, j]], realised as m
    # one-hot MXU dots (compare-with-iota selects the LUT entry); at
    # HIGHEST the selected f32 entry is exact, at DEFAULT the TPU would
    # round the LUT to bf16
    iota = jax.lax.broadcasted_iota(jnp.int32, (blk_l, n_codes), 1)
    scores = jnp.zeros((blk_l,), jnp.float32)
    for sq in range(m):
        onehot = (iota == codes[:, sq:sq + 1]).astype(jnp.float32)
        scores = scores + jax.lax.dot_general(
            onehot, table[sq], (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    scores = jnp.where(li[0] >= 0, scores, -jnp.inf)[None]   # (1, blk_l)

    blk_v, blk_i = sorting.block_topk_desc(scores, li, k)
    mv, mi = sorting.merge_topk_desc(run_v[...], run_i[...], blk_v, blk_i)
    run_v[...] = mv
    run_i[...] = mi

    @pl.when(j == nprobe * nsub - 1)
    def _finalize():
        out_v_ref[0] = run_v[...]
        out_i_ref[0] = run_i[...]


@functools.partial(jax.jit, static_argnames=("k", "blk_l", "interpret"))
def pq_adc_scan(tables: jax.Array, list_codes: jax.Array,
                list_ids: jax.Array, sel: jax.Array, k: int, *,
                blk_l: int = 0, interpret: bool = False
                ) -> Tuple[jax.Array, jax.Array]:
    """Fused ADC scan over the selected PQ posting lists.

    tables (B, m, n_codes) f32 — per-query ADC lookup tables (built
    outside: a tiny einsum); list_codes (p, Lmax, m) uint8; list_ids
    (p, Lmax) int32 (-1 pad); sel (B, nprobe) int32.

    Returns (values (B, k) f32 desc, doc_ids (B, k) int32) — the ADC
    top-k candidates, to be exact-re-ranked by the caller.
    Padding contract (ops.py): Lmax multiple of blk_l, blk_l & k pow2,
    k ≤ blk_l.

    Mosaic tiles the last two block dims by (8, 128) unless a block
    spans the whole array dimension.  The per-row operands (one list's
    ids, one query's outputs) therefore travel as ``(rows, 1, n)``
    arrays with ``(1, 1, n)`` blocks rather than ``(1, n)`` blocks over
    ``(rows, n)``.
    """
    b, m, n_codes = tables.shape
    p, lmax, _ = list_codes.shape
    nprobe = sel.shape[1]
    if blk_l == 0:
        blk_l = lmax
    assert lmax % blk_l == 0, (lmax, blk_l)
    nsub = lmax // blk_l
    assert sorting._is_pow2(k) and sorting._is_pow2(blk_l) and k <= blk_l

    kern = functools.partial(_kernel, k=k, nprobe=nprobe, nsub=nsub)
    grid = (b, nprobe * nsub)

    def codes_map(bi, j, sel_ref):
        return (sel_ref[bi, j // nsub], j % nsub, 0)

    def ids_map(bi, j, sel_ref):
        return (sel_ref[bi, j // nsub], 0, j % nsub)

    def row_map(bi, j, sel_ref):
        return (bi, 0, 0)

    out_v, out_i = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, m, n_codes),
                             lambda bi, j, sel_ref: (bi, 0, 0)),
                pl.BlockSpec((1, blk_l, m), codes_map),
                pl.BlockSpec((1, 1, blk_l), ids_map),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, k), row_map),
                pl.BlockSpec((1, 1, k), row_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((1, k), jnp.float32),
                pltpu.VMEM((1, k), jnp.int32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, k), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(sel, tables, list_codes, list_ids[:, None, :])
    return out_v[:, 0], out_i[:, 0]
