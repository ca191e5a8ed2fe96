"""Pallas TPU megakernel: one whole TopLoc turn in a single dispatch.

The classic path costs three device dispatches per turn — centroid
top-k (``centroid_topk``), probed-list scan / ADC (``ivf_scan`` /
``pq_adc``), exact re-rank (XLA) — with the intermediate probe ids and
candidate buffers bounced through HBM between them.  ``fused_turn``
runs all three stages inside one kernel:

  stage 1  centroid tiles stream in via BlockSpec and are scored on the
           MXU against the whole query batch; a running ``(B, nprobe)``
           probe set lives in a VMEM register tile (tie-aware bitonic
           merge, so the selection order matches ``lax.top_k``).
  stage 2  the probe ids move VMEM→SMEM once, then drive *in-kernel*
           double-buffered DMAs that gather list tiles straight from
           the HBM-resident (``ANY`` memory space) posting-list tensor
           — probe ids and candidates never round-trip through HBM.
           Candidates fold into a running ``(B, r)`` register tile.
  stage 3  per-candidate rows are DMA-gathered (by doc id for IVF-PQ,
           by flat scan position for quantised IVF) and re-ranked with
           a float32 multiply-reduce in-kernel; the final top-k comes
           off the tie-aware network.

``fused_scan`` is the same machinery minus stage 1: the selection is
scalar-prefetched (cached-centroid turns, sharded local scans) and the
kernel fuses scan + re-rank into one dispatch, emitting tie-break
positions compatible with ``distributed_topk_ordered``.

Precision contract
------------------
* ``"f32"``  — stages 1–2 score in float32.  Float IVF needs no
  re-rank; ids, scores and the probe selection match the 3-dispatch
  reference exactly (ties broken by smaller flat position, the
  ``lax.top_k`` order).
* ``"bf16"`` — stage 1–2 operands are cast to bfloat16 and accumulated
  in float32 on the MXU (half the MXU cycles per tile).
* ``"int8"`` — stage 1–2 operands are symmetrically quantised *per
  tile* (scale = 127/max|tile|; per-query-row scale for q), scored
  with integer MXU dots, dequantised once per tile.

Quantised variants keep a widened candidate set (``k·over`` for IVF,
the ADC re-rank depth for IVF-PQ) and ALWAYS finish with the float32
in-kernel re-rank of stage 3 against uncompressed rows, so the
*returned scores are exact float dot products*: quantisation can only
perturb which candidates survive stage 2, never the reported score.
That is why a pinned recall floor (fig8) is the acceptance for
bf16/int8 while f32 keeps strict bit-identity.

The scoring helpers below are pure jnp and shared with the ``ref.py``
oracles, so the reference emulation quantises at exactly the kernel's
tile granularity — integer dots are exact, making interpret-vs-ref
comparisons deterministic even for the int8 path.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import sorting

PAD_POS = sorting.PAD_POS


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


# ---------------------------------------------------------------------------
# scoring helpers — pure jnp, shared with the ref.py oracles
# ---------------------------------------------------------------------------


def quantize_sym(x: jax.Array, axes) -> Tuple[jax.Array, jax.Array]:
    """Symmetric int8 quantisation over ``axes``: (q_int8, scale)."""
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = 127.0 / jnp.maximum(amax, 1e-30)
    q = jnp.clip(jnp.round(x * scale), -127, 127).astype(jnp.int8)
    return q, scale


def score_tile(q: jax.Array, tile: jax.Array, precision: str) -> jax.Array:
    """(B, d) × (T, d) → (B, T) scores under the precision contract.

    int8 quantises ``tile`` with one scale per call and ``q`` per row;
    the ref emulation reshapes the padded operand into the same tiles,
    so both paths see identical integer dots and identical dequant
    divides.
    """
    if precision == "f32":
        # HIGHEST, like the XLA path (``ivf.F32``): at DEFAULT the TPU
        # would round the f32 operands to bf16
        return jax.lax.dot_general(
            q, tile, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    if precision == "bf16":
        return jax.lax.dot_general(
            q.astype(jnp.bfloat16), tile.astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    if precision == "int8":
        qi, sq = quantize_sym(q, axes=(1,))               # (B, d), (B, 1)
        ti, st = quantize_sym(tile, axes=(0, 1))          # (T, d), (1, 1)
        # int8 operands, int32 accumulation: the MXU's integer mode
        acc = jax.lax.dot_general(
            qi, ti, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)             # (B, T)
        return acc.astype(jnp.float32) / (sq * st[0])
    raise ValueError(f"unknown precision {precision!r}")


def adc_score_tile(table: jax.Array, codes: jax.Array, precision: str
                   ) -> jax.Array:
    """ADC scores for one code tile: (m, C) table × (T, ≥m) codes → (T,).

    Realised as m one-hot MXU dots (cf. ``pq_adc``); bf16 casts the
    LUT, int8 quantises it with one scale per (m, C) table — tile
    granularity is irrelevant for PQ because the LUT is constant across
    tiles, which keeps the ref emulation (a plain gather of the same
    integer LUT) exact.
    """
    t = codes.shape[0]
    m, n_codes = table.shape          # codes may carry lane padding past m
    iota = jax.lax.broadcasted_iota(jnp.int32, (t, n_codes), 1)
    if precision == "int8":
        ti, st = quantize_sym(table, axes=(0, 1))         # (m, C) int8
        acc = jnp.zeros((t,), jnp.int32)
        for sq in range(m):
            onehot = (iota == codes[:, sq:sq + 1]).astype(jnp.int8)
            acc = acc + jax.lax.dot_general(
                onehot, ti[sq],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
        return acc.astype(jnp.float32) / st[0, 0]
    tbl = table.astype(jnp.bfloat16) if precision == "bf16" else table
    hi = jax.lax.Precision.HIGHEST if precision == "f32" else None
    scores = jnp.zeros((t,), jnp.float32)
    for sq in range(m):
        onehot = (iota == codes[:, sq:sq + 1]).astype(tbl.dtype)
        scores = scores + jax.lax.dot_general(
            onehot, tbl[sq], (((1,), (0,)), ((), ())), precision=hi,
            preferred_element_type=jnp.float32)
    return scores


def _iota2(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


#: rows per re-rank DMA: HBM arrays are tiled (8, 128), so a copy may
#: only start on, and span, whole 8-row sublane tiles
ROW_TILE = 8


def _gather_rerank(src_of, qrow, rblk, rsem, r_pad):
    """Exact float32 scores of ``r_pad`` candidate rows, gathered from HBM.

    ``src_of(i)`` → (hbm_ref, row) of candidate i; ``hbm_ref`` is a
    2-D (rows, d) ref whose row count is a multiple of ``ROW_TILE``.
    Each candidate's aligned ``ROW_TILE``-row tile is DMA'd into
    ``rblk[i]``; its row is then picked with a one-hot max, which
    returns the selected score exactly.  The score is an explicit
    multiply-reduce (not a dot_general) mirroring
    ``toploc._scan_lists_pq``, so the fused and 3-dispatch paths lower
    the same reduction.  Returns (1, r_pad).
    """
    copies, offs = [], []
    for i in range(r_pad):
        ref, row = src_of(i)
        start = pl.multiple_of((row // ROW_TILE) * ROW_TILE, ROW_TILE)
        c = pltpu.make_async_copy(ref.at[pl.ds(start, ROW_TILE)],
                                  rblk.at[i], rsem)
        c.start()
        copies.append(c)
        offs.append(row - start)
    for c in copies:
        c.wait()
    sub = _iota2((ROW_TILE, 1), 0)
    lane = _iota2((1, r_pad), 1)
    ex = jnp.full((1, r_pad), -jnp.inf, jnp.float32)
    for i in range(r_pad):
        s8 = jnp.sum(rblk[i].astype(jnp.float32) * qrow, axis=-1,
                     keepdims=True)                        # (8, 1)
        si = jnp.max(jnp.where(sub == offs[i], s8, -jnp.inf))
        ex = jnp.where(lane == i, si, ex)
    return ex


# ---------------------------------------------------------------------------
# fused_turn — stages 1+2+3, one dispatch
# ---------------------------------------------------------------------------


def _turn_kernel(*refs, family: str, do_rerank: bool, precision: str,
                 b: int, p: int, nc: int, blk_p: int, nprobe: int,
                 nsub: int, blk_l: int, lpad: int, np_pad: int,
                 r: int, r_pad: int, kp: int):
    # per-query running state is (B, 1, n): Mosaic indexes the leading
    # (untiled) dim dynamically, but not a sublane of a narrow tile
    if family == "pq":
        (q_ref, q3_ref, cents_ref, tbl_ref, lists_hbm, li_hbm,
         corpus_hbm, out_v, out_i, out_sel,
         run_pv, run_pi, sel_smem, run_cv, run_ci, run_cp,
         lbuf, ibuf, cand_smem, rblk, lsem, ssem, rsem) = refs
    elif do_rerank:
        (q_ref, q3_ref, cents_ref, lists_hbm, li_hbm,
         out_v, out_i, out_sel,
         run_pv, run_pi, sel_smem, run_cv, run_ci, run_cp,
         lbuf, ibuf, cand_smem, rblk, lsem, ssem, rsem) = refs
    else:
        (q_ref, q3_ref, cents_ref, lists_hbm, li_hbm,
         out_v, out_i, out_sel,
         run_pv, run_pi, sel_smem, run_cv, run_ci, run_cp,
         lbuf, ibuf, lsem, ssem) = refs

    j = pl.program_id(0)
    npr = nprobe * nsub

    def list_dma(t, slot):
        bq = t // npr
        jp = t % npr
        pid = sel_smem[bq, jp // nsub]
        sub = jp % nsub
        vec = pltpu.make_async_copy(
            lists_hbm.at[pid, pl.ds(sub * blk_l, blk_l)],
            lbuf.at[slot], lsem.at[slot, 0])
        ids = pltpu.make_async_copy(
            li_hbm.at[pid, :, pl.ds(sub * blk_l, blk_l)],
            ibuf.at[slot], lsem.at[slot, 1])
        return vec, ids

    # ---- stage 1: centroid tiles → running (B, np_pad) probe set -----
    @pl.when(j < nc)
    def _stage1():
        @pl.when(j == 0)
        def _init():
            run_pv[...] = jnp.full_like(run_pv, -jnp.inf)
            run_pi[...] = jnp.full_like(run_pi, PAD_POS)

        scores = score_tile(q_ref[...], cents_ref[...], precision)
        pos = j * blk_p + _iota2((b, blk_p), 1)
        valid = pos < p
        vals = jnp.where(valid, scores, -jnp.inf)
        # ``scores > inf`` never holds; selecting on it gives posm the
        # scores' (B, blk_p) vector layout.  A bare iota is laid out
        # sublane-replicated, and Mosaic aborts when the sorting
        # network's jnp.roll slices such a value with B > 8 rows.
        posm = jnp.where(valid & ~(scores > jnp.inf), pos, PAD_POS)
        # the global centroid index doubles as id and tie-break pos —
        # exactly lax.top_k's order over the flat centroid-score row
        bv, bi_, bp_ = sorting.block_topk_desc_tie(vals, posm, posm,
                                                   np_pad)
        mv, mi, _ = sorting.merge_topk_desc_tie(
            run_pv[...], run_pi[...], run_pi[...], bv, bi_, bp_)
        run_pv[...] = mv
        run_pi[...] = mi

        @pl.when(j == nc - 1)
        def _handoff():
            # probe ids leave VMEM exactly once: into SMEM, where they
            # steer the stage-2 gather DMAs as scalars
            cp = pltpu.make_async_copy(run_pi, sel_smem, ssem)
            cp.start()
            cp.wait()
            run_cv[...] = jnp.full_like(run_cv, -jnp.inf)
            run_ci[...] = jnp.full_like(run_ci, -1)
            run_cp[...] = jnp.full_like(run_cp, PAD_POS)
            v0, i0 = list_dma(0, 0)
            v0.start()
            i0.start()

    # ---- stage 2: probed-list tiles → running (B, r_pad) candidates --
    @pl.when((j >= nc) & (j < nc + b * npr))
    def _stage2():
        t = j - nc
        bq = t // npr
        jp = t % npr
        slot = jax.lax.rem(t, 2)

        @pl.when(t + 1 < b * npr)
        def _prefetch():
            vn, in_ = list_dma(t + 1, jax.lax.rem(t + 1, 2))
            vn.start()
            in_.start()

        vc, ic = list_dma(t, slot)
        vc.wait()
        ic.wait()
        tile = lbuf[slot]                                 # (blk_l, d|m)
        lid = ibuf[slot]                                  # (1, blk_l)

        if family == "pq":
            tbl = tbl_ref[pl.ds(bq, 1)][0]                # (m, C)
            s = adc_score_tile(tbl, tile.astype(jnp.int32),
                               precision)[None]
        else:
            s = score_tile(q3_ref[bq], tile, precision)   # (1, blk_l)

        pos = jp * blk_l + _iota2((1, blk_l), 1)
        valid = lid >= 0
        vals = jnp.where(valid, s, -jnp.inf)
        posm = jnp.where(valid, pos, PAD_POS)
        bv, bi_, bp_ = sorting.block_topk_desc_tie(vals, lid, posm,
                                                   r_pad)
        mv, mi, mp = sorting.merge_topk_desc_tie(
            run_cv[bq], run_ci[bq], run_cp[bq], bv, bi_, bp_)
        run_cv[bq] = mv
        run_ci[bq] = mi
        run_cp[bq] = mp

    # ---- stage 3: float32 in-kernel re-rank + write-out --------------
    @pl.when(j >= nc + b * npr)
    def _stage3():
        bq = j - (nc + b * npr)

        @pl.when(j == nc + b * npr)
        def _sel_out():
            out_sel[...] = run_pi[...]
            if do_rerank:
                # every row's candidate keys move VMEM→SMEM in one copy:
                # a DMA may not cut a 128-lane tile, which one (1, r_pad)
                # row of the (B, 1, r_pad) state would
                key_src = run_ci if family == "pq" else run_cp
                cp = pltpu.make_async_copy(key_src, cand_smem, ssem)
                cp.start()
                cp.wait()

        if not do_rerank:
            out_v[bq] = run_cv[bq][:, :kp]
            out_i[bq] = run_ci[bq][:, :kp]
        else:
            def src_of(i):
                if family == "pq":
                    return corpus_hbm, jnp.maximum(cand_smem[bq, 0, i], 0)
                # flat pos → (probe, offset) → uncompressed list row
                cpos = cand_smem[bq, 0, i]
                probe_i = jnp.minimum(cpos // lpad, nprobe - 1)
                off = jax.lax.rem(cpos, lpad)
                return lists_hbm.at[sel_smem[bq, probe_i]], off

            ex = _gather_rerank(src_of, q3_ref[bq], rblk, rsem,
                                r_pad)                    # (1, r_pad)
            ids_row = run_ci[bq]
            rank = _iota2((1, r_pad), 1)
            # candidates past the exact depth r (pow2 padding) must not
            # re-enter: the 3-dispatch path never re-ranks them
            valid = (ids_row >= 0) & (rank < r)
            vals = jnp.where(valid, ex, -jnp.inf)
            bv, bi_, _ = sorting.block_topk_desc_tie(vals, ids_row,
                                                     rank, kp)
            out_v[bq] = bv
            out_i[bq] = bi_


def _turn_call(kern, *, family, do_rerank, b, d, m, n_codes, blk_p, nc,
               blk_l, np_pad, r_pad, kp, grid, list_dtype, row_w,
               interpret):
    def cents_map(j):
        return (jnp.minimum(j, nc - 1), 0)

    in_specs = [
        pl.BlockSpec((b, d), lambda j: (0, 0)),
        pl.BlockSpec((b, 1, d), lambda j: (0, 0, 0)),
        pl.BlockSpec((blk_p, d), cents_map),
    ]
    if family == "pq":
        in_specs.append(pl.BlockSpec((b, m, n_codes),
                                     lambda j: (0, 0, 0)))
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))      # lists
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))      # list ids
    if family == "pq":
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))  # corpus

    scratch = [
        pltpu.VMEM((b, np_pad), jnp.float32),        # run_pv
        pltpu.VMEM((b, np_pad), jnp.int32),          # run_pi
        pltpu.SMEM((b, np_pad), jnp.int32),          # sel_smem
        pltpu.VMEM((b, 1, r_pad), jnp.float32),      # run_cv
        pltpu.VMEM((b, 1, r_pad), jnp.int32),        # run_ci
        pltpu.VMEM((b, 1, r_pad), jnp.int32),        # run_cp
        pltpu.VMEM((2, blk_l, row_w), list_dtype),   # lbuf
        pltpu.VMEM((2, 1, blk_l), jnp.int32),        # ibuf
    ]
    if do_rerank:
        scratch.append(pltpu.SMEM((b, 1, r_pad), jnp.int32))   # cand_smem
        scratch.append(pltpu.VMEM((r_pad, ROW_TILE, d),
                                  jnp.float32))                # rblk
    scratch.append(pltpu.SemaphoreType.DMA((2, 2)))            # lsem
    scratch.append(pltpu.SemaphoreType.DMA)                    # ssem
    if do_rerank:
        scratch.append(pltpu.SemaphoreType.DMA)                # rsem

    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((b, 1, kp), lambda j: (0, 0, 0)),
            pl.BlockSpec((b, 1, kp), lambda j: (0, 0, 0)),
            pl.BlockSpec((b, np_pad), lambda j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, kp), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, kp), jnp.int32),
            jax.ShapeDtypeStruct((b, np_pad), jnp.int32),
        ],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )


def _turn_dims(centroids, lists, nprobe, k, r, blk_p, blk_l):
    p_pad = centroids.shape[0]
    p, lpad = lists.shape[0], lists.shape[1]
    assert p_pad % blk_p == 0 and lpad % blk_l == 0, \
        (p_pad, blk_p, lpad, blk_l)
    assert lpad % ROW_TILE == 0, lpad
    nc = p_pad // blk_p
    nsub = lpad // blk_l
    kp = _next_pow2(k)
    np_pad = _next_pow2(nprobe)
    r_pad = _next_pow2(r)
    assert np_pad <= blk_p and r_pad <= blk_l and kp <= r_pad, \
        (np_pad, blk_p, r_pad, blk_l, kp)
    return p, nc, nsub, lpad, kp, np_pad, r_pad


@functools.partial(jax.jit, static_argnames=(
    "nprobe", "k", "r", "precision", "blk_p", "blk_l", "interpret"))
def fused_turn(queries: jax.Array, centroids: jax.Array,
               list_vecs: jax.Array, list_ids: jax.Array, *,
               nprobe: int, k: int, r: int, precision: str = "f32",
               blk_p: int = 512, blk_l: int = 2048,
               interpret: bool = False
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Single-dispatch IVF turn.

    queries (B, d); centroids (p_pad, d) zero-padded to a blk_p
    multiple (real count = list_vecs.shape[0]; padded rows are masked
    by position); list_vecs (p, lpad, d); list_ids (p, lpad) int32
    (-1 pad); ``r`` = exact candidate depth — k for f32 (no re-rank),
    k·over for quantised (stage 3 re-ranks in-kernel from the float
    list rows).

    Returns (values (B, kp), ids (B, kp), sel (B, np_pad)); callers
    slice to (k, nprobe).  Padding contract (ops.py): pow2 kp/np_pad/
    r_pad, np_pad ≤ blk_p, kp ≤ r_pad ≤ blk_l.
    """
    b, d = queries.shape
    p, nc, nsub, lpad, kp, np_pad, r_pad = _turn_dims(
        centroids, list_vecs, nprobe, k, r, blk_p, blk_l)
    do_rerank = precision != "f32"

    kern = functools.partial(
        _turn_kernel, family="ivf", do_rerank=do_rerank,
        precision=precision, b=b, p=p, nc=nc, blk_p=blk_p,
        nprobe=nprobe, nsub=nsub, blk_l=blk_l, lpad=lpad,
        np_pad=np_pad, r=r, r_pad=r_pad, kp=kp)
    call = _turn_call(
        kern, family="ivf", do_rerank=do_rerank, b=b, d=d, m=0,
        n_codes=0, blk_p=blk_p, nc=nc, blk_l=blk_l, np_pad=np_pad,
        r_pad=r_pad, kp=kp, grid=(nc + b * nprobe * nsub + b,),
        list_dtype=jnp.float32, row_w=d, interpret=interpret)
    v, i, sel = call(queries, queries[:, None], centroids, list_vecs,
                     list_ids[:, None])
    return v[:, 0], i[:, 0], sel


@functools.partial(jax.jit, static_argnames=(
    "nprobe", "k", "r", "precision", "blk_p", "blk_l", "interpret"))
def fused_turn_pq(queries: jax.Array, centroids: jax.Array,
                  tables: jax.Array, list_codes: jax.Array,
                  list_ids: jax.Array, corpus: jax.Array, *,
                  nprobe: int, k: int, r: int, precision: str = "f32",
                  blk_p: int = 512, blk_l: int = 4096,
                  interpret: bool = False
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Single-dispatch IVF-PQ turn: centroid top-k + ADC + exact re-rank.

    tables (B, m, n_codes) f32 per-query ADC LUTs; list_codes
    (p, lpad, mw) uint8 with mw ≥ m (Mosaic DMAs whole 128-lane rows,
    so the ops wrapper pads the code width to a lane multiple); corpus
    (N, d) f32 re-rank rows gathered by candidate doc id, N a multiple
    of ``ROW_TILE``.  ``r`` = ADC re-rank depth
    (max(k, min(rerank, nprobe·lmax)) upstream).  Stage 3 always runs.
    """
    b, d = queries.shape
    _, m, n_codes = tables.shape
    p, nc, nsub, lpad, kp, np_pad, r_pad = _turn_dims(
        centroids, list_codes, nprobe, k, r, blk_p, blk_l)
    assert corpus.shape[0] % ROW_TILE == 0, corpus.shape

    kern = functools.partial(
        _turn_kernel, family="pq", do_rerank=True, precision=precision,
        b=b, p=p, nc=nc, blk_p=blk_p, nprobe=nprobe, nsub=nsub,
        blk_l=blk_l, lpad=lpad, np_pad=np_pad, r=r, r_pad=r_pad, kp=kp)
    call = _turn_call(
        kern, family="pq", do_rerank=True, b=b, d=d, m=m,
        n_codes=n_codes, blk_p=blk_p, nc=nc, blk_l=blk_l,
        np_pad=np_pad, r_pad=r_pad, kp=kp,
        grid=(nc + b * nprobe * nsub + b,),
        list_dtype=jnp.uint8, row_w=list_codes.shape[2],
        interpret=interpret)
    v, i, sel = call(queries, queries[:, None], centroids, tables,
                     list_codes, list_ids[:, None], corpus)
    return v[:, 0], i[:, 0], sel


# ---------------------------------------------------------------------------
# fused_scan — stages 2+3 with a prefetched selection
# ---------------------------------------------------------------------------


def _scan_kernel(sel_ref, own_ref, *refs, family: str, do_rerank: bool,
                 precision: str, nprobe: int, nsub: int, blk_l: int,
                 lpad: int, r: int, r_pad: int, kp: int):
    if family == "pq":
        if do_rerank:
            (tbl_ref, q_ref, tiles_ref, li_ref, corpus_hbm,
             out_v, out_i, out_p, run_v, run_i, run_p,
             cand_smem, rblk, rsem, ssem) = refs
        else:
            (tbl_ref, tiles_ref, li_ref, out_v, out_i, out_p,
             run_v, run_i, run_p) = refs
    else:
        if do_rerank:
            (q_ref, tiles_ref, li_ref, lists_hbm,
             out_v, out_i, out_p, run_v, run_i, run_p,
             cand_smem, rblk, rsem, ssem) = refs
        else:
            (q_ref, tiles_ref, li_ref, out_v, out_i, out_p,
             run_v, run_i, run_p) = refs

    bi = pl.program_id(0)
    j = pl.program_id(1)
    npr = nprobe * nsub

    @pl.when(j == 0)
    def _init():
        run_v[...] = jnp.full_like(run_v, -jnp.inf)
        run_i[...] = jnp.full_like(run_i, -1)
        run_p[...] = jnp.full_like(run_p, PAD_POS)

    @pl.when(j < npr)
    def _scan():
        tile = tiles_ref[0]                               # (blk_l, d|m)
        lid = li_ref[0]                                   # (1, blk_l)
        # shard ownership mask (sharded locals): foreign lists → -1,
        # matching ShardedIVFScan's where(own, li, -1)
        lid_m = jnp.where(own_ref[bi, j // nsub] > 0, lid, -1)
        if family == "pq":
            s = adc_score_tile(tbl_ref[0], tile.astype(jnp.int32),
                               precision)[None]
        else:
            s = score_tile(q_ref[0], tile, precision)     # (1, blk_l)
        pos = j * blk_l + _iota2((1, blk_l), 1)
        valid = lid_m >= 0
        vals = jnp.where(valid, s, -jnp.inf)
        posm = jnp.where(valid, pos, PAD_POS)
        bv, bi_, bp_ = sorting.block_topk_desc_tie(vals, lid_m, posm,
                                                   r_pad)
        mv, mi, mp = sorting.merge_topk_desc_tie(
            run_v[...], run_i[...], run_p[...], bv, bi_, bp_)
        run_v[...] = mv
        run_i[...] = mi
        run_p[...] = mp

    @pl.when(j == npr)
    def _finalize():
        if not do_rerank:
            out_v[0] = run_v[...]
            out_i[0] = run_i[...]
            out_p[0] = run_p[...]
        else:
            key_src = run_i if family == "pq" else run_p
            cp = pltpu.make_async_copy(key_src, cand_smem, ssem)
            cp.start()
            cp.wait()

            def src_of(i):
                if family == "pq":
                    return corpus_hbm, jnp.maximum(cand_smem[0, i], 0)
                cpos = cand_smem[0, i]
                probe_i = jnp.minimum(cpos // lpad, nprobe - 1)
                off = jax.lax.rem(cpos, lpad)
                return lists_hbm.at[sel_ref[bi, probe_i]], off

            ex = _gather_rerank(src_of, q_ref[0], rblk, rsem,
                                r_pad)                    # (1, r_pad)
            rank = _iota2((1, r_pad), 1)
            valid = (run_i[...] >= 0) & (rank < r)
            vals = jnp.where(valid, ex, -jnp.inf)
            bv, bi_, bp_ = sorting.block_topk_desc_tie(
                vals, run_i[...], rank, kp)
            out_v[0] = bv
            out_i[0] = bi_
            # after re-rank the tie-break key is the candidate's ADC
            # rank, not a flat scan position (single-device use only)
            out_p[0] = bp_


def _scan_call(kern, *, family, do_rerank, b, d, m, n_codes, blk_l,
               nsub, npr, r_pad, w, grid, interpret):
    def lv_map(bi, j, sel_ref, own_ref):
        jj = jnp.minimum(j, npr - 1)
        return (sel_ref[bi, jj // nsub], jj % nsub, 0)

    def li_map(bi, j, sel_ref, own_ref):
        jj = jnp.minimum(j, npr - 1)
        return (sel_ref[bi, jj // nsub], 0, jj % nsub)

    # per-row operands travel as (rows, 1, n) with (1, 1, n) blocks:
    # Mosaic tiles the last two block dims by (8, 128) unless a block
    # spans the whole array dimension, which a (1, n) row block of a
    # (rows, n) array does not
    def row_map(bi, j, sel_ref, own_ref):
        return (bi, 0, 0)

    in_specs = []
    if family == "pq":
        in_specs.append(pl.BlockSpec(
            (1, m, n_codes), lambda bi, j, s, o: (bi, 0, 0)))
        if do_rerank:
            in_specs.append(pl.BlockSpec((1, 1, d), row_map))
        in_specs.append(pl.BlockSpec((1, blk_l, m), lv_map))
        in_specs.append(pl.BlockSpec((1, 1, blk_l), li_map))
        if do_rerank:
            in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    else:
        in_specs.append(pl.BlockSpec((1, 1, d), row_map))
        in_specs.append(pl.BlockSpec((1, blk_l, d), lv_map))
        in_specs.append(pl.BlockSpec((1, 1, blk_l), li_map))
        if do_rerank:
            in_specs.append(pl.BlockSpec(memory_space=pl.ANY))

    scratch = [
        pltpu.VMEM((1, r_pad), jnp.float32),
        pltpu.VMEM((1, r_pad), jnp.int32),
        pltpu.VMEM((1, r_pad), jnp.int32),
    ]
    if do_rerank:
        scratch.append(pltpu.SMEM((1, r_pad), jnp.int32))
        scratch.append(pltpu.VMEM((r_pad, ROW_TILE, d), jnp.float32))
        scratch.append(pltpu.SemaphoreType.DMA)
        scratch.append(pltpu.SemaphoreType.DMA)

    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, w), row_map),
                pl.BlockSpec((1, 1, w), row_map),
                pl.BlockSpec((1, 1, w), row_map),
            ],
            scratch_shapes=scratch,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, w), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, w), jnp.int32),
            jax.ShapeDtypeStruct((b, 1, w), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=(
    "k", "r", "precision", "blk_l", "rerank", "interpret"))
def fused_scan(queries: jax.Array, list_vecs: jax.Array,
               list_ids: jax.Array, sel: jax.Array, own: jax.Array, *,
               k: int, r: int, precision: str = "f32",
               blk_l: int = 2048, rerank: bool = False,
               interpret: bool = False
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused IVF scan (+ optional in-kernel re-rank), prefetched sel.

    own (B, nprobe) int32: 1 where this shard owns the probed list
    (ones for single-device).  Without re-rank returns the top r_pad
    candidates with flat *padded* scan positions — after the ops
    wrapper's pos conversion these are tie-break compatible with
    ``distributed_topk_ordered``; with re-rank (quantised precision)
    returns the exact-scored top kp.
    """
    b, d = queries.shape
    p, lpad, _ = list_vecs.shape
    nprobe = sel.shape[1]
    assert lpad % blk_l == 0, (lpad, blk_l)
    nsub = lpad // blk_l
    npr = nprobe * nsub
    kp = _next_pow2(k)
    r_pad = _next_pow2(r)
    assert kp <= r_pad <= blk_l, (kp, r_pad, blk_l)
    w = kp if rerank else r_pad

    kern = functools.partial(
        _scan_kernel, family="ivf", do_rerank=rerank,
        precision=precision, nprobe=nprobe, nsub=nsub, blk_l=blk_l,
        lpad=lpad, r=r, r_pad=r_pad, kp=kp)
    call = _scan_call(
        kern, family="ivf", do_rerank=rerank, b=b, d=d, m=0, n_codes=0,
        blk_l=blk_l, nsub=nsub, npr=npr, r_pad=r_pad, w=w,
        grid=(b, npr + 1), interpret=interpret)
    args = (sel, own, queries[:, None], list_vecs, list_ids[:, None])
    if rerank:
        args = args + (list_vecs,)
    return tuple(o[:, 0] for o in call(*args))


@functools.partial(jax.jit, static_argnames=(
    "k", "r", "precision", "blk_l", "rerank", "interpret"))
def fused_scan_pq(tables: jax.Array, queries: jax.Array,
                  list_codes: jax.Array, list_ids: jax.Array,
                  sel: jax.Array, own: jax.Array, corpus: jax.Array, *,
                  k: int, r: int, precision: str = "f32",
                  blk_l: int = 4096, rerank: bool = True,
                  interpret: bool = False
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused PQ ADC scan (+ optional in-kernel exact re-rank).

    With ``rerank`` the ADC pass and the float32 re-rank collapse into
    one dispatch (single-device turns); without, returns the ADC top
    r_pad with scan positions for the sharded owner-computes merge.
    """
    b, m, n_codes = tables.shape
    p, lpad, _ = list_codes.shape
    d = queries.shape[1]
    assert not rerank or corpus.shape[0] % ROW_TILE == 0, corpus.shape
    nprobe = sel.shape[1]
    assert lpad % blk_l == 0, (lpad, blk_l)
    nsub = lpad // blk_l
    npr = nprobe * nsub
    kp = _next_pow2(k)
    r_pad = _next_pow2(r)
    assert kp <= r_pad <= blk_l, (kp, r_pad, blk_l)
    w = kp if rerank else r_pad

    kern = functools.partial(
        _scan_kernel, family="pq", do_rerank=rerank,
        precision=precision, nprobe=nprobe, nsub=nsub, blk_l=blk_l,
        lpad=lpad, r=r, r_pad=r_pad, kp=kp)
    call = _scan_call(
        kern, family="pq", do_rerank=rerank, b=b, d=d, m=m,
        n_codes=n_codes, blk_l=blk_l, nsub=nsub, npr=npr, r_pad=r_pad,
        w=w, grid=(b, npr + 1), interpret=interpret)
    li = list_ids[:, None]
    if rerank:
        outs = call(sel, own, tables, queries[:, None], list_codes, li,
                    corpus)
    else:
        outs = call(sel, own, tables, list_codes, li)
    return tuple(o[:, 0] for o in outs)
