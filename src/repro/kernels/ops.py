"""jit'd dispatch wrappers for every kernel in this package.

Each public op has three execution paths, selected by ``mode``:

  * ``"ref"``       — pure-jnp oracle (``ref.py``): the CPU-container
                      default and the path lowered in the dry-run (Pallas
                      TPU kernels do not lower on the CPU backend).
  * ``"kernel"``    — the Pallas TPU kernel (real hardware).
  * ``"interpret"`` — the Pallas kernel body executed in Python
                      (correctness validation on CPU; used by tests).

``default_mode()`` picks ``kernel`` on TPU and ``ref`` elsewhere, so
call-sites never branch by hand.  Wrappers also own the padding
contracts (power-of-two k, block-aligned lengths) so kernels stay
assert-clean and callers stay shape-ignorant.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels import tiling
from repro.kernels import centroid_topk as _ck
from repro.kernels import ivf_scan as _iv
from repro.kernels import pq_adc as _pq
from repro.kernels import fused_turn as _ft
from repro.kernels import flash_attention as _fa
from repro.kernels import embedding_bag as _eb

# tile-split policy is shared with the kernel_budget analysis pass —
# see kernels/tiling.py
_next_pow2 = tiling.next_pow2
_pad_axis = tiling.pad_axis


def default_mode() -> str:
    plat = jax.default_backend()
    return "kernel" if plat == "tpu" else "ref"


# ---------------------------------------------------------------------------
# centroid_topk
# ---------------------------------------------------------------------------

def centroid_topk(queries: jax.Array, centroids: jax.Array, k: int, *,
                  mode: Optional[str] = None, blk_p: int = 512
                  ) -> Tuple[jax.Array, jax.Array]:
    """Top-k centroid ids/scores for a query batch. See kernel docstring."""
    mode = mode or default_mode()
    if mode == "ref":
        return ref.centroid_topk(queries, centroids, k)
    p = centroids.shape[0]
    kp = _next_pow2(k)
    blk, p_pad = tiling.centroid_tile(p, kp, blk_p=blk_p)
    c = _pad_axis(centroids, 0, p_pad, 0.0)
    # guard: padded centroids must never win — push them to -inf via a
    # sentinel row of -inf scores (zero vectors tie at 0 for zero queries,
    # so mask by id instead inside merge: ids >= p are dropped post-hoc)
    v, i = _ck.centroid_topk(queries, c, kp, blk_p=blk,
                             interpret=(mode == "interpret"))
    v = jnp.where(i < p, v, -jnp.inf)
    v2, pos = jax.lax.top_k(v, k)
    return v2, jnp.take_along_axis(i, pos, axis=-1)


# ---------------------------------------------------------------------------
# ivf_scan
# ---------------------------------------------------------------------------

def ivf_scan(queries: jax.Array, list_vecs: jax.Array, list_ids: jax.Array,
             sel: jax.Array, k: int, *, mode: Optional[str] = None,
             max_tile: int = 2048) -> Tuple[jax.Array, jax.Array]:
    """Fused scan of the selected posting lists. sel (B, nprobe)."""
    mode = mode or default_mode()
    if mode == "ref":
        return ref.ivf_scan_batch(queries, list_vecs, list_ids, sel, k)
    p, lmax, d = list_vecs.shape
    kp = _next_pow2(k)
    blk_l, lpad = tiling.list_tile(lmax, d * 4, kp=kp, max_tile=max_tile)
    lv = _pad_axis(list_vecs, 1, lpad, 0.0)
    li = _pad_axis(list_ids, 1, lpad, -1)
    v, i = _iv.ivf_scan(queries, lv, li, sel, kp, blk_l=blk_l,
                        interpret=(mode == "interpret"))
    return v[:, :k], i[:, :k]


# ---------------------------------------------------------------------------
# pq_adc_scan
# ---------------------------------------------------------------------------

def pq_adc_scan(tables: jax.Array, list_codes: jax.Array,
                list_ids: jax.Array, sel: jax.Array, k: int, *,
                mode: Optional[str] = None, max_tile: int = 4096
                ) -> Tuple[jax.Array, jax.Array]:
    """Fused ADC scan of selected PQ posting lists. tables (B, m, codes),
    sel (B, nprobe).  Returns the ADC top-k candidates per query."""
    mode = mode or default_mode()
    if mode == "ref":
        return ref.pq_adc_scan_batch(tables, list_codes, list_ids, sel, k)
    p, lmax, m = list_codes.shape
    kp = _next_pow2(k)
    # uint8 code rows: the byte cap never binds before the row cap, so
    # this reduces to the historical max_tile policy (LUT sizing is the
    # (1, m, codes) table block, resident per query row)
    blk_l, lpad = tiling.list_tile(lmax, m, kp=kp, max_tile=max_tile)
    codes = _pad_axis(list_codes, 1, lpad, 0)
    li = _pad_axis(list_ids, 1, lpad, -1)
    v, i = _pq.pq_adc_scan(tables.astype(jnp.float32), codes, li, sel, kp,
                           blk_l=blk_l, interpret=(mode == "interpret"))
    return v[:, :k], i[:, :k]


# ---------------------------------------------------------------------------
# fused_turn / fused_scan — single-dispatch TopLoc turn
# ---------------------------------------------------------------------------


def _fused_depth(k: int, cap: int, *, over: int = 0, rerank: int = 0) -> int:
    """Exact candidate depth r: k·over (quantised IVF) or the PQ re-rank
    depth, clamped to the scannable candidate count and floored at k —
    the same clamp ``toploc._scan_lists_pq`` applies."""
    want = k * over if over else rerank
    return max(k, min(want, cap))


def fused_turn(queries: jax.Array, centroids: jax.Array,
               list_vecs: jax.Array, list_ids: jax.Array, *,
               nprobe: int, k: int, over: int = 2,
               precision: str = "f32", mode: Optional[str] = None,
               blk_p: int = 512, max_tile: int = 2048
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Whole IVF turn in one dispatch: centroid top-nprobe + list scan
    (+ float32 re-rank of the k·over survivors when quantised).

    Returns (values (B, k), ids (B, k), sel (B, nprobe)).  The f32 path
    is bit-identical to centroid_topk → ivf_scan; see the precision
    contract in ``kernels/fused_turn.py``.
    """
    mode = mode or default_mode()
    p, lmax, d = list_vecs.shape
    r = k if precision == "f32" else _fused_depth(k, nprobe * lmax,
                                                  over=over)
    np_pad = _next_pow2(nprobe)
    r_pad = _next_pow2(r)
    blk, p_pad = tiling.centroid_tile(p, np_pad, blk_p=blk_p)
    blk_l, lpad = tiling.list_tile(lmax, d * 4, kp=r_pad,
                                   max_tile=max_tile)
    c = _pad_axis(centroids, 0, p_pad, 0.0)
    lv = _pad_axis(list_vecs, 1, lpad, 0.0)
    li = _pad_axis(list_ids, 1, lpad, -1)
    if mode == "ref":
        return ref.fused_turn_ivf(queries, c, lv, li, p=p, lmax=lmax,
                                  nprobe=nprobe, k=k, r=r,
                                  precision=precision, blk_p=blk,
                                  blk_l=blk_l)
    v, i, s = _ft.fused_turn(queries, c, lv, li, nprobe=nprobe, k=k,
                             r=r, precision=precision, blk_p=blk,
                             blk_l=blk_l,
                             interpret=(mode == "interpret"))
    return v[:, :k], i[:, :k], s[:, :nprobe]


def fused_turn_pq(queries: jax.Array, centroids: jax.Array,
                  tables: jax.Array, list_codes: jax.Array,
                  list_ids: jax.Array, corpus: jax.Array, *,
                  nprobe: int, k: int, rerank: int,
                  precision: str = "f32", mode: Optional[str] = None,
                  blk_p: int = 512, max_tile: int = 4096
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Whole IVF-PQ turn in one dispatch: centroid top-nprobe + ADC scan
    + float32 exact re-rank of the top ``rerank`` candidates in-kernel.
    """
    mode = mode or default_mode()
    p, lmax, m = list_codes.shape
    r = _fused_depth(k, nprobe * lmax, rerank=rerank)
    np_pad = _next_pow2(nprobe)
    r_pad = _next_pow2(r)
    blk, p_pad = tiling.centroid_tile(p, np_pad, blk_p=blk_p)
    blk_l, lpad = tiling.list_tile(lmax, m, kp=r_pad, max_tile=max_tile)
    c = _pad_axis(centroids, 0, p_pad, 0.0)
    codes = _pad_axis(list_codes, 1, lpad, 0)
    li = _pad_axis(list_ids, 1, lpad, -1)
    if mode == "ref":
        return ref.fused_turn_pq(queries, c, tables, codes, li, corpus,
                                 p=p, lmax=lmax, nprobe=nprobe, k=k,
                                 r=r, precision=precision, blk_p=blk)
    # the kernel DMAs code rows itself, and Mosaic only DMAs whole
    # 128-lane rows: a per-call copy of the codes (their HBM tiles
    # already pad m to 128 lanes, so the copy is no larger than them)
    codes = _pad_axis(codes, 2, -(-m // tiling.LANE) * tiling.LANE, 0)
    corpus = _row_tiles(corpus)
    v, i, s = _ft.fused_turn_pq(queries, c, tables.astype(jnp.float32),
                                codes, li, corpus, nprobe=nprobe, k=k,
                                r=r, precision=precision, blk_p=blk,
                                blk_l=blk_l,
                                interpret=(mode == "interpret"))
    return v[:, :k], i[:, :k], s[:, :nprobe]


def _row_tiles(corpus: jax.Array) -> jax.Array:
    """Corpus rows padded to whole ``ROW_TILE`` tiles, the unit the
    in-kernel re-rank DMAs (an engine-placed index already is:
    ``backend.IVFPQBackend.place``; pad rows are never a candidate)."""
    n = corpus.shape[0]
    return _pad_axis(corpus, 0, -(-n // _ft.ROW_TILE) * _ft.ROW_TILE, 0.0)


def _convert_pos(pp: jax.Array, lpad: int, lmax: int) -> jax.Array:
    """Padded flat scan positions → reference (probe·lmax + off) numbering.

    The map is monotone, so tie-break order is preserved; PAD_POS lanes
    (value -inf) stay PAD_POS.
    """
    conv = (pp // lpad) * lmax + jax.lax.rem(pp, lpad)
    return jnp.where(pp == _ft.PAD_POS, _ft.PAD_POS, conv)


def fused_scan(queries: jax.Array, list_vecs: jax.Array,
               list_ids: jax.Array, sel: jax.Array, k: int, *,
               own: Optional[jax.Array] = None, over: int = 2,
               precision: str = "f32", mode: Optional[str] = None,
               max_tile: int = 2048
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused IVF list scan with a caller-supplied selection.

    Returns (values (B, k), ids (B, k), pos (B, k)); pos is the flat
    scan position (``distributed_topk_ordered`` tie-break key) for f32,
    and the candidate rank after the quantised paths' in-kernel
    re-rank (single-device use).  ``own`` masks lists this shard does
    not own (sharded locals).
    """
    mode = mode or default_mode()
    b = queries.shape[0]
    p, lmax, d = list_vecs.shape
    nprobe = sel.shape[1]
    rerank = precision != "f32"
    r = k if not rerank else _fused_depth(k, nprobe * lmax, over=over)
    r_pad = _next_pow2(r)
    blk_l, lpad = tiling.list_tile(lmax, d * 4, kp=r_pad,
                                   max_tile=max_tile)
    if own is None:
        own = jnp.ones((b, nprobe), jnp.int32)
    lv = _pad_axis(list_vecs, 1, lpad, 0.0)
    li = _pad_axis(list_ids, 1, lpad, -1)
    if mode == "ref":
        return ref.fused_scan_ivf(queries, lv, li, sel, own, lmax=lmax,
                                  k=k, r=r, precision=precision,
                                  blk_l=blk_l, rerank=rerank)
    v, i, pp = _ft.fused_scan(queries, lv, li, sel,
                              own.astype(jnp.int32), k=k, r=r,
                              precision=precision, blk_l=blk_l,
                              rerank=rerank,
                              interpret=(mode == "interpret"))
    v, i, pp = v[:, :k], i[:, :k], pp[:, :k]
    if not rerank:
        pp = _convert_pos(pp, lpad, lmax)
    return v, i, pp


def fused_scan_pq(tables: jax.Array, queries: jax.Array,
                  list_codes: jax.Array, list_ids: jax.Array,
                  sel: jax.Array, corpus: jax.Array, k: int, *,
                  rerank: int, own: Optional[jax.Array] = None,
                  precision: str = "f32", fuse_rerank: bool = True,
                  mode: Optional[str] = None, max_tile: int = 4096
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused PQ ADC scan with a caller-supplied selection.

    With ``fuse_rerank`` (single-device turns) the ADC pass and the
    float32 exact re-rank collapse into one dispatch → exact top-k.
    Without (sharded owner-computes locals) returns the ADC top-r with
    flat scan positions for the distributed merge.
    """
    mode = mode or default_mode()
    b = tables.shape[0]
    p, lmax, m = list_codes.shape
    nprobe = sel.shape[1]
    r = _fused_depth(k, nprobe * lmax, rerank=rerank)
    r_pad = _next_pow2(r)
    blk_l, lpad = tiling.list_tile(lmax, m, kp=r_pad, max_tile=max_tile)
    if own is None:
        own = jnp.ones((b, nprobe), jnp.int32)
    codes = _pad_axis(list_codes, 1, lpad, 0)
    li = _pad_axis(list_ids, 1, lpad, -1)
    if mode == "ref":
        return ref.fused_scan_pq(tables, queries, codes, li, sel, own,
                                 corpus, lmax=lmax, k=k, r=r,
                                 precision=precision,
                                 rerank=fuse_rerank)
    if fuse_rerank:
        corpus = _row_tiles(corpus)
    v, i, pp = _ft.fused_scan_pq(tables.astype(jnp.float32), queries,
                                 codes, li, sel, own.astype(jnp.int32),
                                 corpus, k=k, r=r, precision=precision,
                                 blk_l=blk_l, rerank=fuse_rerank,
                                 interpret=(mode == "interpret"))
    w = k if fuse_rerank else r
    v, i, pp = v[:, :w], i[:, :w], pp[:, :w]
    if not fuse_rerank:
        pp = _convert_pos(pp, lpad, lmax)
    return v, i, pp


# ---------------------------------------------------------------------------
# flash attention (custom_vjp: kernel fwd, reference-math bwd)
# ---------------------------------------------------------------------------

# threshold above which the jnp path switches to the chunked
# (flash-style) formulation — keeps the lowered graph free of (S, Skv)
# score tensors so dry-run memory reflects the streaming TPU kernel
_CHUNK_THRESHOLD = 2048


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fa_core(q, k, v, causal: bool, mode: str):
    if mode == "ref":
        return _ref_attention(q, k, v, causal)
    return _fa.flash_attention(q, k, v, causal=causal,
                               interpret=(mode == "interpret"))


def _ref_attention(q, k, v, causal):
    if q.shape[2] * k.shape[2] > _CHUNK_THRESHOLD ** 2:
        return ref.chunked_attention(q, k, v, causal=causal)
    return ref.mha_attention(q, k, v, causal=causal)


def _fa_fwd(q, k, v, causal, mode):
    return _fa_core(q, k, v, causal, mode), (q, k, v)


def _fa_bwd(causal, mode, res, g):
    q, k, v = res
    _, vjp = jax.vjp(lambda q_, k_, v_: _ref_attention(
        q_, k_, v_, causal), q, k, v)
    return vjp(g)


_fa_core.defvjp(_fa_fwd, _fa_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, mode: Optional[str] = None
                    ) -> jax.Array:
    """Differentiable attention: Pallas fwd on TPU, jnp-math bwd."""
    mode = mode or default_mode()
    if mode != "ref":
        s, skv = q.shape[2], k.shape[2]
        if s % 128 or skv % 128:   # padding contract
            mode = "ref"
    return _fa_core(q, k, v, causal, mode)


def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 cache_len: jax.Array, *, mode: Optional[str] = None
                 ) -> jax.Array:
    """Decode attention (no grad path — serving only)."""
    mode = mode or default_mode()
    if mode == "ref":
        return ref.decode_attention(q, k, v, cache_len)
    s = k.shape[2]
    blk = 512 if s % 512 == 0 else (128 if s % 128 == 0 else 0)
    if blk == 0:
        return ref.decode_attention(q, k, v, cache_len)
    return _fa.flash_decode(q, k, v, cache_len, blk_kv=blk,
                            interpret=(mode == "interpret"))


# ---------------------------------------------------------------------------
# embedding_bag (custom_vjp: kernel fwd, gather-scatter bwd)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _eb_core(table, ids, weights, mode: str):
    if mode == "ref":
        return ref.embedding_bag(table, ids, weights, mode="sum")
    return _eb.embedding_bag(table, ids, weights,
                             interpret=(mode == "interpret"))


def _eb_fwd(table, ids, weights, mode):
    return _eb_core(table, ids, weights, mode), (table, ids, weights)


def _eb_bwd(mode, res, g):
    table, ids, weights = res
    _, vjp = jax.vjp(lambda t, w: ref.embedding_bag(t, ids, w, mode="sum"),
                     table, weights if weights is not None else
                     jnp.ones(ids.shape, jnp.float32))
    dt, dw = vjp(g)
    return dt, None, (dw if weights is not None else None)


_eb_core.defvjp(_eb_fwd, _eb_bwd)


def embedding_bag(table: jax.Array, ids: jax.Array,
                  weights: Optional[jax.Array] = None,
                  agg: str = "sum", *, mode: Optional[str] = None
                  ) -> jax.Array:
    """EmbeddingBag: (V,d) table, (B,L) bags (-1 pad) → (B,d)."""
    mode = mode or default_mode()
    out = _eb_core(table, ids, weights, mode)
    if agg == "mean":
        w = (ids >= 0).astype(jnp.float32)
        if weights is not None:
            w = w * weights
        denom = jnp.maximum(w.sum(-1, keepdims=True), 1.0)
        out = (out.astype(jnp.float32) / denom).astype(table.dtype)
    return out
