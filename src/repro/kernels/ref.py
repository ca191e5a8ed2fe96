"""Pure-jnp reference oracles for every Pallas kernel in this package.

These are the ground truth the kernels are validated against
(``tests/test_kernels_*.py`` sweeps shapes/dtypes with
``np.testing.assert_allclose``), and the CPU execution path used whenever
the TPU kernels are unavailable (``ops.py`` dispatch).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import fused_turn as _ft


# ---------------------------------------------------------------------------
# centroid_topk — fused (B,d)x(d,p) matmul + top-k   [TopLoc hot spot 1]
# ---------------------------------------------------------------------------

def centroid_topk(queries: jax.Array, centroids: jax.Array, k: int
                  ) -> Tuple[jax.Array, jax.Array]:
    """Top-k centroids by dot product. queries (B,d), centroids (p,d).

    Returns (values (B,k) f32, ids (B,k) int32), sorted descending.
    """
    scores = jnp.einsum("bd,pd->bp", queries.astype(jnp.float32),
                        centroids.astype(jnp.float32))
    v, i = jax.lax.top_k(scores, k)
    return v, i.astype(jnp.int32)


# ---------------------------------------------------------------------------
# ivf_scan — fused posting-list gather + dot + masked top-k  [hot spot 2]
# ---------------------------------------------------------------------------

def ivf_scan(query: jax.Array, list_vecs: jax.Array, list_ids: jax.Array,
             sel: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """Scan the selected posting lists for one query.

    query (d,); list_vecs (p, Lmax, d); list_ids (p, Lmax) (-1 pad);
    sel (np,) int32 — selected partitions.
    Returns (values (k,), doc_ids (k,)) sorted descending.
    """
    lv = list_vecs[sel]                             # (np, Lmax, d)
    li = list_ids[sel]                              # (np, Lmax)
    scores = jnp.einsum("nld,d->nl", lv.astype(jnp.float32),
                        query.astype(jnp.float32))
    scores = jnp.where(li >= 0, scores, -jnp.inf)
    flat_v, flat_i = scores.reshape(-1), li.reshape(-1)
    v, pos = jax.lax.top_k(flat_v, k)
    return v, flat_i[pos].astype(jnp.int32)


def ivf_scan_batch(queries: jax.Array, list_vecs: jax.Array,
                   list_ids: jax.Array, sel: jax.Array, k: int
                   ) -> Tuple[jax.Array, jax.Array]:
    """vmap of ivf_scan over a query batch; sel (B, np)."""
    return jax.vmap(lambda q, s: ivf_scan(q, list_vecs, list_ids, s, k)
                    )(queries, sel)


# ---------------------------------------------------------------------------
# pq_adc_scan — PQ asymmetric-distance scan of selected posting lists
# ---------------------------------------------------------------------------

def pq_adc_scan(table: jax.Array, list_codes: jax.Array,
                list_ids: jax.Array, sel: jax.Array, k: int
                ) -> Tuple[jax.Array, jax.Array]:
    """ADC scan of the selected PQ-compressed posting lists (one query).

    table (m, n_codes) f32 — the query's ADC lookup table
    (``pq.adc_table``); list_codes (p, Lmax, m) uint8; list_ids
    (p, Lmax) int32 (-1 pad); sel (np,) int32.
    Returns (values (k,), doc_ids (k,)) sorted descending by ADC score.
    """
    codes = list_codes[sel].astype(jnp.int32)       # (np, Lmax, m)
    ids = list_ids[sel]                             # (np, Lmax)
    npb, lmax, m = codes.shape
    # gather along the code axis of the LUT: (m, np·Lmax) partial sums,
    # reduced over the m subquantizers — elementwise per doc, so the
    # reduction order is independent of any batching above
    flat = codes.reshape(npb * lmax, m)
    gathered = jnp.take_along_axis(table, flat.T, axis=1)   # (m, np·Lmax)
    scores = jnp.sum(gathered, axis=0)
    scores = jnp.where(ids.reshape(-1) >= 0, scores, -jnp.inf)
    v, pos = jax.lax.top_k(scores, k)
    return v, ids.reshape(-1)[pos].astype(jnp.int32)


def pq_adc_scan_batch(tables: jax.Array, list_codes: jax.Array,
                      list_ids: jax.Array, sel: jax.Array, k: int
                      ) -> Tuple[jax.Array, jax.Array]:
    """vmap of pq_adc_scan over a query batch; tables (B, m, n_codes),
    sel (B, np)."""
    return jax.vmap(lambda t, s: pq_adc_scan(t, list_codes, list_ids, s, k)
                    )(tables, sel)


# ---------------------------------------------------------------------------
# fused_turn / fused_scan — single-dispatch TopLoc turn oracles
# ---------------------------------------------------------------------------
#
# The f32 paths compose the exact 3-dispatch CPU formulations
# (einsum → masked top_k → gather → multiply-reduce re-rank), so the
# ops-wrapper "ref" mode is bit-identical to the unfused engines by
# construction.  The bf16/int8 paths emulate the kernel's *per-tile*
# quantisation by reshaping the padded operands into the same
# (blk_p / blk_l) tiles and scoring them with the very helpers the
# kernel runs (``fused_turn.score_tile`` / ``adc_score_tile``) —
# integer dots are exact, so interpret-vs-ref stays deterministic for
# int8 too.


def _fused_stage1_scores(queries: jax.Array, cents_pad: jax.Array,
                         p: int, precision: str, blk_p: int) -> jax.Array:
    """Centroid scores (B, p) under the fused precision contract."""
    if precision == "f32":
        c = cents_pad[:p]
        return jnp.einsum("bpd,bd->bp",
                          jnp.broadcast_to(c, (queries.shape[0],) + c.shape),
                          queries, precision=jax.lax.Precision.HIGHEST)
    nc = cents_pad.shape[0] // blk_p
    tiles = cents_pad.reshape(nc, blk_p, -1)
    s = jnp.concatenate(
        [_ft.score_tile(queries, tiles[t], precision) for t in range(nc)],
        axis=1)
    return s[:, :p]


def _fused_stage2_scores(queries: jax.Array, lv_pad: jax.Array,
                         sel: jax.Array, precision: str, blk_l: int,
                         lmax: int) -> jax.Array:
    """Probed-list scores (B, np, lmax) under the precision contract."""
    if precision == "f32":
        lv = lv_pad[:, :lmax][sel]
        return jnp.einsum("bd,bnld->bnl", queries, lv,
                          precision=jax.lax.Precision.HIGHEST)
    b = queries.shape[0]
    npb = sel.shape[1]
    lpad, d = lv_pad.shape[1], lv_pad.shape[2]
    nsub = lpad // blk_l
    g = lv_pad[sel].reshape(b, npb * nsub, blk_l, d)

    def one(qrow, tiles):
        return jnp.concatenate(
            [_ft.score_tile(qrow[None], tiles[t], precision)[0]
             for t in range(npb * nsub)])

    s = jax.vmap(one)(queries, g).reshape(b, npb, lpad)
    return s[:, :, :lmax]


def _fused_adc_candidates(tables: jax.Array, codes_pad: jax.Array,
                          ids_pad: jax.Array, sel: jax.Array, r: int,
                          precision: str, lmax: int
                          ) -> Tuple[jax.Array, jax.Array]:
    """ADC top-r candidates under the precision contract (B, r)."""
    if precision == "f32":
        return pq_adc_scan_batch(tables, codes_pad[:, :lmax],
                                 ids_pad[:, :lmax], sel, r)

    def one(tbl, s):
        codes = codes_pad[:, :lmax][s].astype(jnp.int32)  # (np, lmax, m)
        ids = ids_pad[:, :lmax][s]
        flat = codes.reshape(-1, codes.shape[-1])
        if precision == "int8":
            ti, st = _ft.quantize_sym(tbl, axes=(0, 1))
            g = jnp.take_along_axis(ti.astype(jnp.int32), flat.T, axis=1)
            sc = jnp.sum(g, axis=0).astype(jnp.float32) / st[0, 0]
        else:
            g = jnp.take_along_axis(tbl.astype(jnp.bfloat16), flat.T,
                                    axis=1)
            sc = jnp.sum(g.astype(jnp.float32), axis=0)
        sc = jnp.where(ids.reshape(-1) >= 0, sc, -jnp.inf)
        v, pos = jax.lax.top_k(sc, r)
        return v, ids.reshape(-1)[pos].astype(jnp.int32)

    return jax.vmap(one)(tables, sel)


def fused_turn_ivf(queries: jax.Array, cents_pad: jax.Array,
                   lv_pad: jax.Array, li_pad: jax.Array, *, p: int,
                   lmax: int, nprobe: int, k: int, r: int,
                   precision: str, blk_p: int, blk_l: int
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Oracle for the single-dispatch IVF turn.

    Operands are the kernel's padded tensors; returns unpadded
    (values (B, k), ids (B, k), sel (B, nprobe)).
    """
    b = queries.shape[0]
    cs = _fused_stage1_scores(queries, cents_pad, p, precision, blk_p)
    _, sel = jax.lax.top_k(cs, nprobe)
    sel = sel.astype(jnp.int32)
    li = li_pad[:, :lmax][sel]
    sc = _fused_stage2_scores(queries, lv_pad, sel, precision, blk_l, lmax)
    flat_v = jnp.where(li >= 0, sc, -jnp.inf).reshape(b, -1)
    flat_i = li.reshape(b, -1)
    if precision == "f32":
        v, pos = jax.lax.top_k(flat_v, k)
        return v, jnp.take_along_axis(flat_i, pos, -1).astype(jnp.int32), sel
    cv, pos = jax.lax.top_k(flat_v, r)
    cid = jnp.take_along_axis(flat_i, pos, -1)
    rows = lv_pad[:, :lmax][sel].reshape(b, -1, lv_pad.shape[-1])
    rows = jnp.take_along_axis(rows, pos[..., None], axis=1)
    exact = jnp.sum(rows.astype(jnp.float32) * queries[:, None, :], -1)
    exact = jnp.where(cid >= 0, exact, -jnp.inf)
    v, rpos = jax.lax.top_k(exact, k)
    return v, jnp.take_along_axis(cid, rpos, -1).astype(jnp.int32), sel


def fused_turn_pq(queries: jax.Array, cents_pad: jax.Array,
                  tables: jax.Array, codes_pad: jax.Array,
                  ids_pad: jax.Array, corpus: jax.Array, *, p: int,
                  lmax: int, nprobe: int, k: int, r: int,
                  precision: str, blk_p: int
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Oracle for the single-dispatch IVF-PQ turn (ADC + exact re-rank)."""
    cs = _fused_stage1_scores(queries, cents_pad, p, precision, blk_p)
    _, sel = jax.lax.top_k(cs, nprobe)
    sel = sel.astype(jnp.int32)
    cand_v, cand_ids = _fused_adc_candidates(tables, codes_pad, ids_pad,
                                             sel, r, precision, lmax)
    safe = jnp.maximum(cand_ids, 0)
    exact = jnp.sum(corpus[safe] * queries[:, None, :], axis=-1)
    exact = jnp.where(cand_ids >= 0, exact, -jnp.inf)
    top_v, pos = jax.lax.top_k(exact, k)
    top_i = jnp.take_along_axis(cand_ids, pos, axis=-1)
    return top_v, top_i.astype(jnp.int32), sel


def fused_scan_ivf(queries: jax.Array, lv_pad: jax.Array,
                   li_pad: jax.Array, sel: jax.Array, own: jax.Array, *,
                   lmax: int, k: int, r: int, precision: str,
                   blk_l: int, rerank: bool
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Oracle for the fused IVF scan with prefetched selection.

    Returns (values (B, k), ids (B, k), pos (B, k)) — pos is the
    unpadded flat scan position for the no-re-rank path (tie-break key
    for the distributed merge; undefined where values are -inf) and the
    candidate rank after re-rank.
    """
    b = queries.shape[0]
    li = li_pad[:, :lmax][sel]
    li = jnp.where(own[..., None] > 0, li, -1)
    sc = _fused_stage2_scores(queries, lv_pad, sel, precision, blk_l, lmax)
    flat_v = jnp.where(li >= 0, sc, -jnp.inf).reshape(b, -1)
    flat_i = li.reshape(b, -1)
    if not rerank:
        v, pos = jax.lax.top_k(flat_v, k)
        return (v, jnp.take_along_axis(flat_i, pos, -1).astype(jnp.int32),
                pos.astype(jnp.int32))
    cv, pos = jax.lax.top_k(flat_v, r)
    cid = jnp.take_along_axis(flat_i, pos, -1)
    rows = lv_pad[:, :lmax][sel].reshape(b, -1, lv_pad.shape[-1])
    rows = jnp.take_along_axis(rows, pos[..., None], axis=1)
    exact = jnp.sum(rows.astype(jnp.float32) * queries[:, None, :], -1)
    exact = jnp.where(cid >= 0, exact, -jnp.inf)
    v, rpos = jax.lax.top_k(exact, k)
    return (v, jnp.take_along_axis(cid, rpos, -1).astype(jnp.int32),
            rpos.astype(jnp.int32))


def fused_scan_pq(tables: jax.Array, queries: jax.Array,
                  codes_pad: jax.Array, ids_pad: jax.Array,
                  sel: jax.Array, own: jax.Array, corpus: jax.Array, *,
                  lmax: int, k: int, r: int, precision: str,
                  rerank: bool
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Oracle for the fused PQ scan (+ optional exact re-rank).

    Without re-rank returns the ADC top-r (values, ids, flat pos) for
    the sharded owner-computes merge; with re-rank the exact top-k
    (pos = candidate rank).
    """
    b = tables.shape[0]
    li = ids_pad[:, :lmax][sel]
    li = jnp.where(own[..., None] > 0, li, -1)
    codes = codes_pad[:, :lmax][sel].astype(jnp.int32)
    m = codes.shape[-1]

    def one(tbl, c, ids):
        flat = c.reshape(-1, m)
        if precision == "int8":
            ti, st = _ft.quantize_sym(tbl, axes=(0, 1))
            g = jnp.take_along_axis(ti.astype(jnp.int32), flat.T, axis=1)
            sc = jnp.sum(g, axis=0).astype(jnp.float32) / st[0, 0]
        elif precision == "bf16":
            g = jnp.take_along_axis(tbl.astype(jnp.bfloat16), flat.T,
                                    axis=1)
            sc = jnp.sum(g.astype(jnp.float32), axis=0)
        else:
            g = jnp.take_along_axis(tbl, flat.T, axis=1)
            sc = jnp.sum(g, axis=0)
        sc = jnp.where(ids.reshape(-1) >= 0, sc, -jnp.inf)
        v, pos = jax.lax.top_k(sc, r)
        return v, ids.reshape(-1)[pos].astype(jnp.int32), pos

    cv, cid, cpos = jax.vmap(one)(tables, codes, li)
    if not rerank:
        return cv, cid, cpos.astype(jnp.int32)
    safe = jnp.maximum(cid, 0)
    exact = jnp.sum(corpus[safe] * queries[:, None, :], axis=-1)
    exact = jnp.where(cid >= 0, exact, -jnp.inf)
    v, rpos = jax.lax.top_k(exact, k)
    return (v, jnp.take_along_axis(cid, rpos, -1).astype(jnp.int32),
            rpos.astype(jnp.int32))


# ---------------------------------------------------------------------------
# flash_attention — causal/full softmax attention with GQA
# ---------------------------------------------------------------------------

def mha_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True,
                  logit_soft_cap: Optional[float] = None) -> jax.Array:
    """Reference attention. q (B,H,S,D), k/v (B,Hkv,Skv,D); Hkv divides H.

    f32 softmax accumulation regardless of input dtype (matches kernel).
    Value head dim may differ from qk head dim (MLA).
    """
    b, h, s, d = q.shape
    hkv = k.shape[1]
    dv = v.shape[-1]
    group = h // hkv
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    qg = qf.reshape(b, hkv, group, s, d)
    logits = jnp.einsum("bhgsd,bhtd->bhgst", qg, kf) / jnp.sqrt(d).astype(jnp.float32)
    if logit_soft_cap is not None:
        logits = logit_soft_cap * jnp.tanh(logits / logit_soft_cap)
    if causal:
        skv = k.shape[2]
        # queries occupy the last `s` positions of the kv timeline
        qpos = jnp.arange(s) + (skv - s)
        kpos = jnp.arange(skv)
        mask = qpos[:, None] >= kpos[None, :]
        logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgst,bhtd->bhgsd", probs, vf)
    return out.reshape(b, h, s, dv).astype(q.dtype)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     cache_len: Optional[jax.Array] = None) -> jax.Array:
    """Single-token decode attention. q (B,H,D), k/v (B,Hkv,S,D).

    ``cache_len`` (B,) masks positions >= cache_len (ragged cache fill).
    """
    b, h, d = q.shape
    hkv, s = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    group = h // hkv
    qg = q.reshape(b, hkv, group, d).astype(jnp.float32)
    logits = jnp.einsum("bhgd,bhtd->bhgt", qg, k.astype(jnp.float32))
    logits = logits / jnp.sqrt(d).astype(jnp.float32)
    if cache_len is not None:
        mask = jnp.arange(s)[None] < cache_len[:, None]      # (B, S)
        logits = jnp.where(mask[:, None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgt,bhtd->bhgd", probs, v.astype(jnp.float32))
    return out.reshape(b, h, dv).astype(q.dtype)


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, blk_kv: int = 1024) -> jax.Array:
    """Flash-style chunked attention in pure jnp (lax.scan over KV tiles,
    online softmax).  Numerically equivalent to ``mha_attention`` but
    never materialises the (S, Skv) score matrix — this is the path the
    dry-run lowers (so the compiled memory analysis reflects the
    streaming TPU kernel, not an S² artefact of the plain reference) and
    the grad path of ``ops.flash_attention`` (scan of jnp ops —
    differentiable as-is, recompute-friendly).
    """
    b, h, s, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    group = h // hkv
    nblk = -(-skv // blk_kv)
    pad = nblk * blk_kv - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    scale = 1.0 / (d ** 0.5)
    qg = (q.reshape(b, hkv, group, s, d).astype(jnp.float32) * scale)
    kb = k.reshape(b, hkv, nblk, blk_kv, d).astype(jnp.float32)
    vb = v.reshape(b, hkv, nblk, blk_kv, dv).astype(jnp.float32)
    kb = jnp.moveaxis(kb, 2, 0)              # (nblk, B, Hkv, blk, d)
    vb = jnp.moveaxis(vb, 2, 0)
    qpos = jnp.arange(s) + (skv - s)

    @jax.checkpoint
    def body(carry, xs):
        # rematerialised: without checkpoint, differentiating the scan
        # saves every chunk's (S, blk) score matrix — the S x Skv memory
        # this formulation exists to avoid. Recompute-per-chunk is the
        # flash-attention backward strategy.
        m, l, acc, j = carry[0], carry[1], carry[2], carry[3]
        kj, vj = xs
        sco = jnp.einsum("bhgsd,bhtd->bhgst", qg, kj)   # (B,Hkv,g,S,blk)
        kpos = j * blk_kv + jnp.arange(blk_kv)
        valid = (kpos[None, :] < skv) if pad else jnp.ones(
            (1, blk_kv), bool)
        if causal:
            valid = valid & (qpos[:, None] >= kpos[None, :])
        sco = jnp.where(valid[None, None, None], sco, -jnp.inf)
        m_cur = jnp.max(sco, axis=-1)
        m_new = jnp.maximum(m, m_cur)
        # guard fully-masked rows (m_new == -inf)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(sco - m_safe[..., None])
        p = jnp.where(valid[None, None, None], p, 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = alpha * l + jnp.sum(p, -1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhgst,bhtd->bhgsd", p, vj)
        return (m_new, l_new, acc_new, j + 1), None

    m0 = jnp.full((b, hkv, group, s), -jnp.inf)
    l0 = jnp.zeros((b, hkv, group, s))
    a0 = jnp.zeros((b, hkv, group, s, dv))
    (m, l, acc, _), _ = jax.lax.scan(
        body, (m0, l0, a0, jnp.asarray(0, jnp.int32)), (kb, vb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, h, s, dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# embedding_bag — fused gather + segment-sum   [recsys hot path]
# ---------------------------------------------------------------------------

def embedding_bag(table: jax.Array, ids: jax.Array,
                  weights: Optional[jax.Array] = None,
                  mode: str = "sum") -> jax.Array:
    """EmbeddingBag over fixed-width bags. table (V,d), ids (B,L) int32
    (-1 = pad). Returns (B,d). mode: 'sum' | 'mean'.

    JAX has no native EmbeddingBag — this gather+mask+reduce IS the
    substrate (see kernel_taxonomy §RecSys), and the Pallas kernel fuses
    the row gather with the reduction so rows stream HBM→VMEM once.
    """
    mask = (ids >= 0)
    safe = jnp.maximum(ids, 0)
    rows = table[safe]                               # (B, L, d)
    w = mask.astype(table.dtype)
    if weights is not None:
        w = w * weights.astype(table.dtype)
    out = jnp.einsum("bld,bl->bd", rows.astype(jnp.float32),
                     w.astype(jnp.float32))
    if mode == "mean":
        denom = jnp.maximum(jnp.sum(w.astype(jnp.float32), -1, keepdims=True), 1.0)
        out = out / denom
    return out.astype(table.dtype)
