"""Pallas TPU kernels: flash attention (prefill/train) and flash decode.

The LM architectures in the zoo (grok-1, deepseek-v2-lite, qwen, yi) are
attention-dominated at the assigned shapes (train_4k, prefill_32k,
decode_32k).  These kernels are the standard IO-aware formulation adapted
to TPU: KV tiles stream HBM→VMEM, the (m, l, acc) online-softmax state
lives in VMEM scratch, and the MXU sees (blk_q, d)x(d, blk_kv) /
(blk_q, blk_kv)x(blk_kv, d) matmuls.  GQA is handled in the index_map
(query-head → kv-head division) so KV tiles are fetched once per group,
not per head.

Backward pass: ``ops.flash_attention`` wraps this forward in a
``jax.custom_vjp`` whose backward runs the pure-jnp reference (exact same
math, recompute-based) — the honest CPU-container trade-off; a fused bwd
kernel is a listed future optimisation in EXPERIMENTS.md §Perf.

Grids:
  prefill: (B·H, nq, nkv)  — nkv sequential, causal tiles skipped.
  decode:  (B·Hkv, nkv)    — one query row per kv head group.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
               blk_q: int, blk_kv: int, nkv: int, causal: bool,
               q_offset: int, scale: float):
    """One (q-tile, kv-tile) step of online-softmax attention."""
    i = pl.program_id(1)          # q tile
    j = pl.program_id(2)          # kv tile (sequential)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_first = i * blk_q + q_offset              # absolute q positions
    kv_first = j * blk_kv
    q = q_ref[0].astype(jnp.float32) * scale    # (blk_q, d)
    k = k_ref[0].astype(jnp.float32)            # (blk_kv, d)
    v = v_ref[0].astype(jnp.float32)            # (blk_kv, d)

    def step():
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            qpos = q_first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = kv_first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)       # (blk_q, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                           # (blk_q, blk_kv)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, -1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    if causal:
        # skip fully-masked kv tiles (saves ~half the work on causal)
        @pl.when(kv_first <= q_first + blk_q - 1)
        def _run():
            step()
    else:
        step()

    @pl.when(j == nkv - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "blk_q", "blk_kv", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, blk_q: int = 128,
                    blk_kv: int = 128, interpret: bool = False) -> jax.Array:
    """q (B,H,S,D), k/v (B,Hkv,Skv,D) → (B,H,S,D).

    Padding contract (ops.py): S % blk_q == 0, Skv % blk_kv == 0.
    Causal convention: q occupies the *last* S positions of the Skv
    timeline (prefill-with-prefix / train are S == Skv).
    """
    b, h, s, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    dv = v.shape[-1]                   # may differ from d (MLA)
    group = h // hkv
    assert s % blk_q == 0 and skv % blk_kv == 0, (s, skv, blk_q, blk_kv)
    nq, nkv = s // blk_q, skv // blk_kv
    scale = 1.0 / (d ** 0.5)
    q_offset = skv - s

    qf = q.reshape(b * h, s, d)
    kern = functools.partial(_fa_kernel, blk_q=blk_q, blk_kv=blk_kv,
                             nkv=nkv, causal=causal, q_offset=q_offset,
                             scale=scale)
    out = pl.pallas_call(
        kern,
        grid=(b * h, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, blk_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, blk_kv, d),
                         lambda bh, i, j, g=group, hh=h: (
                             (bh // hh) * hkv + (bh % hh) // g, j, 0)),
            pl.BlockSpec((1, blk_kv, dv),
                         lambda bh, i, j, g=group, hh=h: (
                             (bh // hh) * hkv + (bh % hh) // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, dv), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, k.reshape(b * hkv, skv, d), v.reshape(b * hkv, skv, dv))
    return out.reshape(b, h, s, dv)


# ---------------------------------------------------------------------------
# decode (one new token against a long KV cache)
# ---------------------------------------------------------------------------

def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, blk_kv: int, nkv: int,
                   scale: float):
    bh = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale       # (group, d)
    k = k_ref[0].astype(jnp.float32)               # (blk_kv, d)
    v = v_ref[0].astype(jnp.float32)
    cache_len = len_ref[bh]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (group, blk_kv)
    kpos = j * blk_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kpos < cache_len, s, NEG_INF)

    m_prev, l_prev = m_scr[...], l_scr[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = alpha * l_prev + jnp.sum(p, -1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == nkv - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("blk_kv", "interpret"))
def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 cache_len: jax.Array, *, blk_kv: int = 512,
                 interpret: bool = False) -> jax.Array:
    """Decode attention. q (B,H,D), k/v (B,Hkv,S,D), cache_len (B,) int32.

    Returns (B,H,D). Padding contract: S % blk_kv == 0.
    """
    b, h, d = q.shape
    hkv, s = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    group = h // hkv
    assert s % blk_kv == 0
    nkv = s // blk_kv
    scale = 1.0 / (d ** 0.5)

    qg = q.reshape(b * hkv, group, d)
    # cache_len per (b·hkv) row
    len_rows = jnp.repeat(cache_len.astype(jnp.int32), hkv)

    kern = functools.partial(_decode_kernel, blk_kv=blk_kv, nkv=nkv,
                             scale=scale)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * hkv, nkv),
            in_specs=[
                pl.BlockSpec((1, group, d), lambda bh, j, len_ref: (bh, 0, 0)),
                pl.BlockSpec((1, blk_kv, d), lambda bh, j, len_ref: (bh, j, 0)),
                pl.BlockSpec((1, blk_kv, dv), lambda bh, j, len_ref: (bh, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, group, dv),
                                   lambda bh, j, len_ref: (bh, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b * hkv, group, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(len_rows, qg, k.reshape(b * hkv, s, d), v.reshape(b * hkv, s, dv))
    return out.reshape(b, h, dv)
