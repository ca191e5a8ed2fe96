"""Bitonic sorting networks for exact in-kernel top-k.

Mosaic (Pallas TPU) does not lower ``jax.lax.top_k`` / ``sort`` inside
kernels, so the streaming top-k kernels keep their running (k,) register
tile sorted with compare-exchange networks built from pure vector ops
(roll + where + min/max) — every step is lane-parallel on the VPU and
static-shaped.  Costs: full sort of n elements = log²n/2 stages; merge of
two sorted k-tiles = log(2k) stages.

These helpers are plain jnp functions: they run identically inside a
Pallas kernel body, in interpret mode, and as host-side references (the
tests cross-check them against ``jnp.sort``).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def _is_pow2(n: int) -> bool:
    return n > 0 and (n & (n - 1)) == 0


def _compare_exchange(vals: jax.Array, ids: jax.Array, dist: int,
                      keep_max: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One compare-exchange stage at distance ``dist`` on the last axis.

    ``keep_max`` (bool, same shape): True where the position should keep
    the pairwise max, False where it keeps the min.
    """
    iota = jax.lax.broadcasted_iota(jnp.int32, vals.shape, vals.ndim - 1)
    is_lo = (iota % (2 * dist)) < dist
    pv = jnp.where(is_lo, jnp.roll(vals, -dist, axis=-1),
                   jnp.roll(vals, dist, axis=-1))
    pi = jnp.where(is_lo, jnp.roll(ids, -dist, axis=-1),
                   jnp.roll(ids, dist, axis=-1))
    # boolean selects are spelled as logic ops: Mosaic cannot lower a
    # select (or a constant) whose values are i1
    take_partner = (keep_max & (pv > vals)) | (~keep_max & (pv < vals))
    new_v = jnp.where(take_partner, pv, vals)
    new_i = jnp.where(take_partner, pi, ids)
    return new_v, new_i


def reverse_lanes(x: jax.Array) -> jax.Array:
    """``jnp.flip`` on the last (power-of-two) axis from roll + where.

    Mosaic has no lowering for ``lax.rev``.  Swapping the two halves of
    every 2·dist block, for dist = n/2 … 1, flips every bit of the lane
    index, i.e. maps lane i to n-1-i.  Pure data movement, so the result
    is bit-identical to ``jnp.flip``.
    """
    n = x.shape[-1]
    assert _is_pow2(n), f"lane reversal needs power-of-two length, got {n}"
    iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    dist = n // 2
    while dist >= 1:
        is_lo = (iota % (2 * dist)) < dist
        x = jnp.where(is_lo, jnp.roll(x, -dist, axis=-1),
                      jnp.roll(x, dist, axis=-1))
        dist //= 2
    return x


def bitonic_sort_desc(vals: jax.Array, ids: jax.Array
                      ) -> Tuple[jax.Array, jax.Array]:
    """Full descending sort along the last axis (power-of-two length)."""
    n = vals.shape[-1]
    assert _is_pow2(n), f"bitonic sort needs power-of-two length, got {n}"
    iota = jax.lax.broadcasted_iota(jnp.int32, vals.shape, vals.ndim - 1)
    stage = 2
    while stage <= n:
        desc = (iota & stage) == 0          # per-block direction
        dist = stage // 2
        while dist >= 1:
            is_lo = (iota % (2 * dist)) < dist
            # final merge (stage == n) is fully descending
            keep_max = is_lo if stage == n else ~(is_lo ^ desc)
            vals, ids = _compare_exchange(vals, ids, dist, keep_max)
            dist //= 2
        stage *= 2
    return vals, ids


def bitonic_merge_desc(vals: jax.Array, ids: jax.Array
                       ) -> Tuple[jax.Array, jax.Array]:
    """Descending merge of a *bitonic* sequence along the last axis.

    Input convention: first half sorted descending, second half sorted
    ascending (i.e. ``concat(run_desc, reverse_lanes(block_desc))``).
    """
    n = vals.shape[-1]
    assert _is_pow2(n), f"bitonic merge needs power-of-two length, got {n}"
    iota = jax.lax.broadcasted_iota(jnp.int32, vals.shape, vals.ndim - 1)
    dist = n // 2
    while dist >= 1:
        is_lo = (iota % (2 * dist)) < dist
        vals, ids = _compare_exchange(vals, ids, dist, is_lo)
        dist //= 2
    return vals, ids


def merge_topk_desc(run_v: jax.Array, run_i: jax.Array,
                    blk_v: jax.Array, blk_i: jax.Array,
                    ) -> Tuple[jax.Array, jax.Array]:
    """Merge two descending-sorted k-tiles; return the descending top-k.

    Shapes: (..., k) each; k power of two.
    """
    v = jnp.concatenate([run_v, reverse_lanes(blk_v)], axis=-1)
    i = jnp.concatenate([run_i, reverse_lanes(blk_i)], axis=-1)
    v, i = bitonic_merge_desc(v, i)
    k = run_v.shape[-1]
    return v[..., :k], i[..., :k]


def block_topk_desc(scores: jax.Array, ids: jax.Array, k: int
                    ) -> Tuple[jax.Array, jax.Array]:
    """Exact top-k (descending) of a block via full bitonic sort."""
    v, i = bitonic_sort_desc(scores, ids)
    return v[..., :k], i[..., :k]


# ---------------------------------------------------------------------------
# tie-aware variants: order by (value desc, position asc)
# ---------------------------------------------------------------------------
#
# ``_compare_exchange`` keeps its own element on an exact value tie, so
# the plain network's tie order depends on where elements happen to sit
# in the register tile — fine for the classic kernels (their tests break
# ties in data), wrong for the fused turn, whose ids/sel outputs must be
# *bit-identical* to ``lax.top_k`` over the reference flat layout.
# ``lax.top_k`` (and ``distributed_topk_ordered``) break value ties by
# smaller source position, so these variants carry an explicit position
# lane and sort by the composite key (value desc, position asc) — a
# total order, which also makes the padding convention exact: pads get
# (-inf, pos=INT32_MAX) and can never displace a real candidate.

#: position sentinel for padding lanes in the tie-aware networks
PAD_POS = jnp.iinfo(jnp.int32).max


def _compare_exchange_tie(vals: jax.Array, ids: jax.Array, pos: jax.Array,
                          dist: int, keep_max: jax.Array
                          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Compare-exchange at ``dist`` under (value desc, position asc)."""
    iota = jax.lax.broadcasted_iota(jnp.int32, vals.shape, vals.ndim - 1)
    is_lo = (iota % (2 * dist)) < dist
    pv = jnp.where(is_lo, jnp.roll(vals, -dist, axis=-1),
                   jnp.roll(vals, dist, axis=-1))
    pi = jnp.where(is_lo, jnp.roll(ids, -dist, axis=-1),
                   jnp.roll(ids, dist, axis=-1))
    pp = jnp.where(is_lo, jnp.roll(pos, -dist, axis=-1),
                   jnp.roll(pos, dist, axis=-1))
    gt = (pv > vals) | ((pv == vals) & (pp < pos))   # partner ranks higher
    take_partner = ~(keep_max ^ gt)
    new_v = jnp.where(take_partner, pv, vals)
    new_i = jnp.where(take_partner, pi, ids)
    new_p = jnp.where(take_partner, pp, pos)
    return new_v, new_i, new_p


def bitonic_sort_desc_tie(vals: jax.Array, ids: jax.Array, pos: jax.Array
                          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Full sort by (value desc, position asc) along the last axis."""
    n = vals.shape[-1]
    assert _is_pow2(n), f"bitonic sort needs power-of-two length, got {n}"
    iota = jax.lax.broadcasted_iota(jnp.int32, vals.shape, vals.ndim - 1)
    stage = 2
    while stage <= n:
        desc = (iota & stage) == 0
        dist = stage // 2
        while dist >= 1:
            is_lo = (iota % (2 * dist)) < dist
            keep_max = is_lo if stage == n else ~(is_lo ^ desc)
            vals, ids, pos = _compare_exchange_tie(vals, ids, pos, dist,
                                                   keep_max)
            dist //= 2
        stage *= 2
    return vals, ids, pos


def bitonic_merge_desc_tie(vals: jax.Array, ids: jax.Array, pos: jax.Array
                           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Merge a bitonic sequence under (value desc, position asc)."""
    n = vals.shape[-1]
    assert _is_pow2(n), f"bitonic merge needs power-of-two length, got {n}"
    iota = jax.lax.broadcasted_iota(jnp.int32, vals.shape, vals.ndim - 1)
    dist = n // 2
    while dist >= 1:
        is_lo = (iota % (2 * dist)) < dist
        vals, ids, pos = _compare_exchange_tie(vals, ids, pos, dist, is_lo)
        dist //= 2
    return vals, ids, pos


def merge_topk_desc_tie(run_v: jax.Array, run_i: jax.Array,
                        run_p: jax.Array, blk_v: jax.Array,
                        blk_i: jax.Array, blk_p: jax.Array,
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Merge two tiles sorted by (value desc, position asc); keep top-k."""
    v = jnp.concatenate([run_v, reverse_lanes(blk_v)], axis=-1)
    i = jnp.concatenate([run_i, reverse_lanes(blk_i)], axis=-1)
    p = jnp.concatenate([run_p, reverse_lanes(blk_p)], axis=-1)
    v, i, p = bitonic_merge_desc_tie(v, i, p)
    k = run_v.shape[-1]
    return v[..., :k], i[..., :k], p[..., :k]


def block_topk_desc_tie(scores: jax.Array, ids: jax.Array, pos: jax.Array,
                        k: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Exact top-k under (value desc, position asc) via full sort."""
    v, i, p = bitonic_sort_desc_tie(scores, ids, pos)
    return v[..., :k], i[..., :k], p[..., :k]
