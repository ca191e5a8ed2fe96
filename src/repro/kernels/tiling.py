"""Shared VMEM tile-split policy for the Pallas dispatch wrappers.

Every kernel wrapper in ``ops.py`` used to carry its own copy of the
same three decisions — pad k to a power of two, cap the streamed list
tile by VMEM bytes, round the streamed axis up to a tile multiple.  The
``kernel_budget`` analysis pass (PK401/PK402) re-derived the same
numbers independently, which meant the checker and the wrappers could
drift apart.  This module is now the single source of truth for both:
the wrappers ask it how to split, and the budget pass imports the same
constants it asserts against.

Layout constants (TPU register tiling / per-core VMEM) live here too so
the fused megakernel, the classic per-stage kernels, and the analysis
pass can never disagree on what "fits".
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp

# Per-core VMEM (TPU guide). The budget pass flags any kernel whose
# double-buffered blocks + scratch exceed this.
VMEM_BUDGET_BYTES = 16 * 1024 * 1024

# Per-stream VMEM slice for the dominant streamed tile: the pipeline
# double-buffers it, and queries/ids/outputs/scratch share the ~16 MiB
# core budget, so one buffer gets at most a quarter.
VMEM_TILE_BYTES = 4 * 1024 * 1024

# float32 register tiling: (sublane, lane) = (8, 128); narrower dtypes
# need proportionally taller sublane tiles.
LANE = 128


def sublane(itemsize: int) -> int:
    return {4: 8, 2: 16, 1: 32}.get(int(itemsize), 8)


def next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def pow2_floor(n: int) -> int:
    return max(next_pow2(n + 1) // 2, 1)


def is_pow2(n: int) -> bool:
    return n >= 1 and (n & (n - 1)) == 0


def pad_axis(x: jax.Array, axis: int, to: int, value) -> jax.Array:
    n = x.shape[axis]
    if n == to:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, to - n)
    return jnp.pad(x, pads, constant_values=value)


#: bytes of input a blocked pad copies per step (``pad_rows_blocked``)
PAD_BLOCK_BYTES = 64 * 1024 * 1024


def pad_rows_blocked(x: jax.Array, to: int, value, *,
                     block_bytes: int = PAD_BLOCK_BYTES) -> jax.Array:
    """``pad_axis(x, 1, to, value)`` one block of leading rows at a time.

    A one-shot ``jnp.pad`` of a posting-list tensor lowers on the TPU
    with a temporary as large as its input (4.4 GB beside a 6.4 GB
    output for 2^14 lists of 84 x 768 f32, compiled for v5e), which
    does not fit beside the lists it copies.  Filling the output in
    place one block of at most ``block_bytes`` of input at a time keeps
    the temporary to one block.
    """
    if x.shape[1] == to or x.shape[0] == 0:
        return x
    return _pad_rows_blocked(x, to, value, block_bytes=block_bytes)


@functools.partial(jax.jit, static_argnames=("to", "block_bytes"))
def _pad_rows_blocked(x: jax.Array, to: int, value, *,
                      block_bytes: int) -> jax.Array:
    n = x.shape[0]
    row_bytes = math.prod(x.shape[1:]) * x.dtype.itemsize
    block = max(1, min(n, block_bytes // max(row_bytes, 1)))
    out = jnp.full((n, to) + x.shape[2:], value, x.dtype)
    zeros = (0,) * (x.ndim - 1)

    def body(i, out):
        # the last block may overlap the one before it: it rewrites
        # those rows with the same values
        start = jnp.minimum(i * block, n - block)
        blk = jax.lax.dynamic_slice_in_dim(x, start, block, 0)
        return jax.lax.dynamic_update_slice(out, blk, (start,) + zeros)

    return jax.lax.fori_loop(0, -(-n // block), body, out)


def list_len(lmax: int) -> int:
    """Posting-list length the list kernels stream in place: the
    power-of-two length ``list_tile`` pads to.  A list axis already this
    long is scanned without a per-call copy (``list_tile`` pads further
    only when the merge depth ``kp`` exceeds it)."""
    return next_pow2(lmax)


def list_tile(lmax: int, row_bytes: int, *, kp: int = 1,
              max_tile: int = 2048) -> Tuple[int, int]:
    """Split a streamed posting-list axis: ``(blk_l, lpad)``.

    ``blk_l`` is the per-step tile (power of two, ≥ kp so the running
    top-k merge network has a full block to fold, ≤ ``max_tile`` rows,
    and byte-capped so the double-buffered ``(blk_l, row_bytes)`` tile
    stays inside its VMEM_TILE_BYTES slice — a row cap alone
    over-allocates at large d: d=1024 f32 → 8 MiB tile → 16 MiB in
    flight).  ``lpad`` is ``lmax`` rounded up to a ``blk_l`` multiple.
    """
    lpad = list_len(lmax)
    blk_l = min(lpad, max_tile)
    blk_l = min(blk_l, pow2_floor(VMEM_TILE_BYTES // max(row_bytes, 1)))
    blk_l = max(blk_l, kp)
    lpad = ((lpad + blk_l - 1) // blk_l) * blk_l
    return blk_l, lpad


def centroid_tile(p: int, kp: int, *, blk_p: int = 512
                  ) -> Tuple[int, int]:
    """Split the centroid axis: ``(blk, p_pad)``.

    The tile is a power of two ≥ kp (the merge network folds one block
    into the running (1, kp) top-k per step) and ``p_pad`` rounds the
    centroid count up to a tile multiple.
    """
    blk = min(blk_p, next_pow2(p))
    blk = max(blk, kp)
    p_pad = ((p + blk - 1) // blk) * blk
    return blk, p_pad
