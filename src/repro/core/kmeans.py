"""Distributed balanced k-means for IVF index construction.

The paper builds IVF partitions with K-Means (TopLoc §2, "IVF").  We
implement spherical Lloyd iterations as a pure-JAX program so index build
runs data-parallel under ``pjit`` on the production mesh: points sharded
over devices, centroid statistics reduced with (implicit SPMD) psums.

On TPU the posting lists must be *bucketed-padded* tensors (static shapes),
so we additionally balance the assignment: points whose cluster is over
capacity spill to their next-nearest centroid (the same trick ScaNN/SOAR
use).  This bounds the padding waste of the ``(p, Lmax, d)`` list tensor.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class KMeansResult(NamedTuple):
    centroids: jax.Array        # (p, d) float
    assignment: jax.Array       # (n,) int32 — balanced assignment
    sizes: jax.Array            # (p,) int32 — cluster sizes after balancing
    inertia: jax.Array          # () float — mean max-similarity at convergence


def _assign(points: jax.Array, centroids: jax.Array) -> jax.Array:
    """Nearest-centroid assignment under dot-product similarity."""
    sims = points @ centroids.T                     # (n, p)
    return jnp.argmax(sims, axis=-1).astype(jnp.int32)


def _update(points: jax.Array, assign: jax.Array, p: int) -> Tuple[jax.Array, jax.Array]:
    """Centroid update: per-cluster mean (segment_sum / counts)."""
    sums = jax.ops.segment_sum(points, assign, num_segments=p)
    counts = jax.ops.segment_sum(jnp.ones_like(assign, jnp.float32), assign, num_segments=p)
    safe = jnp.maximum(counts, 1.0)[:, None]
    return sums / safe, counts


def _respawn_empty(centroids: jax.Array, counts: jax.Array, points: jax.Array,
                   key: jax.Array) -> jax.Array:
    """Re-seed empty clusters from random points (keeps p live partitions)."""
    n = points.shape[0]
    idx = jax.random.randint(key, (centroids.shape[0],), 0, n)
    repl = points[idx]
    empty = (counts < 0.5)[:, None]
    return jnp.where(empty, repl, centroids)


def _plusplus_init(points: jax.Array, p: int, key: jax.Array) -> jax.Array:
    """k-means++ seeding (Arthur & Vassilvitskii): each next seed is drawn
    with probability proportional to its squared distance from the nearest
    seed so far.  O(p·n) — used where codebook quality matters more than
    init cost (PQ subspace codebooks)."""
    n = points.shape[0]
    sq = jnp.sum(points ** 2, -1)
    k0, k_rest = jax.random.split(key)
    first = jax.random.randint(k0, (), 0, n)

    def pick(carry, k):
        idx, d2 = carry
        # squared distance to the newest seed, folded into the running min
        c = points[idx]
        d2 = jnp.minimum(d2, sq + jnp.sum(c ** 2) - 2.0 * (points @ c))
        probs = jnp.maximum(d2, 0.0)
        probs = probs / jnp.maximum(probs.sum(), 1e-30)
        nxt = jax.random.choice(k, n, p=probs)
        return (nxt, d2), idx

    keys = jax.random.split(k_rest, p)
    _, seeds = jax.lax.scan(pick, (first, jnp.full((n,), jnp.inf)), keys)
    return points[seeds]


@functools.partial(jax.jit, static_argnames=("p", "iters", "block", "init"))
def kmeans_fit(points: jax.Array, p: int, *, iters: int = 10,
               key: Optional[jax.Array] = None, block: int = 0,
               init: str = "random") -> Tuple[jax.Array, jax.Array]:
    """Lloyd iterations; returns (centroids (p,d), assignment (n,)).

    Pure jnp — shard ``points`` over the data axis under pjit and the
    segment_sum/argmax pattern partitions automatically (the centroid
    statistics become an all-reduce).  ``block`` is unused here (kept for
    API parity with the kernelised assigner).  ``init``: 'random' (sample
    p points) or '++' (k-means++ seeding — better local optima, O(p·n)
    extra init work).
    """
    del block
    n = points.shape[0]
    if key is None:
        key = jax.random.PRNGKey(0)
    k_init, k_iter = jax.random.split(key)
    if init == "++":
        centroids0 = _plusplus_init(points, p, k_init)
    else:
        init_idx = jax.random.choice(k_init, n, (p,), replace=n < p)
        centroids0 = points[init_idx]

    def body(carry, k):
        centroids, _ = carry
        assign = _assign(points, centroids)
        centroids, counts = _update(points, assign, p)
        centroids = _respawn_empty(centroids, counts, points, k)
        return (centroids, assign), None

    keys = jax.random.split(k_iter, iters)
    (centroids, _), _ = jax.lax.scan(body, (centroids0, jnp.zeros(n, jnp.int32)), keys)
    assign = _assign(points, centroids)
    return centroids, assign


#: points per block of the (points × centroids) similarity products in
#: the balancing pass: a (block, p) f32 tile instead of the full (n, p)
#: matrix, which at n = 2^20, p = 2^14 would be 64 GiB
BLOCK = 4096


def _blocked_top(points: jax.Array, centroids: jax.Array, r: int,
                 block: int) -> Tuple[jax.Array, jax.Array]:
    """Per point, the r most similar centroids: (sims (n, r), ids (n, r)).

    Computed ``block`` points at a time so only a (block, p) similarity
    tile is ever live."""
    n, d = points.shape
    block = min(block, n)
    nb = -(-n // block)
    padded = points
    if nb * block != n:
        padded = jnp.pad(points, ((0, nb * block - n), (0, 0)))

    def one(x):
        v, i = jax.lax.top_k(x @ centroids.T, r)
        return v, i.astype(jnp.int32)

    v, i = jax.lax.map(one, padded.reshape(nb, block, d))
    return v.reshape(-1, r)[:n], i.reshape(-1, r)[:n]


@functools.partial(jax.jit,
                   static_argnames=("p", "capacity", "n_choices", "block"))
def balance_assignment(points: jax.Array, centroids: jax.Array, p: int,
                       capacity: int, n_choices: int = 4,
                       block: int = BLOCK) -> Tuple[jax.Array, jax.Array]:
    """Capacity-constrained assignment: greedy spill to next-nearest centroid.

    Deterministic, vectorised: points are processed in similarity-priority
    order per choice rank; a point that does not fit its rank-r centroid
    (cluster already at ``capacity``) is deferred to rank r+1.  After
    ``n_choices`` ranks any still-unplaced point lands in the globally
    least-loaded cluster (no capacity bound; in practice this bucket is
    empty for capacity ≥ 1.25·n/p).

    Memory is O(n·n_choices + block·p): the similarities are reduced to
    each point's top ``n_choices`` centroids block by block, and a
    point's rank within its candidate cluster comes from stable sorts
    by (candidate, -similarity, point index).

    Returns (assignment (n,), sizes (p,)).
    """
    n = points.shape[0]
    n_choices = min(n_choices, p)
    choice_sims, choice_ids = _blocked_top(points, centroids, n_choices,
                                           block)
    pos = jnp.arange(n, dtype=jnp.int32)

    def place_rank(carry, r):
        assignment, sizes = carry
        want = assignment < 0                                     # unplaced
        # group the unplaced points by candidate cluster (placed ones go
        # to the sentinel group p); within a group the best-matching
        # points come first, ties by point index, so they win the
        # cluster's remaining capacity
        grp = jnp.where(want, choice_ids[:, r], p)
        # two single-key sorts, not one three-key sort: the TPU compiler
        # takes about half as long over them at n = 2^20
        order = jnp.argsort(jnp.where(want, -choice_sims[:, r], jnp.inf))
        g_s, i_s = jax.lax.sort((grp[order], order), num_keys=1,
                                is_stable=True)
        first = jnp.concatenate([jnp.ones((1,), bool), g_s[1:] != g_s[:-1]])
        start = jax.lax.cummax(jnp.where(first, pos, 0))
        pos_in_cluster = jnp.zeros((n,), jnp.int32).at[i_s].set(pos - start)
        room = capacity - sizes[jnp.minimum(grp, p - 1)]
        ok = want & (pos_in_cluster < room)
        assignment = jnp.where(ok, grp, assignment)
        sizes = sizes + jax.ops.segment_sum(
            ok.astype(jnp.int32), jnp.where(ok, grp, p),
            num_segments=p + 1)[:p]
        return (assignment, sizes), None

    (assignment, sizes), _ = jax.lax.scan(
        place_rank, (jnp.full((n,), -1, jnp.int32),
                     jnp.zeros((p,), jnp.int32)),
        jnp.arange(n_choices))

    # fallback: dump stragglers, in index order, into the least-loaded
    # cluster one by one — a loop over the unplaced points only
    n_left = jnp.sum(assignment < 0)
    left = jnp.nonzero(assignment < 0, size=n, fill_value=0)[0]

    def fallback(carry):
        i, assignment, sizes = carry
        tgt = jnp.argmin(sizes).astype(jnp.int32)
        assignment = assignment.at[left[i]].set(tgt)
        sizes = sizes.at[tgt].add(1)
        return i + 1, assignment, sizes

    _, assignment, sizes = jax.lax.while_loop(
        lambda c: c[0] < n_left, fallback, (0, assignment, sizes))
    return assignment, sizes


@functools.partial(jax.jit, static_argnames=("block",))
def mean_max_similarity(points: jax.Array, centroids: jax.Array, *,
                        block: int = BLOCK) -> jax.Array:
    """Mean over points of the best centroid similarity (the inertia),
    blocked like ``balance_assignment``."""
    v, _ = _blocked_top(points, centroids, 1, block)
    return jnp.mean(v[:, 0])


def fit_balanced(points: jax.Array, p: int, *, iters: int = 10,
                 key: Optional[jax.Array] = None,
                 capacity_factor: float = 1.3) -> KMeansResult:
    """End-to-end: Lloyd fit + capacity-balanced final assignment."""
    n = points.shape[0]
    centroids, _ = kmeans_fit(points, p, iters=iters, key=key)
    capacity = max(1, int(capacity_factor * n / p + 0.9999))
    assignment, sizes = balance_assignment(points, centroids, p, capacity)
    inertia = mean_max_similarity(points, centroids)
    return KMeansResult(centroids, assignment, sizes, inertia)
