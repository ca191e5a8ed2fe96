"""IVF (Inverted File) index — TPU-native bucketed-padded realisation.

Semantics follow FAISS IVF as described in the paper (TopLoc §2):

  * ``p`` centroids from (balanced) k-means; each data point lives in the
    posting list of its nearest centroid (modulo capacity spill, see
    ``core.kmeans.balance_assignment``).
  * A query scores all ``p`` centroids, selects the top-``nprobe`` lists,
    scans them exhaustively and returns the global top-k by dot product.

TPU adaptation (DESIGN.md §2): posting lists are stored as a dense
``(p, Lmax, d)`` tensor (+ id / mask tensors) so list scans are regular
gathers + matmuls.  Work counters report *real* (unpadded) distance
computations so efficiency numbers are not flattered by padding.

The pure-jnp search here is also the oracle for the Pallas ``ivf_scan``
kernel (kernels/ref.py re-exports pieces of it).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import kmeans as _kmeans
from repro.core.topk import masked_topk


#: Precision of every float32 score the contracts compare: batched ==
#: sequential, fused f32 == the XLA path, recall against exact search.
#: At DEFAULT precision a TPU rounds f32 matmul operands to bf16 (the
#: CPU computes in f32 either way), which moved ids on the chip.
F32 = jax.lax.Precision.HIGHEST


class IVFIndex(NamedTuple):
    """Bucketed-padded IVF index. All fields are device arrays (a pytree)."""
    centroids: jax.Array    # (p, d)  float32
    list_vecs: jax.Array    # (p, Lmax, d) float32 — padded posting lists
    list_ids: jax.Array     # (p, Lmax) int32 — original doc ids, -1 = pad
    list_sizes: jax.Array   # (p,) int32 — real sizes

    @property
    def p(self) -> int:
        return self.centroids.shape[0]

    @property
    def d(self) -> int:
        return self.centroids.shape[1]

    @property
    def lmax(self) -> int:
        return self.list_ids.shape[1]

    @property
    def n_docs(self) -> int:
        return int(self.list_sizes.sum())


class SearchStats(NamedTuple):
    """Per-query work counters (the hardware-independent cost model)."""
    centroid_dists: jax.Array   # (B,) int32 — centroid scoring work
    list_dists: jax.Array       # (B,) int32 — real doc distances computed
    padded_list_dists: jax.Array  # (B,) int32 — incl. padding (TPU lanes)


def build(vectors: jax.Array, p: int, *, iters: int = 10,
          key: Optional[jax.Array] = None,
          capacity_factor: float = 1.3) -> IVFIndex:
    """Build the index: balanced k-means + bucketed posting-list layout.

    The list axis is the largest list's length; pad slots carry id -1,
    which every scan masks.  An engine whose scan runs a Pallas list
    kernel pads it to the kernel tile once, when it places the index
    (``backend.RetrievalBackend.place``).
    """
    vectors = jnp.asarray(vectors)
    res = _kmeans.fit_balanced(vectors, p, iters=iters, key=key,
                               capacity_factor=capacity_factor)
    list_ids = bucketize(jax.device_get(res.assignment), p)
    return IVFIndex(res.centroids, gather_lists(vectors, list_ids),
                    list_ids, res.sizes.astype(jnp.int32))


def bucketize(assign, p: int) -> jax.Array:
    """(p, max size) doc ids per list, ascending, -1 padded."""
    assign = np.asarray(assign)
    sizes = np.bincount(assign, minlength=p)
    lmax = max(int(sizes.max()), 1)
    docs = np.argsort(assign, kind="stable")
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    slot = np.arange(len(assign)) - starts[assign[docs]]
    ids = np.full((p, lmax), -1, np.int32)
    ids[assign[docs], slot] = docs
    return jnp.asarray(ids)


@jax.jit
def gather_lists(vectors: jax.Array, list_ids: jax.Array) -> jax.Array:
    """(p, L, d) posting-list rows for ``list_ids``; zero rows at -1.

    One gather into the output (ids -1 read out of range, filled with
    0), so the build holds one list tensor, not a gather and a masked
    copy of it."""
    idx = jnp.where(list_ids >= 0, list_ids, vectors.shape[0])
    return jnp.take(vectors, idx, axis=0, mode="fill", fill_value=0)


# ---------------------------------------------------------------------------
# Search paths
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k",))
def exact_search(vectors: jax.Array, queries: jax.Array, k: int
                 ) -> Tuple[jax.Array, jax.Array]:
    """Brute-force top-k over the full collection (paper's 'Exact' row)."""
    scores = jnp.matmul(queries, vectors.T, precision=F32)   # (B, n)
    return jax.lax.top_k(scores, k)


def _scan_lists(index: IVFIndex, queries: jax.Array, sel: jax.Array,
                k: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Scan the selected posting lists; returns (top_v, top_ids, real_dists).

    queries: (B, d); sel: (B, np) selected centroid indices.
    """
    lv = index.list_vecs[sel]                       # (B, np, Lmax, d)
    li = index.list_ids[sel]                        # (B, np, Lmax)
    scores = jnp.einsum("bd,bnld->bnl", queries, lv, precision=F32)
    mask = li >= 0
    b = queries.shape[0]
    flat_scores = scores.reshape(b, -1)
    flat_mask = mask.reshape(b, -1)
    flat_ids = li.reshape(b, -1)
    top_v, pos = masked_topk(flat_scores, flat_mask, k)
    top_i = jnp.take_along_axis(flat_ids, pos, axis=-1)
    real = jnp.sum(index.list_sizes[sel], axis=-1).astype(jnp.int32)
    return top_v, top_i, real


@functools.partial(jax.jit, static_argnames=("nprobe", "k", "scan"))
def search(index: IVFIndex, queries: jax.Array, *, nprobe: int, k: int,
           scan=None) -> Tuple[jax.Array, jax.Array, SearchStats]:
    """Plain IVF search (the paper's baseline).

    ``scan`` optionally replaces the posting-list scan (same signature as
    ``_scan_lists``) — the device-sharded retrieval path
    (``distributed.retrieval.ShardedIVFScan``) plugs in here.
    Returns (scores (B,k), doc_ids (B,k), stats).
    """
    b = queries.shape[0]
    cscores = jnp.matmul(queries, index.centroids.T,
                         precision=F32)             # (B, p)
    _, sel = jax.lax.top_k(cscores, nprobe)          # (B, np)
    top_v, top_i, real = (scan or _scan_lists)(index, queries, sel, k)
    stats = SearchStats(
        centroid_dists=jnp.full((b,), index.p, jnp.int32),
        list_dists=real,
        padded_list_dists=jnp.full((b,), nprobe * index.lmax, jnp.int32),
    )
    return top_v, top_i, stats


@functools.partial(jax.jit, static_argnames=("nprobe", "k"))
def search_cached(index: IVFIndex, cache_ids: jax.Array, cache_vecs: jax.Array,
                  queries: jax.Array, *, nprobe: int, k: int
                  ) -> Tuple[jax.Array, jax.Array, jax.Array, SearchStats]:
    """TopLoc_IVF search against a cached centroid subset ``C0``.

    cache_ids:  (h,) int32 — global centroid indices in the cache
    cache_vecs: (h, d)     — the cached centroid vectors (gathered once at
                              conversation start; resident per session)

    Returns (scores, doc_ids, sel_global (B,np) — the *global* centroid ids
    the query probed, needed by the ``I0`` drift proxy — and stats).
    """
    b = queries.shape[0]
    h = cache_ids.shape[0]
    cscores = jnp.matmul(queries, cache_vecs.T,
                         precision=F32)             # (B, h)
    _, sel_local = jax.lax.top_k(cscores, nprobe)   # (B, np) into cache
    sel_global = cache_ids[sel_local]               # (B, np) global ids
    top_v, top_i, real = _scan_lists(index, queries, sel_global, k)
    stats = SearchStats(
        centroid_dists=jnp.full((b,), h, jnp.int32),
        list_dists=real,
        padded_list_dists=jnp.full((b,), nprobe * index.lmax, jnp.int32),
    )
    return top_v, top_i, sel_global, stats


@functools.partial(jax.jit, static_argnames=("h",))
def make_cache(index: IVFIndex, q0: jax.Array, *, h: int
               ) -> Tuple[jax.Array, jax.Array]:
    """Build the hot-centroid cache from the first utterance:
    ``C0 = top_h(q0, C)`` (TopLoc §2). q0: (d,).

    Returns (cache_ids (h,), cache_vecs (h,d)).
    """
    cscores = jnp.matmul(index.centroids, q0, precision=F32)   # (p,)
    _, ids = jax.lax.top_k(cscores, h)
    return ids.astype(jnp.int32), index.centroids[ids]
