"""Product quantisation for IVF posting lists (beyond-paper extension).

At the paper's scale (38.6M × 768-d passages) FAISS deployments store
posting lists PQ-compressed (IVF-PQ, the paper's reference [11]): each
vector is split into ``m`` subspaces, each encoded as one byte (256
k-means codewords per subspace) — 32–64× smaller lists, scanned via
asymmetric distance computation (ADC): the query precomputes a
``(m, 256)`` lookup table once, then every encoded doc costs ``m`` table
gathers + adds instead of a d-dim dot product.

TPU mapping: the LUT build is a tiny matmul; the ADC scan is a gather-
accumulate along the lanes — the same HBM→VMEM streaming shape as
``kernels/ivf_scan`` with 32× fewer bytes per document, which directly
divides the memory roofline term of list scanning.  TopLoc composes
orthogonally (it prunes *which* lists are scanned; PQ compresses *how*).

Pure-jnp here (build is offline).  The hot ADC scan lives in
``kernels/pq_adc.py`` (same PrefetchScalarGridSpec pattern as ivf_scan
with the (m, 256) LUT resident in VMEM); ``IVFPQIndex`` below packages
the compressed lists + re-rank source that ``backend.IVFPQBackend``
and the serving engines consume.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import ivf as _ivf
from repro.core import kmeans as _kmeans


class PQCodebook(NamedTuple):
    codewords: jax.Array     # (m, 256, d_sub)
    m: int = 0               # static copy of subspace count

    @property
    def d(self) -> int:
        return self.codewords.shape[0] * self.codewords.shape[2]


def train(vectors: jax.Array, m: int, *, iters: int = 8,
          key: Optional[jax.Array] = None, n_codes: int = 256
          ) -> PQCodebook:
    """Per-subspace k-means codebooks. vectors (n, d), d % m == 0."""
    n, d = vectors.shape
    assert d % m == 0, (d, m)
    d_sub = d // m
    if key is None:
        key = jax.random.PRNGKey(0)
    keys = jax.random.split(key, m)
    subs = vectors.reshape(n, m, d_sub)
    books = []
    for j in range(m):
        # ++ seeding: random init leaves enough near-duplicate codewords
        # in the low-dim subspaces to visibly hurt ADC fidelity
        c, _ = _kmeans.kmeans_fit(subs[:, j], n_codes, iters=iters,
                                  key=keys[j], init="++")
        books.append(c)
    return PQCodebook(jnp.stack(books), m)


@jax.jit
def encode(book: PQCodebook, vectors: jax.Array) -> jax.Array:
    """→ codes (n, m) uint8: nearest codeword per subspace (L2)."""
    n, d = vectors.shape
    m, n_codes, d_sub = book.codewords.shape
    subs = vectors.reshape(n, m, d_sub)
    # ||x - c||² = ||x||² - 2<x,c> + ||c||²; argmin over codewords.
    # HIGHEST: bf16-rounded dots (a TPU's DEFAULT) pick a farther
    # codeword whenever two are near, adding quantisation error
    dots = jnp.einsum("nmd,mkd->nmk", subs, book.codewords,
                      precision=jax.lax.Precision.HIGHEST)
    c_sq = jnp.sum(book.codewords ** 2, -1)                 # (m, k)
    return jnp.argmin(c_sq[None] - 2 * dots, axis=-1).astype(jnp.uint8)


@jax.jit
def decode(book: PQCodebook, codes: jax.Array) -> jax.Array:
    """Reconstruct (n, d) from (n, m) codes."""
    n, m = codes.shape
    rows = jnp.take_along_axis(
        book.codewords[None], codes[:, :, None, None].astype(jnp.int32),
        axis=2)[:, :, 0]                                    # (n, m, d_sub)
    return rows.reshape(n, -1)


@jax.jit
def adc_table(book: PQCodebook, query: jax.Array) -> jax.Array:
    """Query → (m, 256) inner-product lookup table (built once/query)."""
    m, n_codes, d_sub = book.codewords.shape
    q = query.reshape(m, d_sub)
    return jnp.einsum("md,mkd->mk", q, book.codewords,
                      precision=jax.lax.Precision.HIGHEST)  # (m, 256)


@jax.jit
def adc_scores(table: jax.Array, codes: jax.Array) -> jax.Array:
    """ADC: approximate <q, x> for encoded docs. codes (n, m) → (n,)."""
    n, m = codes.shape
    gathered = jnp.take_along_axis(
        jnp.broadcast_to(table, (n, m, table.shape[1])),
        codes.astype(jnp.int32)[:, :, None], axis=2)[:, :, 0]
    return jnp.sum(gathered, axis=-1)


# ---------------------------------------------------------------------------
# IVF-PQ index: IVF geometry + PQ-compressed posting lists
# ---------------------------------------------------------------------------

class IVFPQIndex(NamedTuple):
    """IVF index whose posting lists store PQ codes instead of floats.

    Same bucketed-padded layout as ``ivf.IVFIndex`` but each list entry
    is ``m`` uint8 codes (m bytes/doc vs 4·d), cutting the
    bytes-from-HBM of a list scan by 4·d/m (16x at d=128, m=32... and
    64x at the paper's d=768, m=48).  ``doc_vecs`` keeps the
    uncompressed collection for exact re-ranking of the top-R ADC
    candidates — the standard IVFPQ+refine design: only R rows per
    query ever touch the float corpus.

    All fields are device arrays so the index is a pytree (jit/vmap
    friendly); static shape properties mirror ``IVFIndex``.
    """
    centroids: jax.Array    # (p, d)  float32 — IVF coarse quantiser
    codewords: jax.Array    # (m, n_codes, d_sub) — PQ codebooks
    list_codes: jax.Array   # (p, Lmax, m) uint8 — PQ-encoded lists
    list_ids: jax.Array     # (p, Lmax) int32 — doc ids, -1 = pad
    list_sizes: jax.Array   # (p,) int32 — real sizes
    doc_vecs: jax.Array     # (n, d) float32 — re-rank source

    @property
    def p(self) -> int:
        return self.centroids.shape[0]

    @property
    def d(self) -> int:
        return self.centroids.shape[1]

    @property
    def m(self) -> int:
        return self.codewords.shape[0]

    @property
    def lmax(self) -> int:
        return self.list_ids.shape[1]

    @property
    def n_docs(self) -> int:
        return int(self.list_sizes.sum())

    @property
    def book(self) -> PQCodebook:
        return PQCodebook(self.codewords, self.codewords.shape[0])

    @property
    def bytes_per_doc(self) -> int:
        """Posting-list payload per document (codes only)."""
        return self.codewords.shape[0]


def build_ivf_pq(index, vectors: jax.Array, m: int, *, iters: int = 8,
                 key: Optional[jax.Array] = None, n_codes: int = 256
                 ) -> IVFPQIndex:
    """PQ-compress the posting lists of a built ``ivf.IVFIndex``.

    Trains per-subspace codebooks on the full collection, encodes every
    doc, and gathers the codes into the index's bucketed layout (pad
    rows encode as code 0 but stay masked by ``list_ids == -1``).
    """
    book = train(vectors, m, iters=iters, key=key, n_codes=n_codes)
    codes = encode(book, vectors)                   # (n, m) uint8
    list_codes = _ivf.gather_lists(codes, index.list_ids)
    return IVFPQIndex(index.centroids, book.codewords, list_codes,
                      index.list_ids, index.list_sizes.astype(jnp.int32),
                      jnp.asarray(vectors))


def adc_scores_masked(tables: jax.Array, codes: jax.Array,
                      ids: jax.Array) -> jax.Array:
    """ADC scores for pre-gathered candidate blocks, batched + masked.

    tables (B, m, n_codes) f32; codes (B, N, m) int32; ids (B, N) int32
    (-1 = pad/foreign → score -inf).  Returns (B, N) f32.

    The per-candidate compute — an m-row LUT gather transposed to
    (m, N) and reduced over the m axis — is formulated *exactly* like
    ``kernels.ref.pq_adc_scan`` so each candidate's reduction order (and
    therefore its last-ulp value) matches the single-device scan.  The
    device-sharded scan (``distributed.retrieval.ShardedPQScan``) relies
    on this to stay bit-identical to the unsharded backend.
    """
    def one(table, codes_q, ids_q):
        gathered = jnp.take_along_axis(table, codes_q.T, axis=1)  # (m, N)
        scores = jnp.sum(gathered, axis=0)
        return jnp.where(ids_q >= 0, scores, -jnp.inf)

    return jax.vmap(one)(tables, codes, ids)


@functools.partial(jax.jit, static_argnames=("k",))
def adc_search_lists(book: PQCodebook, query: jax.Array,
                     list_codes: jax.Array, list_ids: jax.Array,
                     sel: jax.Array, k: int
                     ) -> Tuple[jax.Array, jax.Array]:
    """TopLoc+PQ composed: scan the selected PQ-encoded posting lists.

    query (d,); list_codes (p, Lmax, m) uint8; list_ids (p, Lmax);
    sel (np,) — e.g. from the TopLoc centroid cache.
    """
    table = adc_table(book, query)                          # (m, 256)
    codes = list_codes[sel]                                 # (np, L, m)
    ids = list_ids[sel]
    npb, lmax, m = codes.shape
    flat = codes.reshape(-1, m)
    scores = adc_scores(table, flat).reshape(npb, lmax)
    scores = jnp.where(ids >= 0, scores, -jnp.inf)
    v, pos = jax.lax.top_k(scores.reshape(-1), k)
    return v, ids.reshape(-1)[pos]
