"""Session-level historical-embedding result cache.

Frieder et al., *Caching Historical Embeddings in Conversational
Search*, observe that the same topical locality TopLoc exploits for
index pruning also makes per-conversation *result* caches effective:
within a conversation, consecutive utterances are near-duplicates in
embedding space, so the documents retrieved for turn j-1 usually contain
the answer for turn j.  This module caches, per session, the previous
answering turn:

    q_vec      (d,)    — the query embedding the entry is anchored to
    doc_ids    (k,)    — the turn's top-k document ids (-1 = empty)
    doc_scores (k,)    — their scores under ``q_vec``
    doc_vecs   (k, d)  — the *historical embeddings* of those documents
    valid      ()      — entry holds real state

A new turn first probes the cache: when ``cos(q_new, q_vec) >=
threshold`` the turn is answered **without touching the backend** by
re-scoring the cached document embeddings under the new query (or, when
the backend keeps no flat corpus, by replaying the cached ranking);
otherwise the backend runs and the entry is refreshed with the new
turn's results.  ``threshold <= 0`` disables the cache entirely — the
engines then execute the exact uncached program, bit for bit
(tests/test_result_cache.py pins cache-off == cache-absent and
threshold-0 == uncached).

Numerics follow the repo's batch-size-stability rule: the cosine
similarity and the re-scoring are explicit multiply-reduce contractions,
so the sequential engine (B=1 probes) and the batched engine (slab
gather → one fused probe per wave) stay bit-identical with the cache
enabled.

Storage reuses ``sessions.SessionStore`` as the slab container: the
batched engine keys cache rows by the *same* slot ids as the session
slab and registers a slot-freed listener so an evicted/released
conversation can never leak its entries to the slot's next occupant.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Any, Dict, NamedTuple, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.concurrency import guarded_by
from repro.serving import sessions as _sessions


class CacheEntry(NamedTuple):
    """One session's cached turn (device resident; a pytree)."""
    q_vec: jax.Array       # (d,) float — anchor query embedding
    doc_ids: jax.Array     # (k,) int32 — cached top-k ids, -1 = empty
    doc_scores: jax.Array  # (k,) float — scores under q_vec
    doc_vecs: jax.Array    # (k, d) float — historical doc embeddings
    valid: jax.Array       # () bool


def entry_template(d: int, k: int, dtype=jnp.float32) -> CacheEntry:
    return CacheEntry(
        q_vec=jnp.zeros((d,), dtype),
        doc_ids=jnp.full((k,), -1, jnp.int32),
        doc_scores=jnp.zeros((k,), dtype),
        doc_vecs=jnp.zeros((k, d), dtype),
        valid=jnp.zeros((), bool))


@functools.partial(jax.jit, static_argnames=("out_k", "threshold",
                                             "rescore"))
def probe(entries: CacheEntry, q: jax.Array, *, out_k: int,
          threshold: float, rescore: bool
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Probe a batch of cache entries. entries: leading (B,); q: (B, d).

    Returns (hit (B,) bool, scores (B, out_k), ids (B, out_k)).  A hit
    requires a valid entry whose anchor query's cosine similarity to the
    new query reaches ``threshold``.  With ``rescore`` the cached
    document embeddings (``depth`` per entry, depth >= out_k) are
    re-scored under the new query (exact dot products — the same
    multiply-reduce shape as the IVF-PQ re-rank) and the best out_k
    returned; without, the cached ranking is replayed as-is.
    """
    qq = jnp.sum(q * q, axis=-1)
    cc = jnp.sum(entries.q_vec * entries.q_vec, axis=-1)
    dot = jnp.sum(entries.q_vec * q, axis=-1)
    sim = dot * jax.lax.rsqrt(jnp.maximum(qq * cc, 1e-30))
    hit = entries.valid & (sim >= jnp.asarray(threshold, sim.dtype))
    if rescore:
        scores = jnp.sum(entries.doc_vecs * q[:, None, :], axis=-1)
        scores = jnp.where(entries.doc_ids >= 0, scores, -jnp.inf)
        v, pos = jax.lax.top_k(scores, out_k)
        ids = jnp.take_along_axis(entries.doc_ids, pos, axis=-1)
    else:
        # cached scores are already sorted — the prefix is the top-out_k
        v = entries.doc_scores[..., :out_k]
        ids = entries.doc_ids[..., :out_k]
    return hit, v, ids


@jax.jit
def _make_entries_rescore(q: jax.Array, v: jax.Array, ids: jax.Array,
                          corpus: jax.Array) -> CacheEntry:
    vecs = corpus[jnp.maximum(ids, 0)]
    vecs = jnp.where((ids >= 0)[..., None], vecs, 0.0)
    return CacheEntry(q, ids.astype(jnp.int32), v, vecs,
                      jnp.ones(q.shape[:-1], bool))


@jax.jit
def _make_entries_static(q: jax.Array, v: jax.Array, ids: jax.Array
                         ) -> CacheEntry:
    b, k = ids.shape
    d = q.shape[-1]
    return CacheEntry(q, ids.astype(jnp.int32), v,
                      jnp.zeros((b, k, d), q.dtype),
                      jnp.ones((b,), bool))


@functools.partial(jax.jit, static_argnames=("out_k", "threshold",
                                             "rescore"))
def fuse_wave(entries: CacheEntry, q: jax.Array, v: jax.Array,
              i: jax.Array, sess_old: Any, sess_new: Any, stats: Any,
              corpus: Optional[jax.Array], *, out_k: int, threshold: float,
              rescore: bool):
    """One fused cache pass for a batched wave.

    ``v``/``i`` are the backend's depth-wide results (depth >= out_k).
    Probes the gathered ``entries`` against the wave queries and, per
    hit row, substitutes the cached answer, zeroes the work counters
    (a hit pays no backend work — the documented scalar-cost semantics
    of ``TurnStats``), keeps the *old* session state (the sequential
    engine never steps a session on a hit), and keeps the old cache
    entry; miss rows adopt the backend results (returned sliced to
    out_k) and a refreshed depth-wide entry.

    Returns (v (B, out_k), i (B, out_k), sess, stats, entries, hit).
    """
    hit, cv, ci = probe(entries, q, out_k=out_k, threshold=threshold,
                        rescore=rescore)
    fresh = (_make_entries_rescore(q, v, i, corpus) if rescore
             else _make_entries_static(q, v, i))
    h1 = hit[:, None]
    v = jnp.where(h1, cv, v[..., :out_k])
    i = jnp.where(h1, ci, i[..., :out_k])
    b = q.shape[0]
    z = jnp.zeros((b,), jnp.int32)
    zero_stats = type(stats)(z, z, z, z, jnp.full((b,), -1, jnp.int32),
                             jnp.zeros((b,), bool))
    stats = jax.tree.map(lambda zs, s: jnp.where(hit, zs, s),
                         zero_stats, stats)

    def row_sel(old, new):
        mask = hit.reshape((b,) + (1,) * (new.ndim - 1))
        return jnp.where(mask, old, new)

    sess = jax.tree.map(row_sel, sess_old, sess_new)
    entries = jax.tree.map(row_sel, entries, fresh)
    return v, i, sess, stats, entries, hit


@dataclasses.dataclass(frozen=True)
class WaveFuse:
    """``fuse_wave`` with its static arguments bound: the optional,
    jit-static cache part of the session store's wave program
    (``sessions.step_batch``).  Frozen, so equal settings hit one
    compiled program."""
    out_k: int
    threshold: float
    rescore: bool

    def __call__(self, entries, q, v, i, sess_old, sess_new, stats,
                 corpus):
        return fuse_wave(entries, q, v, i, sess_old, sess_new, stats,
                         corpus, out_k=self.out_k,
                         threshold=self.threshold, rescore=self.rescore)


@guarded_by("_lock", "hits", "misses", "_entries")
class ResultCache:
    """Per-session result cache for both serving engines.

    Sequential mode (``n_slots=None``): entries keyed by conversation id
    in a host dict (one device row each).  Batched mode: a slab of
    ``n_slots`` rows + trash slot, addressed by the engine's session
    slot ids (``gather``/``wave_fuse``/``scatter``); ``clear_slot`` is the
    ``SessionStore`` slot-freed listener.

    ``corpus`` (n, d) enables historical-embedding re-scoring on hits;
    without it the cache replays the stored ranking (scores stale by one
    turn's drift).  ``depth >= k`` rows are cached per session (the
    engines over-fetch the backend to depth and serve/record only the
    top-k), so a hit rescoring a deeper candidate pool loses less
    recall — the Frieder et al. design.  ``threshold <= 0`` never hits
    (``enabled`` False) — the engines skip the cache path entirely,
    keeping disabled runs bit-identical to cache-absent ones.

    Thread safety: the hit/miss counters and the sequential-mode entry
    dict are guarded by an internal lock — in batched serving,
    ``count_hits`` runs on the pump thread at wave retirement while
    ``invalidate_docs`` arrives on client threads through
    ``delete_documents``.  Device work (``probe``/``fuse_wave``) runs
    outside the lock; slab-mode row state is guarded by the underlying
    ``SessionStore``'s own lock.
    """

    def __init__(self, *, d: int, k: int, threshold: float,
                 depth: Optional[int] = None,
                 corpus: Optional[jax.Array] = None,
                 n_slots: Optional[int] = None, mesh: Any = None,
                 dtype=jnp.float32):
        self.threshold = float(threshold)
        self.k = int(k)
        self.depth = max(int(depth or k), int(k))
        self.corpus = corpus
        self.rescore = corpus is not None
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self._template = entry_template(d, self.depth, dtype)
        self._entries: Dict[str, CacheEntry] = {}
        self._slab: Optional[_sessions.SessionStore] = None
        if n_slots is not None:
            self._slab = _sessions.SessionStore(self._template, n_slots,
                                                mesh=mesh)

    @property
    def enabled(self) -> bool:
        return self.threshold > 0.0

    def stats(self) -> Dict[str, float]:
        with self._lock:
            total = self.hits + self.misses
            return {"hits": self.hits, "misses": self.misses,
                    "hit_rate": (self.hits / total) if total else 0.0}

    def count_hits(self, hit: np.ndarray, b: int) -> None:
        """Fold a wave's materialized hit mask (first ``b`` rows are
        real requests) into the counters.  Separate from the fuse so the
        continuous-batching engine can defer the blocking ``device_get``
        of the mask to wave retirement instead of the launch path."""
        n_hit = int(np.asarray(hit)[:b].sum())
        with self._lock:
            self.hits += n_hit
            self.misses += b - n_hit

    # -- sequential (dict) mode ---------------------------------------

    def lookup(self, conv_id: str, q: jax.Array
               ) -> Optional[Tuple[jax.Array, jax.Array]]:
        """Probe ``conv_id``'s entry with q (d,); (scores (k,), ids
        (k,)) on a hit, None (counted as a miss) otherwise."""
        with self._lock:
            entry = self._entries.get(conv_id)
        if entry is None:
            with self._lock:
                self.misses += 1
            return None
        batched = jax.tree.map(lambda a: a[None], entry)
        hit, v, ids = probe(batched, q[None], out_k=self.k,
                            threshold=self.threshold,
                            rescore=self.rescore)
        if bool(jax.device_get(hit[0])):
            with self._lock:
                self.hits += 1
            return v[0], ids[0]
        with self._lock:
            self.misses += 1
        return None

    def update(self, conv_id: str, q: jax.Array, v: jax.Array,
               ids: jax.Array) -> None:
        """Refresh ``conv_id``'s entry with the turn's backend answer
        (``v``/``ids`` depth-wide)."""
        fresh = (_make_entries_rescore(q[None], v[None], ids[None],
                                       self.corpus) if self.rescore
                 else _make_entries_static(q[None], v[None], ids[None]))
        row = jax.tree.map(lambda a: a[0], fresh)
        with self._lock:
            self._entries[conv_id] = row

    def invalidate(self, conv_id: str) -> None:
        with self._lock:
            self._entries.pop(conv_id, None)

    def invalidate_docs(self, doc_ids) -> int:
        """Corpus-tombstone sweep: drop every entry whose cached
        candidate pool intersects ``doc_ids``, in both storage modes —
        after this, no later hit can serve or re-score a deleted
        document.  The engines call it on every ``delete_documents``
        (each corpus-epoch bump); returns entries/rows dropped.
        """
        dead = np.atleast_1d(np.asarray(doc_ids, np.int64))
        if dead.size == 0:
            return 0
        n = 0
        with self._lock:                               # sequential mode
            drop = [cid for cid, e in self._entries.items()
                    if np.isin(np.asarray(e.doc_ids), dead).any()]
            for cid in drop:
                del self._entries[cid]
            n += len(drop)
        if self._slab is not None:                     # slab mode
            slab = self._slab.slab
            ids = np.asarray(jax.device_get(slab.doc_ids))
            valid = np.asarray(jax.device_get(slab.valid))
            rows = np.flatnonzero(valid & np.isin(ids, dead).any(axis=-1))
            if rows.size:
                self._slab.clear(rows.tolist())
            n += int(rows.size)
        return n

    # -- batched (slab) mode ------------------------------------------

    def gather(self, slots: Sequence[int]) -> CacheEntry:
        return self._slab.gather(slots)

    def scatter(self, slots: Sequence[int], entries: CacheEntry) -> None:
        self._slab.scatter(slots, entries)

    def clear_slot(self, slot: int) -> None:
        """Slot-freed listener: wipe the slot's cache row."""
        self._slab.clear([slot])

    @property
    def wave_fuse(self) -> WaveFuse:
        """The batched-wave cache pass (``fuse_wave``) the session
        store's wave program runs over ``gather``-ed entries; the engine
        ``scatter``s the entries it returns."""
        return WaveFuse(self.k, self.threshold, self.rescore)
