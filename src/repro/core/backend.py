"""RetrievalBackend registry — one seam for every ANN backend.

The paper's TopLoc session logic (centroid cache, Eq. 1 ``|I0|`` drift
proxy, α·np refresh, privileged entry points) is backend-agnostic, yet
it used to be hand-copied into 12+ prefixed ``toploc.*`` entry points
(``ivf_start``, ``ivf_pq_step_batch``, ``hnsw_conversation``, …) with
every upper layer re-branching on backend strings.  This module
collapses the families behind one interface:

  * a backend is a **frozen, hashable dataclass** — it rides through
    ``jax.jit`` as a static argument, so the generic drivers
    (``toploc.start/step/plain(+_batch)/conversation``) compile one
    program per (backend, k) pair exactly as the prefixed clones did;
  * backend *knobs* (h, nprobe, alpha, rerank, ef, up, …) live on the
    dataclass; the *index* stays a pytree argument so sharded/device
    placement is orthogonal;
  * the IVF and IVF-PQ families share one implementation of the session
    machinery — only ``_list_scan`` differs (float posting lists vs
    ADC over PQ codes + exact re-rank), which is the whole point of the
    paper's backend-agnostic formulation;
  * ``session_template`` gives ``serving.sessions.SessionStore`` its
    slab layout; ``corpus_vectors`` gives the serving result cache its
    re-scoring source; ``index_kwarg``/``stateful`` let the engines
    stay entirely free of ``backend == "..."`` branches.

Registering a new backend:

    @register
    @dataclasses.dataclass(frozen=True)
    class MyBackend(RetrievalBackend):
        name: ClassVar[str] = "my"
        index_kwarg: ClassVar[str] = "my_index"
        ...knob fields...
        def start(self, index, q0, *, k): ...

and every layer — both serving engines, the session store, the result
cache, the benchmarks — picks it up through ``backend.make(...)``.

Bit-identity contract: the methods below are the *same formulations*
(same ops, same reduction shapes) as the legacy prefixed entry points,
which remain as deprecated aliases; ``tests/test_backend_registry.py``
pins registry == legacy bit for bit for all three backends across
sequential / batched / conversation drivers.
"""
from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, Dict, Optional, Tuple, Type

import jax
import jax.numpy as jnp

from repro.core import hnsw as _hnsw
from repro.core import ivf as _ivf
from repro.core import toploc as _tl
from repro.core.topk import intersect_count
from repro.kernels import ops as _kops
from repro.kernels import tiling
from repro.kernels.fused_turn import ROW_TILE as _ROW_TILE


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Type["RetrievalBackend"]] = {}


def register(cls: Type["RetrievalBackend"]) -> Type["RetrievalBackend"]:
    """Class decorator: make ``cls`` resolvable by ``get``/``make``."""
    _REGISTRY[cls.name] = cls
    return cls


def names() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get(name: str) -> Type["RetrievalBackend"]:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown retrieval backend {name!r}; registered: "
            f"{', '.join(names())}") from None


def _knob_fields(cls: Type["RetrievalBackend"]) -> set:
    return {f.name for f in dataclasses.fields(cls)}


def make(name: str, *, strict: bool = False,
         **knobs: Any) -> "RetrievalBackend":
    """Build a backend from a flat knob mapping (e.g. a ServingConfig's
    fields): knobs the backend does not declare are ignored, so one
    config dataclass can parameterise every backend.

    A knob that no *registered* backend declares is a typo, not a
    cross-backend knob, and raises even on the lenient path (a typo'd
    ``nprob=16`` used to yield a default-nprobe backend with no signal).
    ``strict=True`` (user-facing callers) additionally rejects knobs
    this backend doesn't declare itself.
    """
    cls = get(name)
    fields = _knob_fields(cls)
    if strict:
        unknown = sorted(set(knobs) - fields)
        if unknown:
            raise TypeError(
                f"make({name!r}, strict=True): unknown knob(s) "
                f"{', '.join(unknown)}; {cls.__name__} declares "
                f"{', '.join(sorted(fields))}")
        return cls(**knobs)
    union = set().union(*(_knob_fields(c) for c in _REGISTRY.values()))
    unknown = sorted(set(knobs) - union)
    if unknown:
        raise TypeError(
            f"make({name!r}): knob(s) {', '.join(unknown)} match no "
            f"registered backend's fields (likely a typo); known knobs: "
            f"{', '.join(sorted(union))}")
    return cls(**{k: v for k, v in knobs.items() if k in fields})


# ---------------------------------------------------------------------------
# shared IVF-family implementation (float and PQ lists)
#
# ``list_scan(index, q (B,d), sel (B,np), k)`` -> (top_v (B,k),
# top_i (B,k), list_dists (B,), code_dists (B,)) abstracts the only
# thing that differs between TopLoc_IVF and TopLoc_IVFPQ; everything
# session-shaped below is written once.
# ---------------------------------------------------------------------------


def _ivf_family_start(index, q0, *, h, nprobe, k, list_scan):
    """First utterance: full centroid scan, C0 = top_h(q0, C), answer."""
    cache_ids, cache_vecs = _ivf.make_cache(index, q0, h=h)
    # top_np(q0, C0) == top_np(q0, C) since C0 holds q0's h best centroids
    anchor_sel = cache_ids[:nprobe]
    top_v, top_i, list_d, code_d = list_scan(index, q0[None],
                                             anchor_sel[None], k)
    sess = _tl.IVFSession(cache_ids, cache_vecs, anchor_sel,
                          jnp.asarray(0, jnp.int32),
                          jnp.asarray(1, jnp.int32))
    stats = _tl.TurnStats(
        centroid_dists=jnp.asarray(index.p, jnp.int32),
        list_dists=list_d[0],
        graph_dists=jnp.asarray(0, jnp.int32),
        code_dists=code_d[0],
        i0=jnp.asarray(-1, jnp.int32),
        refreshed=jnp.asarray(True),
    )
    return top_v[0], top_i[0], sess, stats


def _ivf_family_step(index, sess, q, *, nprobe, k, alpha, list_scan):
    """Follow-up utterance: cached centroid selection, Eq. 1 drift check
    (``alpha < 0`` static cache, ``alpha >= 0`` refresh), one list scan.

    The drift check runs *before* any posting list is scanned, so a
    refreshed turn pays (h + p) centroid distances but only one scan.
    """
    h = sess.cache_ids.shape[0]
    # 1. centroid selection against the cached set C0  (cost: h)
    csims = jnp.matmul(sess.cache_vecs, q, precision=_ivf.F32)   # (h,)
    _, sel_local = jax.lax.top_k(csims, nprobe)
    sel_cached = sess.cache_ids[sel_local]           # (np,) global ids

    # 2. drift proxy |I0| = |top_np(qj, C0) ∩ top_np(q0, C0)|   (Eq. 1)
    i0 = intersect_count(sel_cached, sess.anchor_sel)
    need_refresh = (alpha >= 0.0) & (i0 < jnp.asarray(alpha * nprobe))

    # 3. optional refresh: rescan the full centroid set, re-anchor on qj
    def refreshed(_):
        cache_ids, cache_vecs = _ivf.make_cache(index, q, h=h)
        return cache_ids, cache_vecs, cache_ids[:nprobe], cache_ids[:nprobe]

    def kept(_):
        return sess.cache_ids, sess.cache_vecs, sess.anchor_sel, sel_cached

    cache_ids, cache_vecs, anchor_sel, sel = jax.lax.cond(
        need_refresh, refreshed, kept, None)

    # 4. one posting-list scan with the final selection
    top_v, top_i, list_d, code_d = list_scan(index, q[None], sel[None], k)

    new_sess = _tl.IVFSession(cache_ids, cache_vecs, anchor_sel,
                              sess.refreshes + need_refresh.astype(jnp.int32),
                              sess.turn + 1)
    stats = _tl.TurnStats(
        centroid_dists=jnp.asarray(h, jnp.int32)
        + need_refresh.astype(jnp.int32) * index.p,
        list_dists=list_d[0],
        graph_dists=jnp.asarray(0, jnp.int32),
        code_dists=code_d[0],
        i0=i0,
        refreshed=need_refresh,
    )
    return top_v[0], top_i[0], new_sess, stats


def _ivf_family_start_batch(index, q0, *, h, nprobe, k, list_scan):
    """Batched first utterances: B conversations in one dispatch."""
    b = q0.shape[0]
    cache_ids, cache_vecs = _tl.make_cache_batch(index, q0, h=h)
    anchor_sel = cache_ids[:, :nprobe]
    top_v, top_i, list_d, code_d = list_scan(index, q0, anchor_sel, k)
    sess = _tl.IVFSession(cache_ids, cache_vecs, anchor_sel,
                          jnp.zeros((b,), jnp.int32),
                          jnp.ones((b,), jnp.int32))
    stats = _tl.TurnStats(
        centroid_dists=jnp.full((b,), index.p, jnp.int32),
        list_dists=list_d,
        graph_dists=jnp.zeros((b,), jnp.int32),
        code_dists=code_d,
        i0=jnp.full((b,), -1, jnp.int32),
        refreshed=jnp.ones((b,), bool),
    )
    return top_v, top_i, sess, stats


def _ivf_family_step_batch(index, sess, q, *, nprobe, k, alpha, is_first,
                           list_scan):
    """Batched follow-ups over B concurrent conversations.

    ``is_first`` ((B,) bool) rows ignore the slot contents, pay a full
    centroid scan, and re-anchor — exactly first-turn semantics realised
    as a forced refresh so the whole batch stays one uniform program.
    Per-row logic is select-only (no per-row ``lax.cond``); the refresh
    scan itself is gated on the *batch-wide* predicate so steady-state
    follow-up flushes stay O(B·h) instead of O(B·p).
    """
    b, h = sess.cache_ids.shape
    csims = jnp.einsum("bhd,bd->bh", sess.cache_vecs, q,
                       precision=_ivf.F32)
    _, sel_local = jax.lax.top_k(csims, nprobe)
    sel_cached = jnp.take_along_axis(sess.cache_ids, sel_local, axis=1)

    i0 = jax.vmap(intersect_count)(sel_cached, sess.anchor_sel)
    drift = (alpha >= 0.0) & (i0 < jnp.asarray(alpha * nprobe))

    first = (jnp.zeros((b,), bool) if is_first is None else is_first)
    refresh = first | drift

    if is_first is not None or alpha >= 0.0:
        fresh_ids, fresh_vecs = jax.lax.cond(
            jnp.any(refresh),
            lambda: _tl.make_cache_batch(index, q, h=h),
            lambda: (jnp.zeros((b, h), jnp.int32),
                     jnp.zeros((b, h) + index.centroids.shape[1:],
                               index.centroids.dtype)))
        r1 = refresh[:, None]
        cache_ids = jnp.where(r1, fresh_ids, sess.cache_ids)
        cache_vecs = jnp.where(r1[..., None], fresh_vecs, sess.cache_vecs)
        anchor_sel = jnp.where(r1, fresh_ids[:, :nprobe], sess.anchor_sel)
        sel = jnp.where(r1, fresh_ids[:, :nprobe], sel_cached)
    else:
        cache_ids, cache_vecs = sess.cache_ids, sess.cache_vecs
        anchor_sel, sel = sess.anchor_sel, sel_cached

    top_v, top_i, list_d, code_d = list_scan(index, q, sel, k)

    step_refresh = drift & ~first      # first turns don't count as refreshes
    new_sess = _tl.IVFSession(
        cache_ids, cache_vecs, anchor_sel,
        jnp.where(first, 0, sess.refreshes + step_refresh.astype(jnp.int32)),
        jnp.where(first, 1, sess.turn + 1))
    stats = _tl.TurnStats(
        centroid_dists=jnp.where(
            first, index.p,
            h + step_refresh.astype(jnp.int32) * index.p).astype(jnp.int32),
        list_dists=list_d,
        graph_dists=jnp.zeros((b,), jnp.int32),
        code_dists=code_d,
        i0=jnp.where(first, -1, i0),
        refreshed=refresh,
    )
    return top_v, top_i, new_sess, stats


def _ivf_family_plain_batch(index, q, *, nprobe, k, list_scan):
    """Stateless baseline turn: full centroid scan, one list scan."""
    b = q.shape[0]
    cscores = _tl._bcast_centroid_scores(index.centroids, q)
    _, sel = jax.lax.top_k(cscores, nprobe)
    top_v, top_i, list_d, code_d = list_scan(index, q, sel, k)
    stats = _tl.TurnStats(
        centroid_dists=jnp.full((b,), index.p, jnp.int32),
        list_dists=list_d,
        graph_dists=jnp.zeros((b,), jnp.int32),
        code_dists=code_d,
        i0=jnp.full((b,), -1, jnp.int32),
        refreshed=jnp.zeros((b,), bool),
    )
    return top_v, top_i, stats


# ---------------------------------------------------------------------------
# backends
# ---------------------------------------------------------------------------


class RetrievalBackend:
    """Interface + shared glue for registered backends.

    Subclasses are frozen dataclasses (hashable ⇒ jit-static) exposing:
      ``start(index, q0, *, k)``               → (v, i, sess, stats)
      ``step(index, sess, q, *, k)``           → (v, i, sess, stats)
      ``plain(index, q, *, k)``                → (v, i, stats)
      ``start_batch / step_batch / plain_batch`` — leading batch dim;
        ``step_batch`` takes ``is_first`` ((B,) bool or None)
      ``session_template(index)``              → single-session pytree
        (None for stateless backends)
      ``corpus_vectors(index)``                → (n, d) float rows for
        result-cache re-scoring, or None if the index keeps no flat
        corpus
    ``stats`` are always ``toploc.TurnStats`` (the paper's cost model).
    """

    name: ClassVar[str] = "?"
    index_kwarg: ClassVar[str] = "?"       # engine kwarg holding the index
    stateful: ClassVar[bool] = True        # has per-conversation sessions

    def plain(self, index, q, *, k):
        """Single-query plain turn — B=1 through the (batch-size-stable)
        batched path, so sequential and batched serving stay
        bit-identical."""
        v, i, st = self.plain_batch(index, q[None], k=k)
        return v[0], i[0], jax.tree.map(lambda a: a[0], st)

    def start(self, index, q0, *, k):
        raise NotImplementedError(f"{self.name} backend is stateless")

    def step(self, index, sess, q, *, k):
        raise NotImplementedError(f"{self.name} backend is stateless")

    def start_batch(self, index, q0, *, k):
        raise NotImplementedError(f"{self.name} backend is stateless")

    def step_batch(self, index, sess, q, *, k, is_first=None):
        raise NotImplementedError(f"{self.name} backend is stateless")

    def session_template(self, index) -> Optional[Any]:
        return None

    def corpus_vectors(self, index) -> Optional[jax.Array]:
        return None

    def place(self, index):
        """``index`` laid out for this backend's scan.  The engines call
        it once, when they place an index; identity unless a Pallas
        kernel scans the index (``IVFBackend.place``)."""
        return index

    def query_dim(self, index) -> int:
        """Embedding dimensionality queries against ``index`` must have."""
        raise NotImplementedError

    def fetch_limit(self, index) -> int:
        """Largest per-query result depth a turn can request while
        executing the *same* program a plain k-request would (same
        candidate pool, so the top-k prefix is unchanged).  The serving
        result cache clamps its over-fetch depth to this."""
        raise NotImplementedError


@register
@dataclasses.dataclass(frozen=True)
class IVFBackend(RetrievalBackend):
    """TopLoc_IVF / TopLoc_IVF+ over float posting lists.

    ``alpha < 0`` → static centroid cache (TopLoc_IVF); ``alpha >= 0`` →
    Eq. 1 refresh at ``|I0| < alpha·nprobe`` (TopLoc_IVF+).  ``scan``
    optionally replaces the posting-list scan (signature of
    ``ivf._scan_lists``; sharded: ``distributed.retrieval.ShardedIVFScan``).
    ``fused`` (a ``toploc.FusedTurn``) routes the scan — and, on the
    stateless plain path, the whole turn — through the single-dispatch
    Pallas megakernel; ``scan`` wins if both are set (the sharded scan
    carries its own fused plugin).
    """

    name: ClassVar[str] = "ivf"
    index_kwarg: ClassVar[str] = "ivf_index"

    h: int = 1024
    nprobe: int = 64
    alpha: float = -1.0
    scan: Any = None
    fused: Any = None

    def _list_scan(self, index, q, sel, k):
        if self.scan is not None:
            v, i, real = self.scan(index, q, sel, k)
        elif self.fused is not None:
            v, i, real = self.fused.list_scan_ivf(index, q, sel, k)
        else:
            v, i, real = _ivf._scan_lists(index, q, sel, k)
        return v, i, real, jnp.zeros_like(real)

    def start(self, index, q0, *, k):
        return _ivf_family_start(index, q0, h=self.h, nprobe=self.nprobe,
                                 k=k, list_scan=self._list_scan)

    def step(self, index, sess, q, *, k):
        return _ivf_family_step(index, sess, q, nprobe=self.nprobe, k=k,
                                alpha=self.alpha, list_scan=self._list_scan)

    def start_batch(self, index, q0, *, k):
        return _ivf_family_start_batch(index, q0, h=self.h,
                                       nprobe=self.nprobe, k=k,
                                       list_scan=self._list_scan)

    def step_batch(self, index, sess, q, *, k, is_first=None):
        return _ivf_family_step_batch(index, sess, q, nprobe=self.nprobe,
                                      k=k, alpha=self.alpha,
                                      is_first=is_first,
                                      list_scan=self._list_scan)

    def plain_batch(self, index, q, *, k):
        if self.fused is not None and self.scan is None:
            # whole turn in one kernel dispatch: centroid scoring, probe
            # selection, list scan/merge (and re-rank) never leave VMEM
            b = q.shape[0]
            v, i, _sel, real = self.fused.turn_ivf(index, q,
                                                   nprobe=self.nprobe, k=k)
            stats = _tl.TurnStats(
                centroid_dists=jnp.full((b,), index.p, jnp.int32),
                list_dists=real,
                graph_dists=jnp.zeros((b,), jnp.int32),
                code_dists=jnp.zeros((b,), jnp.int32),
                i0=jnp.full((b,), -1, jnp.int32),
                refreshed=jnp.zeros((b,), bool),
            )
            return v, i, stats
        return _ivf_family_plain_batch(index, q, nprobe=self.nprobe, k=k,
                                       list_scan=self._list_scan)

    def session_template(self, index):
        return _tl.IVFSession(
            cache_ids=jnp.zeros((self.h,), jnp.int32),
            cache_vecs=jnp.zeros((self.h, index.d), index.centroids.dtype),
            anchor_sel=jnp.zeros((self.nprobe,), jnp.int32),
            refreshes=jnp.zeros((), jnp.int32),
            turn=jnp.zeros((), jnp.int32))

    def query_dim(self, index) -> int:
        return index.d

    def fetch_limit(self, index) -> int:
        # the float scan's candidate pool: every slot of every probed list
        return self.nprobe * index.lmax

    def _kernel_scans(self) -> bool:
        """Does a Pallas list kernel (not its XLA reference) scan the
        lists?  The sharded scan carries the same ``fused`` plugin."""
        return (self.fused is not None and
                (self.fused.mode or _kops.default_mode()) != "ref")

    def _pad_lists(self, index, payload: str, fill):
        lpad = tiling.list_len(index.lmax)
        return index._replace(**{
            payload: tiling.pad_rows_blocked(getattr(index, payload),
                                             lpad, fill),
            "list_ids": tiling.pad_rows_blocked(index.list_ids, lpad, -1)})

    def place(self, index):
        """Pad the list axis once to the length the Pallas list kernels
        stream (``tiling.list_len``) when one of them scans it, so the
        kernel wrappers scan in place instead of copying every list on
        each call.  Pad slots carry id -1.  The XLA scan reads the
        stored length and gets the index as built."""
        if not self._kernel_scans():
            return index
        return self._pad_lists(index, "list_vecs", 0.0)


@register
@dataclasses.dataclass(frozen=True)
class IVFPQBackend(IVFBackend):
    """TopLoc_IVFPQ: identical session machinery, PQ-compressed lists.

    Lists are ADC-scanned (``kernels.ops.pq_adc_scan``) and the top-R
    candidates exact-re-ranked against the float corpus; ``list_dists``
    counts the R re-rank dots, ``code_dists`` the ADC table-sums.
    ``scan`` replaces the whole ADC-scan + re-rank stage (signature of
    ``toploc._scan_lists_pq``; sharded: ``ShardedPQScan``).
    """

    name: ClassVar[str] = "ivf_pq"
    index_kwarg: ClassVar[str] = "ivf_pq_index"

    rerank: int = 64

    def _list_scan(self, index, q, sel, k):
        if self.scan is not None:
            v, i, code_d, rerank_d = self.scan(index, q, sel, k, self.rerank)
        elif self.fused is not None:
            v, i, code_d, rerank_d = self.fused.list_scan_pq(
                index, q, sel, k, self.rerank)
        else:
            v, i, code_d, rerank_d = _tl._scan_lists_pq(
                index, q, sel, k, self.rerank)
        return v, i, rerank_d, code_d

    def plain_batch(self, index, q, *, k):
        if self.fused is not None and self.scan is None:
            b = q.shape[0]
            v, i, _sel, code_d, rerank_d = self.fused.turn_pq(
                index, q, nprobe=self.nprobe, k=k, rerank=self.rerank)
            stats = _tl.TurnStats(
                centroid_dists=jnp.full((b,), index.p, jnp.int32),
                list_dists=rerank_d,
                graph_dists=jnp.zeros((b,), jnp.int32),
                code_dists=code_d,
                i0=jnp.full((b,), -1, jnp.int32),
                refreshed=jnp.zeros((b,), bool),
            )
            return v, i, stats
        return _ivf_family_plain_batch(index, q, nprobe=self.nprobe, k=k,
                                       list_scan=self._list_scan)

    def corpus_vectors(self, index):
        return index.doc_vecs

    def _kernel_scans(self) -> bool:
        # unfused and unsharded, lists go through the Pallas ADC kernel
        if self.fused is None and self.scan is None:
            return _kops.default_mode() != "ref"
        return super()._kernel_scans()

    def place(self, index):
        """As ``IVFBackend.place`` for the code lists; the fused
        single-device turn also DMAs its re-rank rows as whole
        ``ROW_TILE``-row tiles, so the corpus rows are padded to a
        multiple of that once here (pad rows are never a candidate)."""
        if not self._kernel_scans():
            return index
        index = self._pad_lists(index, "list_codes", 0)
        if self.fused is not None and self.scan is None:
            n = index.doc_vecs.shape[0]
            index = index._replace(doc_vecs=tiling.pad_axis(
                index.doc_vecs, 0, -(-n // _ROW_TILE) * _ROW_TILE, 0.0))
        return index

    def fetch_limit(self, index) -> int:
        # asking for k beyond this would widen the exact re-rank pool
        # (``r = max(k, min(rerank, np·Lmax))`` in ``_scan_lists_pq``),
        # changing which candidates the top-k is drawn from
        return min(self.rerank, self.nprobe * index.lmax)


@register
@dataclasses.dataclass(frozen=True)
class HNSWBackend(RetrievalBackend):
    """TopLoc_HNSW: privileged entry point, first-turn ef upscaling.

    ``adaptive=True`` is the beyond-paper extension re-anchoring the
    entry point at every turn's top-1.  ``search`` optionally replaces
    ``hnsw.search`` (sharded: ``ShardedHNSWSearch``).
    """

    name: ClassVar[str] = "hnsw"
    index_kwarg: ClassVar[str] = "hnsw_index"

    ef: int = 64
    up: int = 2
    adaptive: bool = False
    search: Any = None

    def _search(self):
        return self.search or _hnsw.search

    def start(self, index, q0, *, k):
        v, i, nd = self._search()(index, q0[None], ef=self.up * self.ef,
                                  k=k)
        sess = _tl.HNSWSession(entry_point=i[0, 0].astype(jnp.int32),
                               turn=jnp.asarray(1, jnp.int32))
        stats = _tl._zero_stats()._replace(graph_dists=nd[0],
                                           refreshed=jnp.asarray(True))
        return v[0], i[0], sess, stats

    def step(self, index, sess, q, *, k):
        v, i, nd = self._search()(
            index, q[None], ef=self.ef, k=k,
            entry_override=sess.entry_point[None],
            use_entry_override=True)
        new_entry = (i[0, 0].astype(jnp.int32) if self.adaptive
                     else sess.entry_point)
        sess = _tl.HNSWSession(entry_point=new_entry, turn=sess.turn + 1)
        stats = _tl._zero_stats()._replace(graph_dists=nd[0])
        return v[0], i[0], sess, stats

    def start_batch(self, index, q0, *, k):
        b = q0.shape[0]
        v, i, nd = self._search()(index, q0, ef=self.up * self.ef, k=k)
        sess = _tl.HNSWSession(entry_point=i[:, 0].astype(jnp.int32),
                               turn=jnp.ones((b,), jnp.int32))
        z = jnp.zeros((b,), jnp.int32)
        stats = _tl.TurnStats(z, z, nd, z, jnp.full((b,), -1, jnp.int32),
                              jnp.ones((b,), bool))
        return v, i, sess, stats

    def step_batch(self, index, sess, q, *, k, is_first=None):
        b = q.shape[0]
        do_search = self._search()
        v, i, nd = do_search(index, q, ef=self.ef, k=k,
                             entry_override=sess.entry_point,
                             use_entry_override=True)
        if is_first is not None:
            # batch-wide gate: steady-state flushes (no first turns) skip
            # the full-descent upscaled search entirely
            v0, i_0, nd0 = jax.lax.cond(
                jnp.any(is_first),
                lambda: do_search(index, q, ef=self.up * self.ef, k=k),
                lambda: (jnp.zeros((b, k), index.vectors.dtype),
                         jnp.zeros((b, k), jnp.int32),
                         jnp.zeros((b,), jnp.int32)))
            f1 = is_first[:, None]
            v = jnp.where(f1, v0, v)
            i = jnp.where(f1, i_0, i)
            nd = jnp.where(is_first, nd0, nd)
            first = is_first
        else:
            first = jnp.zeros((b,), bool)

        top1 = i[:, 0].astype(jnp.int32)
        new_entry = top1 if self.adaptive else jnp.where(first, top1,
                                                         sess.entry_point)
        new_sess = _tl.HNSWSession(entry_point=new_entry,
                                   turn=jnp.where(first, 1, sess.turn + 1))
        z = jnp.zeros((b,), jnp.int32)
        stats = _tl.TurnStats(z, z, nd, z, jnp.full((b,), -1, jnp.int32),
                              first)
        return v, i, new_sess, stats

    def plain_batch(self, index, q, *, k):
        b = q.shape[0]
        v, i, nd = self._search()(index, q, ef=self.ef, k=k)
        z = jnp.zeros((b,), jnp.int32)
        stats = _tl.TurnStats(z, z, nd, z, jnp.full((b,), -1, jnp.int32),
                              jnp.zeros((b,), bool))
        return v, i, stats

    def session_template(self, index):
        return _tl.HNSWSession(entry_point=jnp.zeros((), jnp.int32),
                               turn=jnp.zeros((), jnp.int32))

    def corpus_vectors(self, index):
        return index.vectors

    def query_dim(self, index) -> int:
        return index.vectors.shape[1]

    def fetch_limit(self, index) -> int:
        # the level-0 beam holds ef candidates; top_k beyond that is
        # unsatisfiable (first turns search wider at up·ef, but every
        # follow-up is capped at ef)
        return self.ef


@register
@dataclasses.dataclass(frozen=True)
class ExactBackend(RetrievalBackend):
    """Brute-force top-k over the full collection (the paper's 'Exact'
    row).  Stateless: the engines route every strategy through
    ``plain``; its index is the raw ``(n, d)`` doc-vector array."""

    name: ClassVar[str] = "exact"
    index_kwarg: ClassVar[str] = "doc_vecs"
    stateful: ClassVar[bool] = False

    def plain_batch(self, index, q, *, k):
        b = q.shape[0]
        v, i = _ivf.exact_search(index, q, k)
        z = jnp.zeros((b,), jnp.int32)
        stats = _tl.TurnStats(z, z, z, z, jnp.full((b,), -1, jnp.int32),
                              jnp.zeros((b,), bool))
        return v, i, stats

    def corpus_vectors(self, index):
        return index

    def query_dim(self, index) -> int:
        return index.shape[1]

    def fetch_limit(self, index) -> int:
        return index.shape[0]
