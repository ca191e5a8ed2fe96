"""TopLoc — the paper's contribution (§2), as a composable JAX module.

The session logic (centroid cache, Eq. 1 ``|I0|`` drift proxy, α·np
refresh, privileged HNSW entry points) is backend-agnostic; the concrete
backends live in ``core.backend`` as registered, jit-static dataclasses
(``IVFBackend``, ``IVFPQBackend``, ``HNSWBackend``, ``ExactBackend``).
This module holds what is shared across all of them:

  * the session pytrees (``IVFSession``, ``HNSWSession``) and the
    ``TurnStats`` work counters mirroring the paper's cost model —
    centroid distances (p for a full scan, h for a cached one), float
    doc distances (lists/re-rank), graph distances, and PQ code
    distances (ADC table-sum evaluations);
  * the **generic jitted drivers** — one compiled program per
    (backend, k) pair, replacing the old per-prefix clones:

      ``start(backend, index, q0, k=…)``        first utterance
      ``step(backend, index, sess, q, k=…)``    follow-up utterance
      ``plain(backend, index, q, k=…)``         stateless baseline turn
      ``start_batch / step_batch / plain_batch`` batched serving path
      ``conversation(backend, index, utterances, k=…, mode=…)``
                                                whole-conversation scan

  * batch-size-stable numeric helpers (``_bcast_centroid_scores``,
    ``make_cache_batch``, ``_adc_tables``, ``_scan_lists_pq``) keeping
    sequential, batched and sharded paths bit-identical.

The legacy prefixed entry points (``ivf_start``, ``ivf_pq_step_batch``,
``hnsw_conversation``, …) remain as thin aliases that emit a
``DeprecationWarning`` and forward to the registry drivers;
``tests/test_backend_registry.py`` pins alias == driver bit for bit.
"""
from __future__ import annotations

import functools
import sys
import warnings
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import ivf as _ivf
from repro.core import pq as _pq
from repro.kernels import ops as _kops


class IVFSession(NamedTuple):
    """Per-conversation TopLoc_IVF state (device resident)."""
    cache_ids: jax.Array    # (h,) int32 — global centroid ids of C0
    cache_vecs: jax.Array   # (h, d)     — gathered centroid vectors
    anchor_sel: jax.Array   # (np,) int32 — top_np(q0, C0), for Eq. 1
    refreshes: jax.Array    # () int32
    turn: jax.Array         # () int32


class HNSWSession(NamedTuple):
    """Per-conversation TopLoc_HNSW state."""
    entry_point: jax.Array  # () int32 — privileged entry node
    turn: jax.Array         # () int32


class TurnStats(NamedTuple):
    centroid_dists: jax.Array  # () int32
    list_dists: jax.Array      # () int32 — float doc distances (lists/rerank)
    graph_dists: jax.Array     # () int32
    code_dists: jax.Array      # () int32 — PQ ADC table-sum evaluations
    i0: jax.Array              # () int32 — |I0| (IVF+ only; -1 otherwise)
    refreshed: jax.Array       # () bool


def _zero_stats() -> TurnStats:
    z = jnp.asarray(0, jnp.int32)
    return TurnStats(z, z, z, z, jnp.asarray(-1, jnp.int32),
                     jnp.asarray(False))


# ---------------------------------------------------------------------------
# batch-size-stable numeric helpers
#
# The one subtlety of batched serving: a ``(B, d) @ (d, p)`` matmul
# lowers to a tiled reduction whose order differs from the sequential
# ``(p, d) @ (d,)`` matvec, so results would drift bitwise with batch
# size.  Broadcasting the static operand into the batch dim instead
# makes each row's dot_general reduce exactly like the matvec
# (tests/test_serving_batched.py pins this down).
# ---------------------------------------------------------------------------


def _bcast_centroid_scores(centroids: jax.Array, q: jax.Array) -> jax.Array:
    """(B, p) centroid scores, bit-identical per row to ``centroids @ q``."""
    b = q.shape[0]
    return jnp.einsum("bpd,bd->bp",
                      jnp.broadcast_to(centroids, (b,) + centroids.shape), q,
                      precision=_ivf.F32)


@functools.partial(jax.jit, static_argnames=("h",))
def make_cache_batch(index: _ivf.IVFIndex, q: jax.Array, *, h: int
                     ) -> Tuple[jax.Array, jax.Array]:
    """Batched ``ivf.make_cache``: C0 = top_h(q, C) per row. q: (B, d)."""
    cscores = _bcast_centroid_scores(index.centroids, q)
    _, ids = jax.lax.top_k(cscores, h)
    ids = ids.astype(jnp.int32)
    return ids, index.centroids[ids]


def _adc_tables(index: _pq.IVFPQIndex, q: jax.Array) -> jax.Array:
    """Per-query ADC lookup tables, (B, m, n_codes).

    Broadcasts the codewords into the batch dim (cf.
    ``_bcast_centroid_scores``) so each row's d_sub-length contractions
    are bit-identical at any batch size.
    """
    b = q.shape[0]
    m, n_codes, d_sub = index.codewords.shape
    qs = q.reshape(b, m, d_sub)
    cw = jnp.broadcast_to(index.codewords, (b,) + index.codewords.shape)
    return jnp.einsum("bmd,bmkd->bmk", qs, cw, precision=_ivf.F32)


def _scan_lists_pq(index: _pq.IVFPQIndex, q: jax.Array, sel: jax.Array,
                   k: int, rerank: int
                   ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """ADC-scan the selected PQ lists, exact-re-rank the top-R.

    q (B, d); sel (B, np).  Returns (top_v (B,k), top_i (B,k),
    code_dists (B,), rerank_dists (B,)).
    """
    nprobe = sel.shape[1]
    r = max(k, min(rerank, nprobe * index.lmax))
    tables = _adc_tables(index, q)
    cand_v, cand_ids = _kops.pq_adc_scan(tables, index.list_codes,
                                         index.list_ids, sel, r)
    # exact re-rank of the R survivors against the float corpus — the
    # only place uncompressed vectors are touched (R rows, not np·Lmax).
    # Explicit multiply-reduce, not a dot_general: XLA canonicalises the
    # unit batch dim away at B=1 and retiles the reduction (cf.
    # hnsw._dots), which would break sequential↔batched bit-identity.
    safe = jnp.maximum(cand_ids, 0)
    exact = jnp.sum(index.doc_vecs[safe] * q[:, None, :], axis=-1)
    exact = jnp.where(cand_ids >= 0, exact, -jnp.inf)
    top_v, pos = jax.lax.top_k(exact, k)
    top_i = jnp.take_along_axis(cand_ids, pos, axis=-1)
    code_d = jnp.sum(index.list_sizes[sel], axis=-1).astype(jnp.int32)
    rerank_d = jnp.sum((cand_ids >= 0), axis=-1).astype(jnp.int32)
    return top_v, top_i, code_d, rerank_d


# ---------------------------------------------------------------------------
# fused single-dispatch turn plugin (kernels.fused_turn)
# ---------------------------------------------------------------------------


import dataclasses as _dataclasses


@_dataclasses.dataclass(frozen=True)
class FusedTurn:
    """Opt-in plugin routing IVF-family turns through the fused Pallas
    megakernel (``kernels.fused_turn``): centroid scoring, probed-list
    scan/merge and (for quantized precisions or PQ) the exact re-rank run
    as ONE kernel dispatch instead of three.

    Precision contract (see ``kernels.fused_turn`` module docstring):
    ``precision="f32"`` is bit-identical to the 3-dispatch path — same
    ids, same scores, same ``TurnStats`` counters; ``"bf16"``/``"int8"``
    score stages 1–2 quantized but ALWAYS exact-re-rank the top
    ``k·over`` candidates in float32 inside the kernel, so returned
    scores are exact dots and recall@k is floored (fig8 pins ≥ 0.95×
    the float path).

    Frozen + hashable so it rides on the backend dataclass as a
    jit-static field.  ``mode=None`` follows ``kernels.ops`` dispatch:
    the pure-XLA oracle in ``kernels.ref`` off TPU, the compiled Pallas
    kernel on TPU; ``mode="interpret"`` runs the kernel body in the
    Pallas interpreter, ``mode="ref"`` forces the oracle.
    """

    precision: str = "f32"
    over: int = 2            # quantized candidate depth: r = k·over
    mode: Optional[str] = None

    # -- whole-turn entry points (stateless plain path) ---------------

    def turn_ivf(self, index: _ivf.IVFIndex, q: jax.Array, *,
                 nprobe: int, k: int):
        """Full single-dispatch turn: returns (v, i, sel, list_dists)."""
        v, i, sel = _kops.fused_turn(
            q, index.centroids, index.list_vecs, index.list_ids,
            nprobe=nprobe, k=k, over=self.over, precision=self.precision,
            mode=self.mode)
        real = jnp.sum(index.list_sizes[sel], axis=-1).astype(jnp.int32)
        return v, i, sel, real

    def turn_pq(self, index: _pq.IVFPQIndex, q: jax.Array, *,
                nprobe: int, k: int, rerank: int):
        """Full single-dispatch PQ turn: (v, i, sel, code_d, rerank_d)."""
        tables = _adc_tables(index, q)
        v, i, sel = _kops.fused_turn_pq(
            q, index.centroids, tables, index.list_codes, index.list_ids,
            index.doc_vecs, nprobe=nprobe, k=k, rerank=rerank,
            precision=self.precision, mode=self.mode)
        code_d = jnp.sum(index.list_sizes[sel], axis=-1).astype(jnp.int32)
        # every valid ADC candidate outranks the -inf pads, so the
        # re-ranked count is exactly min(r, candidates available)
        r = max(k, min(rerank, nprobe * index.lmax))
        rerank_d = jnp.minimum(r, code_d).astype(jnp.int32)
        return v, i, sel, code_d, rerank_d

    # -- list-scan entry points (cached/sessioned paths) --------------
    #
    # Stage 1 (centroid cache, Eq. 1 drift) stays in XLA on the
    # sessioned paths — only the scan+merge(+re-rank) stages fuse.

    def list_scan_ivf(self, index: _ivf.IVFIndex, q: jax.Array,
                      sel: jax.Array, k: int):
        """Drop-in for ``ivf._scan_lists``: (v, i, real_dists)."""
        v, i, _pos = _kops.fused_scan(
            q, index.list_vecs, index.list_ids, sel, k, over=self.over,
            precision=self.precision, mode=self.mode)
        real = jnp.sum(index.list_sizes[sel], axis=-1).astype(jnp.int32)
        return v, i, real

    def list_scan_pq(self, index: _pq.IVFPQIndex, q: jax.Array,
                     sel: jax.Array, k: int, rerank: int):
        """Drop-in for ``_scan_lists_pq``: (v, i, code_d, rerank_d)."""
        tables = _adc_tables(index, q)
        v, i, _pos = _kops.fused_scan_pq(
            tables, q, index.list_codes, index.list_ids, sel,
            index.doc_vecs, k, rerank=rerank, precision=self.precision,
            fuse_rerank=True, mode=self.mode)
        nprobe = sel.shape[1]
        code_d = jnp.sum(index.list_sizes[sel], axis=-1).astype(jnp.int32)
        r = max(k, min(rerank, nprobe * index.lmax))
        rerank_d = jnp.minimum(r, code_d).astype(jnp.int32)
        return v, i, code_d, rerank_d


# ---------------------------------------------------------------------------
# generic registry drivers — ONE jitted program per (backend, k) pair
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("backend", "k"))
def start(backend, index, q0: jax.Array, *, k: int):
    """First utterance through any registered backend.

    q0: (d,).  Returns (scores (k,), doc_ids (k,), session, stats).
    """
    return backend.start(index, q0, k=k)


@functools.partial(jax.jit, static_argnames=("backend", "k"))
def step(backend, index, sess, q: jax.Array, *, k: int):
    """Follow-up utterance. Returns (scores, doc_ids, session, stats)."""
    return backend.step(index, sess, q, k=k)


@functools.partial(jax.jit, static_argnames=("backend", "k"))
def plain(backend, index, q: jax.Array, *, k: int):
    """Stateless baseline turn. q: (d,). Returns (scores, doc_ids, stats)."""
    return backend.plain(index, q, k=k)


@functools.partial(jax.jit, static_argnames=("backend", "k"))
def start_batch(backend, index, q0: jax.Array, *, k: int):
    """Batched ``start``: B first utterances in one dispatch. q0: (B, d)."""
    return backend.start_batch(index, q0, k=k)


@functools.partial(jax.jit, static_argnames=("backend", "k"))
def step_batch(backend, index, sess, q: jax.Array, *, k: int,
               is_first: Optional[jax.Array] = None):
    """Batched ``step`` over B concurrent conversations.

    Session fields carry a leading batch dim (gathered from a
    ``serving.sessions.SessionStore`` slab); ``is_first`` ((B,) bool)
    marks rows whose slot is fresh — those run first-turn semantics as a
    forced refresh so the whole batch stays one uniform program.
    """
    return backend.step_batch(index, sess, q, k=k, is_first=is_first)


@functools.partial(jax.jit, static_argnames=("backend", "k"))
def plain_batch(backend, index, q: jax.Array, *, k: int):
    """Batched stateless baseline turn. q: (B, d)."""
    return backend.plain_batch(index, q, k=k)


@functools.partial(jax.jit, static_argnames=("backend", "k", "mode"))
def conversation(backend, index, utterances: jax.Array, *, k: int,
                 mode: str = "toploc"):
    """Run a (T, d) conversation through one strategy (benchmark path).

    mode: 'toploc' (sessioned; the backend's alpha/adaptive knobs pick
    the refresh flavour) or 'plain' (the stateless baseline every turn —
    turns run as one batch, which the batch-size-stable formulations
    keep bit-identical to per-turn dispatch).
    Returns (scores (T,k), ids (T,k), stats stacked over turns).
    """
    if mode == "plain":
        return backend.plain_batch(index, utterances, k=k)
    if mode != "toploc":
        raise ValueError(f"mode must be 'toploc' or 'plain', got {mode!r}")

    q0, rest = utterances[0], utterances[1:]
    v0, i0_, sess, st0 = backend.start(index, q0, k=k)

    def body(sess, q):
        v, i, sess, st = backend.step(index, sess, q, k=k)
        return sess, (v, i, st)

    _, (v, i, st) = jax.lax.scan(body, sess, rest)
    v = jnp.concatenate([v0[None], v])
    i = jnp.concatenate([i0_[None], i])
    stats = jax.tree.map(lambda a, b: jnp.concatenate([a[None], b]), st0, st)
    return v, i, stats


# ---------------------------------------------------------------------------
# deprecated prefixed aliases (pre-registry API)
#
# Every alias forwards to the exact registry driver path — bit-identity
# is pinned by tests/test_backend_registry.py — and warns so downstream
# callers migrate.  New code should build a ``core.backend`` dataclass
# once and call the generic drivers above.
#
# Warning policy: once per *call site* (caller filename:lineno), with
# ``stacklevel=2`` so the warning points at the caller, not the alias.
# A serving loop hammering one legacy entry point logs a single line
# instead of one per request; distinct call sites each still get their
# warning.  The ``__deprecated_alias__`` marker is what the analyzer's
# deprecated-alias pass keys on (``repro.analysis.deprecation``).
# ---------------------------------------------------------------------------

_warned_sites: set = set()


def _deprecated_alias(repl: str):
    """Mark a legacy ``toploc.*`` entry point; warn once per call site."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            frame = sys._getframe(1)
            site = (frame.f_code.co_filename, frame.f_lineno)
            if site not in _warned_sites:
                _warned_sites.add(site)
                warnings.warn(
                    f"toploc.{fn.__name__} is deprecated; use the "
                    f"core.backend registry: {repl}",
                    DeprecationWarning, stacklevel=2)
            return fn(*args, **kwargs)
        wrapper.__deprecated_alias__ = True
        return wrapper
    return deco


def _ivf_backend(**knobs):
    from repro.core import backend as _backend
    return _backend.IVFBackend(**knobs)


def _pq_backend(**knobs):
    from repro.core import backend as _backend
    return _backend.IVFPQBackend(**knobs)


def _hnsw_backend(**knobs):
    from repro.core import backend as _backend
    return _backend.HNSWBackend(**knobs)


@_deprecated_alias("start(IVFBackend(h=…, nprobe=…), …)")
def ivf_start(index, q0, *, h, nprobe, k, scan=None):
    return start(_ivf_backend(h=h, nprobe=nprobe, scan=scan), index, q0,
                 k=k)


@_deprecated_alias("step(IVFBackend(…, alpha=…), …)")
def ivf_step(index, sess, q, *, nprobe, k, alpha=-1.0, scan=None):
    return step(_ivf_backend(h=sess.cache_ids.shape[0], nprobe=nprobe,
                             alpha=alpha, scan=scan), index, sess, q, k=k)


@_deprecated_alias("start_batch(IVFBackend(…), …)")
def ivf_start_batch(index, q0, *, h, nprobe, k, scan=None):
    return start_batch(_ivf_backend(h=h, nprobe=nprobe, scan=scan), index,
                       q0, k=k)


@_deprecated_alias("step_batch(IVFBackend(…), …)")
def ivf_step_batch(index, sess, q, *, nprobe, k, alpha=-1.0, is_first=None,
                   scan=None):
    return step_batch(_ivf_backend(h=sess.cache_ids.shape[1], nprobe=nprobe,
                                   alpha=alpha, scan=scan), index, sess, q,
                      k=k, is_first=is_first)


@_deprecated_alias("plain_batch(IVFBackend(…), …)")
def ivf_plain_batch(index, q, *, nprobe, k, scan=None):
    return plain_batch(_ivf_backend(nprobe=nprobe, scan=scan), index, q,
                       k=k)


@_deprecated_alias("conversation(IVFBackend(…), …)")
def ivf_conversation(index, utterances, *, h, nprobe, k, alpha=-1.0,
                     mode="toploc", scan=None):
    return conversation(_ivf_backend(h=h, nprobe=nprobe, alpha=alpha,
                                     scan=scan), index, utterances, k=k,
                        mode=mode)


@_deprecated_alias("start(IVFPQBackend(…), …)")
def ivf_pq_start(index, q0, *, h, nprobe, k, rerank=32, scan=None):
    return start(_pq_backend(h=h, nprobe=nprobe, rerank=rerank, scan=scan),
                 index, q0, k=k)


@_deprecated_alias("step(IVFPQBackend(…), …)")
def ivf_pq_step(index, sess, q, *, nprobe, k, alpha=-1.0, rerank=32,
                scan=None):
    return step(_pq_backend(h=sess.cache_ids.shape[0], nprobe=nprobe,
                            alpha=alpha, rerank=rerank, scan=scan), index,
                sess, q, k=k)


@_deprecated_alias("start_batch(IVFPQBackend(…), …)")
def ivf_pq_start_batch(index, q0, *, h, nprobe, k, rerank=32, scan=None):
    return start_batch(_pq_backend(h=h, nprobe=nprobe, rerank=rerank,
                                   scan=scan), index, q0, k=k)


@_deprecated_alias("step_batch(IVFPQBackend(…), …)")
def ivf_pq_step_batch(index, sess, q, *, nprobe, k, alpha=-1.0, rerank=32,
                      is_first=None, scan=None):
    return step_batch(_pq_backend(h=sess.cache_ids.shape[1], nprobe=nprobe,
                                  alpha=alpha, rerank=rerank, scan=scan),
                      index, sess, q, k=k, is_first=is_first)


@_deprecated_alias("plain_batch(IVFPQBackend(…), …)")
def ivf_pq_plain_batch(index, q, *, nprobe, k, rerank=32, scan=None):
    return plain_batch(_pq_backend(nprobe=nprobe, rerank=rerank, scan=scan),
                       index, q, k=k)


@_deprecated_alias("conversation(IVFPQBackend(…), …)")
def ivf_pq_conversation(index, utterances, *, h, nprobe, k, alpha=-1.0,
                        rerank=32, mode="toploc", scan=None):
    return conversation(_pq_backend(h=h, nprobe=nprobe, alpha=alpha,
                                    rerank=rerank, scan=scan), index,
                        utterances, k=k, mode=mode)


@_deprecated_alias("start(HNSWBackend(ef=…, up=…), …)")
def hnsw_start(index, q0, *, ef, k, up=2, search=None):
    return start(_hnsw_backend(ef=ef, up=up, search=search), index, q0,
                 k=k)


@_deprecated_alias("step(HNSWBackend(…), …)")
def hnsw_step(index, sess, q, *, ef, k, adaptive=False, search=None):
    return step(_hnsw_backend(ef=ef, adaptive=adaptive, search=search),
                index, sess, q, k=k)


@_deprecated_alias("start_batch(HNSWBackend(…), …)")
def hnsw_start_batch(index, q0, *, ef, k, up=2, search=None):
    return start_batch(_hnsw_backend(ef=ef, up=up, search=search), index,
                       q0, k=k)


@_deprecated_alias("step_batch(HNSWBackend(…), …)")
def hnsw_step_batch(index, sess, q, *, ef, k, up=2, adaptive=False,
                    is_first=None, search=None):
    return step_batch(_hnsw_backend(ef=ef, up=up, adaptive=adaptive,
                                    search=search), index, sess, q, k=k,
                      is_first=is_first)


@_deprecated_alias("plain_batch(HNSWBackend(…), …)")
def hnsw_plain_batch(index, q, *, ef, k, search=None):
    return plain_batch(_hnsw_backend(ef=ef, search=search), index, q, k=k)


@_deprecated_alias("conversation(HNSWBackend(…), …)")
def hnsw_conversation(index, utterances, *, ef, k, up=2, mode="toploc",
                      search=None):
    adaptive = mode == "adaptive"
    return conversation(
        _hnsw_backend(ef=ef, up=up, adaptive=adaptive, search=search),
        index, utterances, k=k, mode="plain" if mode == "plain" else
        "toploc")
