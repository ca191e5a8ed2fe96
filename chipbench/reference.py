"""The plain reference that decides ``correct``, and its control.

The reference imports nothing of the program.  It takes the corpus and
the queries the benchmark generated from the seed, and for a sample of
the window's turns it computes

* the exact top-k of each query over the whole corpus (a blocked scan at
  HIGHEST precision, so only a (Q, block) score tile is live), and
* the score of every served id, as a float64 dot product on the host.

Two numbers are compared with the configuration's limits:

* ``score_err``: the mean gap between a served score and the float64
  score of the id served with it.  The configuration states float32
  scores at HIGHEST precision; a path that scores in a lower precision,
  or an answer whose ids and scores do not belong together, reads far
  above it.  The mean rather than the widest gap: on a v5e chip the
  control's mean reads about 16 times a sound run's, its widest gap 13
  times, and a CPU's float32 accumulation keeps the mean well apart too.
* ``recall_miss``: the share of the exact top-k that the served answers
  miss.  IVF is approximate by design; a broken session, selection or
  scan reads far above the configuration's sound runs.

The control is this reference put in the program's place, computed in
the precision below the configuration's float32-at-HIGHEST: three
bfloat16 passes (``Precision.HIGH`` on a TPU), written out here so that
it reads the same on any platform.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np


#: the gap read for an id that names no document, or is served twice
WRONG_ANSWER = 1.0e9


def _blocked_topk(docs, q, k: int, block: int, score):
    import jax
    import jax.numpy as jnp

    nb = docs.shape[0] // block

    def body(carry, i):
        v, ids = carry
        blk = jax.lax.dynamic_slice_in_dim(docs, i * block, block)
        bv, bi = jax.lax.top_k(score(q, blk), k)
        cv = jnp.concatenate([v, bv], axis=1)
        ci = jnp.concatenate([ids, bi + i * block], axis=1)
        tv, pos = jax.lax.top_k(cv, k)
        return (tv, jnp.take_along_axis(ci, pos, axis=1)), None

    init = (jnp.full((q.shape[0], k), -jnp.inf),
            jnp.full((q.shape[0], k), -1, jnp.int32))
    (v, ids), _ = jax.lax.scan(body, init, jnp.arange(nb))
    return v, ids


def _highest(q, blk):
    import jax
    import jax.numpy as jnp
    return jnp.einsum("qd,nd->qn", q, blk,
                      precision=jax.lax.Precision.HIGHEST)


def _bf16_part(x):
    """``x`` rounded to the nearest bfloat16 (ties to even), kept in
    float32.  Rounded on the bits rather than converted, so that no
    compiler may fold a float32 -> bfloat16 -> float32 round trip into
    the identity."""
    import jax
    import jax.numpy as jnp
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def _bf16x3(q, blk):
    """float32 product from three bfloat16 passes: hi*hi + hi*lo + lo*hi,
    each accumulated in float32 (the lo*lo pass of HIGHEST dropped)."""
    import jax
    import jax.numpy as jnp

    def split(x):
        hi = _bf16_part(x)
        lo = _bf16_part(x - hi)
        return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)

    (qh, ql), (bh, bl) = split(q), split(blk)

    def dot(a, b):
        return jnp.einsum("qd,nd->qn", a, b,
                          preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.DEFAULT)
    return dot(qh, bh) + dot(qh, bl) + dot(ql, bh)


def _block(n: int) -> int:
    block = min(n, 1 << 16)
    if n % block:
        raise ValueError(f"corpus of {n} rows is not a multiple of {block}")
    return block


def exact_topk(docs, queries: np.ndarray, k: int) -> np.ndarray:
    """Exact top-k ids (Q, k) over ``docs`` at HIGHEST precision."""
    import jax
    run = jax.jit(lambda d, q: _blocked_topk(d, q, k, _block(d.shape[0]),
                                             _highest)[1])
    return np.asarray(run(docs, jax.numpy.asarray(queries)))


def control_answers(docs, queries: np.ndarray, k: int
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """The control: exact top-k computed in three bfloat16 passes.
    Returns (scores (Q, k), ids (Q, k))."""
    import jax
    run = jax.jit(lambda d, q: _blocked_topk(d, q, k, _block(d.shape[0]),
                                             _bf16x3))
    v, i = run(docs, jax.numpy.asarray(queries))
    return np.asarray(v), np.asarray(i)


def served_scores_f64(docs, queries: np.ndarray, ids: np.ndarray
                      ) -> np.ndarray:
    """float64 score of every served id, (Q, k); ids outside the corpus
    read NaN."""
    import jax.numpy as jnp
    n = docs.shape[0]
    valid = (ids >= 0) & (ids < n)
    rows = np.asarray(jnp.take(docs, jnp.asarray(np.where(valid, ids, 0)),
                               axis=0)).astype(np.float64)
    s = np.einsum("qkd,qd->qk", rows, queries.astype(np.float64))
    return np.where(valid, s, np.nan)


def compare(docs, queries: np.ndarray, scores: np.ndarray,
            ids: np.ndarray, k: int) -> Dict[str, float]:
    """The numbers ``correct`` is decided on, for served (scores, ids)
    of ``queries`` (all (Q, ...) host arrays)."""
    exact = exact_topk(docs, queries, k)
    ref = served_scores_f64(docs, queries, ids)
    distinct = np.array([len(set(r.tolist())) == len(r) for r in ids])
    gap = np.abs(scores.astype(np.float64) - ref)
    # an id outside the corpus, or served twice, is a wrong answer: it
    # reads WRONG_ANSWER, far above any limit
    bad = ~np.isfinite(gap) | ~distinct[:, None]
    gap = np.where(bad, WRONG_ANSWER, gap)
    hits = [len(set(a.tolist()) & set(b.tolist()))
            for a, b in zip(ids, exact)]
    recall_miss = 1.0 - float(np.sum(hits)) / exact.size if len(hits) \
        else 0.0
    return {"score_err": float(gap.mean()),
            "score_err_max": float(gap.max()),
            "recall_miss": recall_miss}
