"""Golden cost-model regression: pinned TurnStats for all three backends.

The paper's speedup claims reduce to the work counters; an optimization
that silently changes them (scans a different number of lists, skips the
drift check, re-ranks a different depth) would invalidate the reported
accounting even if retrieval quality looks fine.  This test pins the
per-turn counters of a fixed-seed 8-turn conversation on IVF, IVF-PQ
and HNSW so any such change fails loudly and must be justified in
review.  On the IVF family it pins what k-means float output cannot
move (centroid work, the refresh schedule, the re-rank depth) and
checks the rest by identity against the selections the run made.

The pinned values also encode the PQ cost-model identity: TopLoc_IVFPQ
pays the same centroid work and the same |I0| refresh schedule as float
TopLoc_IVF, its ``code_dists`` equal the float backend's ``list_dists``
(same posting lists, scanned compressed), and its float ``list_dists``
collapse to the re-rank depth R.

Determinism scope: fixed seeds end-to-end (workload, k-means, PQ
codebooks, HNSW build) on the CPU backend CI runs — the same platform
the tier-1 suite targets.
"""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import hnsw, ivf, pq, toploc
from repro.core.backend import HNSWBackend, IVFBackend, IVFPQBackend

H, NPROBE, K, ALPHA, RERANK, EF, UP = 16, 4, 10, 0.3, 32, 16, 2
IVF_BK = IVFBackend(h=H, nprobe=NPROBE, alpha=ALPHA)
PQ_BK = IVFPQBackend(h=H, nprobe=NPROBE, alpha=ALPHA, rerank=RERANK)
HNSW_BK = HNSWBackend(ef=EF, up=UP)

# The pinned IVF-family counters are the ones k-means float output does
# not move: centroid work and the refresh schedule.  The list work and
# |I0| depend on the partitioning the installed jax's k-means produces,
# so they are checked by identity instead (``_check_ivf_family``).
GOLD_IVF = {
    "centroid_dists": [32, 16, 16, 16, 16, 48, 16, 16],
    "graph_dists": [0, 0, 0, 0, 0, 0, 0, 0],
    "code_dists": [0, 0, 0, 0, 0, 0, 0, 0],
    "refreshed": [1, 0, 0, 0, 0, 1, 0, 0],
}
GOLD_IVF_PQ = {
    "centroid_dists": [32, 16, 16, 16, 16, 48, 16, 16],
    "list_dists": [32, 32, 32, 32, 32, 32, 32, 32],      # = RERANK
    "graph_dists": [0, 0, 0, 0, 0, 0, 0, 0],
    "refreshed": [1, 0, 0, 0, 0, 1, 0, 0],
}
GOLD_HNSW = {
    "centroid_dists": [0, 0, 0, 0, 0, 0, 0, 0],
    "list_dists": [0, 0, 0, 0, 0, 0, 0, 0],
    "graph_dists": [315, 186, 178, 164, 183, 173, 178, 169],
    "code_dists": [0, 0, 0, 0, 0, 0, 0, 0],
    "i0": [-1, -1, -1, -1, -1, -1, -1, -1],
    "refreshed": [1, 0, 0, 0, 0, 0, 0, 0],
}


@pytest.fixture(scope="module")
def golden_setup():
    from repro.data import synthetic as SY
    wl = SY.make_workload(SY.WorkloadConfig(
        n_docs=1500, d=32, n_topics=12, n_conversations=2,
        turns_per_conversation=8, query_drift=0.15, walk_step=0.05,
        shift_prob=0.15, seed=7))
    fidx = ivf.build(jnp.asarray(wl.doc_vecs), p=32, iters=5,
                     key=jax.random.PRNGKey(0))
    pqi = pq.build_ivf_pq(fidx, jnp.asarray(wl.doc_vecs), m=8, iters=6,
                          key=jax.random.PRNGKey(0))
    hidx = hnsw.build(wl.doc_vecs[:800], m=8, ef_construction=32, seed=0)
    return jnp.asarray(wl.conversations[0]), fidx, pqi, hidx


def _check(stats: toploc.TurnStats, gold: dict) -> None:
    for field, expect in gold.items():
        got = np.asarray(getattr(stats, field)).astype(int).tolist()
        assert got == expect, (field, got, expect)


def _selections(bk, index, conv) -> np.ndarray:
    """The probe selection of every turn, re-derived from the session
    state the sequential drivers hand back: a refreshed turn probes its
    new anchor, any other turn the top-nprobe of its cached centroids."""
    sels = []
    _, _, sess, _ = toploc.start(bk, index, conv[0], k=K)
    sels.append(np.asarray(sess.anchor_sel))
    for q in conv[1:]:
        _, _, sess, st = toploc.step(bk, index, sess, q, k=K)
        if bool(st.refreshed):
            sels.append(np.asarray(sess.anchor_sel))
        else:
            _, loc = jax.lax.top_k(sess.cache_vecs @ q, NPROBE)
            sels.append(np.asarray(sess.cache_ids)[np.asarray(loc)])
    return np.stack(sels)


def _check_ivf_family(stats: toploc.TurnStats, bk, index, conv,
                      field: str) -> None:
    """Counter identities that hold for any partitioning: the scanned
    list work is the real size of the probed lists, and a follow-up turn
    refreshes exactly when |I0| < alpha·nprobe."""
    sizes = np.asarray(index.list_sizes)
    want = sizes[_selections(bk, index, conv)].sum(axis=1)
    np.testing.assert_array_equal(np.asarray(getattr(stats, field)), want)
    i0 = np.asarray(stats.i0)
    assert i0[0] == -1
    np.testing.assert_array_equal(np.asarray(stats.refreshed)[1:],
                                  i0[1:] < ALPHA * NPROBE)


def test_golden_ivf_counters(golden_setup):
    conv, fidx, _, _ = golden_setup
    _, _, st = toploc.conversation(IVF_BK, fidx, conv, k=K)
    _check(st, GOLD_IVF)
    _check_ivf_family(st, IVF_BK, fidx, conv, "list_dists")


def test_golden_ivf_pq_counters(golden_setup):
    conv, _, pqi, _ = golden_setup
    _, _, st = toploc.conversation(PQ_BK, pqi, conv, k=K)
    _check(st, GOLD_IVF_PQ)
    _check_ivf_family(st, PQ_BK, pqi, conv, "code_dists")


def test_golden_hnsw_counters(golden_setup):
    conv, _, _, hidx = golden_setup
    _, _, st = toploc.conversation(HNSW_BK, hidx, conv, k=K)
    _check(st, GOLD_HNSW)


@pytest.mark.parametrize("name,bk,gold", [("ivf", IVF_BK, GOLD_IVF),
                                          ("ivf_pq", PQ_BK, GOLD_IVF_PQ)])
def test_golden_fused_counters_equal_classic(golden_setup, name, bk,
                                             gold):
    """The fused megakernel path reports the SAME pinned work counters
    as the 3-dispatch turn it replaces — fusion changes dispatch
    structure, never the cost accounting the paper's claims rest on."""
    conv, fidx, pqi, _ = golden_setup
    index = fidx if name == "ivf" else pqi
    fbk = dataclasses.replace(bk, fused=toploc.FusedTurn())
    _, _, st = toploc.conversation(fbk, index, conv, k=K)
    _check(st, gold)
    _check_ivf_family(st, bk, index, conv,
                      "list_dists" if name == "ivf" else "code_dists")
    _, _, st_classic = toploc.conversation(bk, index, conv, k=K)
    for f in toploc.TurnStats._fields:
        np.testing.assert_array_equal(np.asarray(getattr(st, f)),
                                      np.asarray(getattr(st_classic, f)))


def test_golden_pq_cost_identity(golden_setup):
    """The structural identity behind the pinned numbers: PQ scans the
    SAME lists as float IVF (code_dists == float list_dists, same
    refresh schedule) while float work collapses to R per turn."""
    conv, fidx, pqi, _ = golden_setup
    _, _, st_f = toploc.conversation(IVF_BK, fidx, conv, k=K)
    _, _, st_q = toploc.conversation(PQ_BK, pqi, conv, k=K)
    np.testing.assert_array_equal(np.asarray(st_q.code_dists),
                                  np.asarray(st_f.list_dists))
    np.testing.assert_array_equal(np.asarray(st_q.centroid_dists),
                                  np.asarray(st_f.centroid_dists))
    np.testing.assert_array_equal(np.asarray(st_q.refreshed),
                                  np.asarray(st_f.refreshed))
    assert np.all(np.asarray(st_q.list_dists) == RERANK)
