"""Core IVF + k-means invariants."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import ivf, kmeans


def test_build_partitions_all_docs(ivf_index, small_corpus):
    assert int(ivf_index.list_sizes.sum()) == small_corpus.doc_vecs.shape[0]
    ids = np.asarray(ivf_index.list_ids)
    real = ids[ids >= 0]
    assert len(np.unique(real)) == small_corpus.doc_vecs.shape[0]


def test_balanced_capacity(ivf_index, small_corpus):
    n, p = small_corpus.doc_vecs.shape[0], ivf_index.p
    cap = int(1.3 * n / p + 1)
    assert int(ivf_index.list_sizes.max()) <= cap
    assert ivf_index.lmax <= cap


def test_full_probe_equals_exact(ivf_index, small_corpus):
    """nprobe == p must reproduce exhaustive search (paper §2: np=p)."""
    q = jnp.asarray(small_corpus.conversations[:2, 0])
    ev, ei = ivf.exact_search(jnp.asarray(small_corpus.doc_vecs), q, 10)
    sv, si, _ = ivf.search(ivf_index, q, nprobe=ivf_index.p, k=10)
    np.testing.assert_array_equal(np.asarray(si), np.asarray(ei))
    np.testing.assert_allclose(np.asarray(sv), np.asarray(ev), rtol=1e-5)


def test_recall_monotone_in_nprobe(ivf_index, small_corpus):
    q = jnp.asarray(small_corpus.conversations.reshape(-1, 32)[:16])
    ev, ei = ivf.exact_search(jnp.asarray(small_corpus.doc_vecs), q, 10)
    recalls = []
    for npb in (1, 4, 16, 32):
        _, si, _ = ivf.search(ivf_index, q, nprobe=npb, k=10)
        r = np.mean([len(set(np.asarray(si[i]).tolist())
                         & set(np.asarray(ei[i]).tolist())) / 10
                     for i in range(q.shape[0])])
        recalls.append(r)
    assert all(b >= a - 1e-9 for a, b in zip(recalls, recalls[1:]))
    assert recalls[-1] == 1.0


def test_search_stats_counts(ivf_index):
    q = jnp.ones((3, ivf_index.d)) / np.sqrt(ivf_index.d)
    _, _, st = ivf.search(ivf_index, q, nprobe=4, k=5)
    assert st.centroid_dists.shape == (3,)
    assert int(st.centroid_dists[0]) == ivf_index.p
    sizes = np.asarray(ivf_index.list_sizes)
    assert np.all(np.asarray(st.list_dists) <= 4 * sizes.max())
    assert np.all(np.asarray(st.list_dists) > 0)


def test_cached_search_matches_full_when_cache_is_all(ivf_index,
                                                      small_corpus):
    """h == p: cached search must equal plain search exactly."""
    q = jnp.asarray(small_corpus.conversations[0, :3])
    cache_ids, cache_vecs = ivf.make_cache(ivf_index, q[0], h=ivf_index.p)
    v1, i1, _ = ivf.search(ivf_index, q, nprobe=8, k=10)
    v2, i2, sel, _ = ivf.search_cached(ivf_index, cache_ids, cache_vecs,
                                       q, nprobe=8, k=10)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_kmeans_balance_respects_capacity(rng):
    x = jnp.asarray(rng.normal(size=(500, 16)).astype(np.float32))
    res = kmeans.fit_balanced(x, 8, iters=4, capacity_factor=1.2)
    cap = int(1.2 * 500 / 8 + 1)
    assert int(res.sizes.max()) <= cap
    assert int(res.sizes.sum()) == 500
    # every point assigned to a real cluster
    assert np.all(np.asarray(res.assignment) >= 0)
    assert np.all(np.asarray(res.assignment) < 8)


# ---------------------------------------------------------------------------
# blocked balance_assignment == the dense (n, p) formulation it replaced
# ---------------------------------------------------------------------------


def _dense_balance_assignment(points, centroids, p, capacity, n_choices=4):
    """The previous formulation, kept as the reference: the full (n, p)
    similarity matrix and an (n, p+1) one-hot cumsum per choice rank."""
    n = points.shape[0]
    n_choices = min(n_choices, p)
    sims = points @ centroids.T
    choice_sims, choice_ids = jax.lax.top_k(sims, n_choices)
    assignment = jnp.full((n,), -1, jnp.int32)
    sizes = jnp.zeros((p,), jnp.int32)
    for r in range(n_choices):
        cand = choice_ids[:, r]
        want = assignment < 0
        order = jnp.argsort(jnp.where(want, -choice_sims[:, r], jnp.inf))
        cand_o, want_o = cand[order], want[order]
        onehot_pos = jnp.cumsum(jax.nn.one_hot(
            jnp.where(want_o, cand_o, p), p + 1, dtype=jnp.int32), axis=0)
        pos = jnp.take_along_axis(
            onehot_pos, jnp.where(want_o, cand_o, p)[:, None], axis=1)[:, 0] - 1
        room = capacity - sizes[jnp.where(want_o, cand_o, 0)]
        ok = want_o & (pos < room)
        new_assign = jnp.zeros((n,), jnp.int32).at[order].set(
            jnp.where(ok, cand_o, -1))
        placed = jnp.zeros((n,), bool).at[order].set(ok)
        assignment = jnp.where(placed, new_assign, assignment)
        sizes = sizes + jax.ops.segment_sum(
            placed.astype(jnp.int32), jnp.where(placed, assignment, p),
            num_segments=p + 1)[:p]
    assignment, sizes = np.array(assignment), np.array(sizes)
    for i in range(n):
        if assignment[i] < 0:
            tgt = int(np.argmin(sizes))
            assignment[i] = tgt
            sizes[tgt] += 1
    return assignment, sizes


@pytest.mark.parametrize("n,p,capacity,block", [
    (2000, 32, 82, 4096),     # the conftest fixture's build
    (2000, 32, 82, 256),      # several similarity blocks
    (1000, 16, 64, 333),      # ragged last block
    (1000, 16, 40, 4096),     # capacity short: stragglers hit the fallback
    (500, 64, 9, 128),
])
def test_blocked_balance_matches_dense(small_corpus, n, p, capacity, block):
    x = jnp.asarray(small_corpus.doc_vecs[:n])
    c, _ = kmeans.kmeans_fit(x, p, iters=3, key=jax.random.PRNGKey(1))
    a, s = kmeans.balance_assignment(x, c, p, capacity, block=block)
    ra, rs = _dense_balance_assignment(x, c, p, capacity)
    np.testing.assert_array_equal(np.asarray(a), ra)
    np.testing.assert_array_equal(np.asarray(s), rs)


def test_blocked_inertia_matches_dense(small_corpus):
    x = jnp.asarray(small_corpus.doc_vecs)
    c, _ = kmeans.kmeans_fit(x, 32, iters=3, key=jax.random.PRNGKey(1))
    dense = jnp.mean(jnp.max(x @ c.T, axis=-1))
    assert float(kmeans.mean_max_similarity(x, c, block=300)) == \
        float(dense)


def test_build_matches_dense_formulation(small_corpus, ivf_index):
    """The fixture index equals the one the dense balancing pass and the
    per-document bucketing loop built: same centroids, same lists in the
    same order, pad slots masked."""
    x = jnp.asarray(small_corpus.doc_vecs)
    n, p = x.shape[0], ivf_index.p
    c, _ = kmeans.kmeans_fit(x, p, iters=5, key=jax.random.PRNGKey(0))
    cap = max(1, int(1.3 * n / p + 0.9999))
    assign, sizes = _dense_balance_assignment(x, c, p, cap)
    lmax = int(sizes.max())
    ids = np.full((p, lmax), -1, np.int32)
    fill = np.zeros(p, np.int64)
    for doc, cl in enumerate(assign):
        ids[cl, fill[cl]] = doc
        fill[cl] += 1
    vecs = np.where((ids >= 0)[..., None],
                    np.asarray(x)[np.maximum(ids, 0)], 0.0)
    np.testing.assert_array_equal(np.asarray(ivf_index.centroids),
                                  np.asarray(c))
    np.testing.assert_array_equal(np.asarray(ivf_index.list_sizes), sizes)
    np.testing.assert_array_equal(np.asarray(ivf_index.list_ids), ids)
    np.testing.assert_array_equal(np.asarray(ivf_index.list_vecs), vecs)
