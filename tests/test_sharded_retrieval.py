"""Device-sharded retrieval: bit-equality with the single-device path.

The contract under test (distributed/retrieval.py): partitioning the
corpus over a mesh — IVF posting lists, PQ code lists + re-rank corpus,
the HNSW vector corpus — changes *where* distances are computed and
nothing else.  Scores, ids, every ``TurnStats`` counter and the session
state must equal the single-device path bit for bit, at every shard
count, for all three backends, across a full 8-turn conversation.

Under the default 1-device run these tests still exercise the complete
``shard_map`` + collective path on a 1-shard mesh; the CI job with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` runs them at
2/4/8 shards.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import hnsw, ivf, pq, toploc
from repro.core.backend import HNSWBackend, IVFBackend, IVFPQBackend
from repro.core.topk import distributed_topk_ordered
from repro.distributed import retrieval as R
from repro.serving.engine import (BatchedConversationalSearchEngine,
                                  ConversationalSearchEngine, ServingConfig)

SHARD_COUNTS = [s for s in (1, 2, 4, 8) if s <= jax.device_count()]
T = 8            # acceptance: 8-turn conversations
K, H, NPROBE, EF, UP, RR, ALPHA = 10, 16, 4, 16, 2, 32, 0.3


@pytest.fixture(scope="module")
def wl8():
    """Topic-clustered workload with 8-turn conversations."""
    from repro.data import synthetic as SY
    return SY.make_workload(SY.WorkloadConfig(
        n_docs=1500, d=32, n_topics=16, n_conversations=4,
        turns_per_conversation=T, seed=7))


@pytest.fixture(scope="module")
def idx8(wl8):
    return ivf.build(jnp.asarray(wl8.doc_vecs), p=32, iters=4,
                     key=jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def pq8(wl8, idx8):
    return pq.build_ivf_pq(idx8, jnp.asarray(wl8.doc_vecs), m=8, iters=4,
                           key=jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def hnsw8(wl8):
    return hnsw.build(wl8.doc_vecs, m=8, ef_construction=32, seed=0)


def _assert_stats_equal(ref, got, ctx):
    for f in toploc.TurnStats._fields:
        assert bool((jnp.asarray(getattr(ref, f))
                     == jnp.asarray(getattr(got, f))).all()), (f, ctx)


# ------------------------------------------------- toploc step bit-equality

@pytest.mark.parametrize("shards", SHARD_COUNTS)
def test_ivf_sharded_conversation_bit_identical(idx8, wl8, shards):
    mesh = R.retrieval_mesh(shards)
    bk = IVFBackend(h=H, nprobe=NPROBE, alpha=ALPHA)
    sbk, sidx = R.shard_backend(mesh, bk, idx8)
    conv = jnp.asarray(wl8.conversations[0])
    v, i, s, st = toploc.start(bk, idx8, conv[0], k=K)
    sv, si, ss, sst = toploc.start(sbk, sidx, conv[0], k=K)
    assert bool((v == sv).all()) and bool((i == si).all())
    _assert_stats_equal(st, sst, ("start", shards))
    for t in range(1, T):
        v, i, s, st = toploc.step(bk, idx8, s, conv[t], k=K)
        sv, si, ss, sst = toploc.step(sbk, sidx, ss, conv[t], k=K)
        assert bool((v == sv).all()) and bool((i == si).all()), t
        _assert_stats_equal(st, sst, (t, shards))
    for f in toploc.IVFSession._fields:
        assert bool((getattr(s, f) == getattr(ss, f)).all()), f


@pytest.mark.parametrize("shards", SHARD_COUNTS)
def test_ivf_pq_sharded_conversation_bit_identical(pq8, wl8, shards):
    mesh = R.retrieval_mesh(shards)
    bk = IVFPQBackend(h=H, nprobe=NPROBE, alpha=ALPHA, rerank=RR)
    sbk, sidx = R.shard_backend(mesh, bk, pq8)
    conv = jnp.asarray(wl8.conversations[1])
    v, i, s, st = toploc.start(bk, pq8, conv[0], k=K)
    sv, si, ss, sst = toploc.start(sbk, sidx, conv[0], k=K)
    assert bool((v == sv).all()) and bool((i == si).all())
    _assert_stats_equal(st, sst, ("start", shards))
    for t in range(1, T):
        v, i, s, st = toploc.step(bk, pq8, s, conv[t], k=K)
        sv, si, ss, sst = toploc.step(sbk, sidx, ss, conv[t], k=K)
        assert bool((v == sv).all()) and bool((i == si).all()), t
        _assert_stats_equal(st, sst, (t, shards))


@pytest.mark.parametrize("shards", SHARD_COUNTS)
def test_hnsw_sharded_conversation_bit_identical(hnsw8, wl8, shards):
    mesh = R.retrieval_mesh(shards)
    bk = HNSWBackend(ef=EF, up=UP)
    sbk, sidx = R.shard_backend(mesh, bk, hnsw8)
    conv = jnp.asarray(wl8.conversations[2])
    v, i, s, st = toploc.start(bk, hnsw8, conv[0], k=K)
    sv, si, ss, sst = toploc.start(sbk, sidx, conv[0], k=K)
    assert bool((v == sv).all()) and bool((i == si).all())
    _assert_stats_equal(st, sst, ("start", shards))
    for t in range(1, T):
        v, i, s, st = toploc.step(bk, hnsw8, s, conv[t], k=K)
        sv, si, ss, sst = toploc.step(sbk, sidx, ss, conv[t], k=K)
        assert bool((v == sv).all()) and bool((i == si).all()), t
        _assert_stats_equal(st, sst, (t, shards))
    assert int(s.entry_point) == int(ss.entry_point)


def test_sharded_batched_step_matches_sequential(idx8, wl8):
    """Mixed first/follow-up batch on the sharded scan reproduces the
    sharded sequential rows (the is_first select logic composes with
    shard_map inside the batch-wide lax.cond gate)."""
    mesh = R.retrieval_mesh(SHARD_COUNTS[-1])
    bk = IVFBackend(h=H, nprobe=NPROBE, alpha=ALPHA)
    sbk, sidx = R.shard_backend(mesh, bk, idx8)
    q0 = jnp.asarray(wl8.conversations[:4, 0])
    _, _, sess0, _ = toploc.start_batch(sbk, sidx, q0, k=K)
    first = jnp.asarray([True, False, True, False])
    qmix = jnp.where(first[:, None], q0, jnp.asarray(wl8.conversations[:4, 1]))
    mv, mi, _, mst = toploc.step_batch(sbk, sidx, sess0, qmix, k=K,
                                       is_first=first)
    for b in range(4):
        if bool(first[b]):
            rv, ri, _, rst = toploc.start(bk, idx8, q0[b], k=K)
        else:
            sb = jax.tree.map(lambda a: a[b], sess0)
            rv, ri, _, rst = toploc.step(
                bk, idx8, sb, jnp.asarray(wl8.conversations[b, 1]), k=K)
        assert bool((mv[b] == rv).all()) and bool((mi[b] == ri).all()), b


# ------------------------------------------------------- engine wiring

def _records_key(recs):
    return sorted((r.conv_id, r.turn, r.centroid_dists, r.list_dists,
                   r.graph_dists, r.code_dists, r.refreshed, r.i0)
                  for r in recs)


@pytest.mark.parametrize("backend,strategy", [
    ("ivf", "toploc+"), ("ivf", "plain"),
    ("ivf_pq", "toploc+"), ("hnsw", "toploc"),
])
def test_sharded_engine_matches_unsharded(wl8, idx8, pq8, hnsw8, backend,
                                          strategy):
    base = dict(backend=backend, strategy=strategy, nprobe=NPROBE, h=H,
                alpha=ALPHA, ef_search=EF, up=UP, k=K, rerank=RR)
    seq = ConversationalSearchEngine(
        ServingConfig(**base), ivf_index=idx8, ivf_pq_index=pq8,
        hnsw_index=hnsw8)
    shd = ConversationalSearchEngine(
        ServingConfig(**base, shards=SHARD_COUNTS[-1]), ivf_index=idx8,
        ivf_pq_index=pq8, hnsw_index=hnsw8)
    for t in range(T):
        for c in range(3):
            qv = jnp.asarray(wl8.conversations[c, t])
            v0, i0 = seq.query(f"c{c}", qv)
            v1, i1 = shd.query(f"c{c}", qv)
            np.testing.assert_array_equal(v0, v1)
            np.testing.assert_array_equal(i0, i1)
    assert _records_key(seq.records) == _records_key(shd.records)


@pytest.mark.parametrize("backend", ["ivf", "ivf_pq", "hnsw"])
def test_sharded_batched_engine_matches_unsharded_sequential(
        wl8, idx8, pq8, hnsw8, backend):
    """The full serving stack — MicroBatcher flush, SessionStore slab,
    batched step, sharded scan — stays bit-identical to the unsharded
    sequential oracle."""
    strategy = "toploc" if backend == "hnsw" else "toploc+"
    base = dict(backend=backend, strategy=strategy, nprobe=NPROBE, h=H,
                alpha=ALPHA, ef_search=EF, up=UP, k=K, rerank=RR)
    seq = ConversationalSearchEngine(
        ServingConfig(**base), ivf_index=idx8, ivf_pq_index=pq8,
        hnsw_index=hnsw8)
    bat = BatchedConversationalSearchEngine(
        ServingConfig(**base, shards=SHARD_COUNTS[-1]), ivf_index=idx8,
        ivf_pq_index=pq8, hnsw_index=hnsw8, max_batch=4, max_wait_s=1e-4)
    placed = [a.sharding for a in jax.tree.leaves(bat.store.slab)]
    for t in range(T):
        futs = []
        for c in range(3):          # 3 rows → padded to bucket 4
            qv = jnp.asarray(wl8.conversations[c, t])
            futs.append((*seq.query(f"c{c}", qv),
                         bat.submit(f"c{c}", qv)))
        bat.drain()
        for sv, si, fut in futs:
            bv, bi = fut.result(timeout=30)
            np.testing.assert_array_equal(sv, bv)
            np.testing.assert_array_equal(si, bi)
    assert _records_key(seq.records) == _records_key(bat.records)
    # the wave program hands the slab back replicated over the mesh
    assert [a.sharding for a in jax.tree.leaves(bat.store.slab)] == placed


# --------------------------------------------------- building blocks

def test_distributed_topk_ordered_breaks_ties_by_position():
    """Equal scores must resolve by global flat position (the single-
    device lax.top_k order), not by shard order."""
    from repro import compat
    from jax.sharding import PartitionSpec as P
    shards = SHARD_COUNTS[-1]
    mesh = R.retrieval_mesh(shards)
    # every shard offers the same score; positions distinguish them
    v = jnp.tile(jnp.asarray([[1.0, 0.5]]), (1, shards))       # (1, 2S)
    pos = jnp.arange(2 * shards, dtype=jnp.int32)[None]
    pos = pos.at[0, 0].set(100)      # shard 0's best has a HIGH position
    ids = jnp.arange(2 * shards, dtype=jnp.int32)[None] + 10

    def f(v, p, i):
        return distributed_topk_ordered(v, p, i, 2, "model")

    out_v, out_i = compat.shard_map(
        f, mesh=mesh, in_specs=(P(None, "model"), P(None, "model"),
                                P(None, "model")),
        out_specs=(P(None, None), P(None, None)), check_vma=False)(
            v, pos, ids)
    # the tie at 1.0 resolves to the LOWEST position, which is not
    # shard 0's entry (pos 100) when more than one shard ties
    if shards > 1:
        assert np.asarray(out_v).tolist() == [[1.0, 1.0]]
        assert int(np.asarray(out_i)[0, 0]) == 12   # shard 1's pos-2 entry
    else:
        assert np.asarray(out_v)[0].tolist() == [1.0, 0.5]
        assert int(np.asarray(out_i)[0, 0]) == 10


def test_sharded_index_padding_is_inert(idx8, wl8):
    """Padded partitions (p not divisible by S) are never selected and
    contribute no work."""
    # p=32 on 3 shards → pad to 33; needs a 3-shard mesh
    if jax.device_count() < 3:
        pytest.skip("needs >= 3 devices to make padding observable")
    mesh = R.retrieval_mesh(3)
    sidx = R.shard_ivf_index(mesh, idx8)
    assert sidx.list_ids.shape[0] % 3 == 0
    assert sidx.centroids.shape[0] == idx8.p      # centroids unpadded
    scan = R.ShardedIVFScan(mesh)
    q = jnp.asarray(wl8.conversations[0, :2])
    cs = q @ idx8.centroids.T
    _, sel = jax.lax.top_k(cs, NPROBE)
    v, i, real = scan(sidx, q, sel, K)
    rv, ri, rreal = ivf._scan_lists(idx8, q, sel, K)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(rv))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(real), np.asarray(rreal))


def test_per_shard_list_work_partitions_total(idx8, wl8):
    """Per-device work sums to the single-device total and shrinks with
    the shard count (the fig4 measurement helper)."""
    sizes = np.asarray(idx8.list_sizes)
    q = jnp.asarray(wl8.conversations[0, 0])
    cs = idx8.centroids @ q
    _, sel = jax.lax.top_k(cs, 16)
    sel = np.asarray(sel)
    total = sizes[sel].sum()
    for s in (1, 2, 4, 8):
        work = R.per_shard_list_work(sizes, sel, s)
        assert work.shape == (s,)
        assert work.sum() == total
    assert R.per_shard_list_work(sizes, sel, 8).max() < total


def test_retrieval_mesh_validates_device_count():
    with pytest.raises(ValueError, match="shards"):
        R.retrieval_mesh(jax.device_count() + 1)
