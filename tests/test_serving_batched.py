"""Batched multi-conversation serving: equivalence + SessionStore.

The contract under test: serving B concurrent conversations through one
batched dispatch (``toploc.*_batch`` registry drivers /
``BatchedConversationalSearchEngine``) is *bit-identical* — scores, ids,
and every ``TurnStats`` field — to serving them one at a time through
the sequential path.  This is what makes the batched path a drop-in: no
effectiveness re-evaluation is needed when the only change is the
batching.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import hnsw, ivf, toploc
from repro.core.backend import HNSWBackend, IVFBackend, IVFPQBackend
from repro.serving import (BatchedConversationalSearchEngine,
                           ConversationalSearchEngine, ServingConfig,
                           SessionStore, hnsw_session_store,
                           ivf_session_store)

B, T = 4, 4          # ≥ 4 interleaved conversations
K, H, NPROBE, EF, UP = 10, 16, 4, 16, 2


@pytest.fixture(scope="module")
def convs(small_corpus):
    return jnp.asarray(small_corpus.conversations[:B, :T])


def _stats_equal(seq_stats_rows, batched_stats):
    """Every TurnStats field equal between stacked sequential rows and
    one batched TurnStats."""
    for f in toploc.TurnStats._fields:
        seq = jnp.stack([getattr(s, f) for s in seq_stats_rows])
        if not bool((seq == getattr(batched_stats, f)).all()):
            return False
    return True


# ------------------------------------------------------------------ IVF

@pytest.mark.parametrize("alpha", [-1.0, 0.3])
def test_ivf_batch_equals_sequential(ivf_index, convs, alpha):
    idx = ivf_index
    bk = IVFBackend(h=H, nprobe=NPROBE, alpha=alpha)
    # sequential: B independent conversations
    sess, vs, is_, sts = [], [], [], []
    for b in range(B):
        v, i, s, st = toploc.start(bk, idx, convs[b, 0], k=K)
        sess.append(s)
        vs.append([v]); is_.append([i]); sts.append([st])
    for t in range(1, T):
        for b in range(B):
            v, i, s, st = toploc.step(bk, idx, sess[b], convs[b, t], k=K)
            sess[b] = s
            vs[b].append(v); is_[b].append(i); sts[b].append(st)

    # batched: one dispatch per turn over all B conversations
    bv, bi, bsess, bst = toploc.start_batch(bk, idx, convs[:, 0], k=K)
    assert bool((jnp.stack([vs[b][0] for b in range(B)]) == bv).all())
    assert bool((jnp.stack([is_[b][0] for b in range(B)]) == bi).all())
    assert _stats_equal([sts[b][0] for b in range(B)], bst)
    for t in range(1, T):
        bv, bi, bsess, bst = toploc.step_batch(bk, idx, bsess, convs[:, t],
                                               k=K)
        assert bool((jnp.stack([vs[b][t] for b in range(B)]) == bv).all()), t
        assert bool((jnp.stack([is_[b][t] for b in range(B)]) == bi).all()), t
        assert _stats_equal([sts[b][t] for b in range(B)], bst), t
    # final session state also matches (cache, anchors, counters)
    for f in toploc.IVFSession._fields:
        seq = jnp.stack([getattr(sess[b], f) for b in range(B)])
        assert bool((seq == getattr(bsess, f)).all()), f


def test_ivf_mixed_first_and_followup_batch(ivf_index, convs):
    """One batch mixing first turns and follow-ups via the is_first mask
    reproduces start rows and step rows exactly."""
    idx = ivf_index
    bk = IVFBackend(h=H, nprobe=NPROBE, alpha=0.3)
    v0, i0_, sess0, st0 = toploc.start_batch(bk, idx, convs[:, 0], k=K)
    first = jnp.asarray([True, False, True, False])
    qmix = jnp.where(first[:, None], convs[:, 0], convs[:, 1])
    mv, mi, msess, mst = toploc.step_batch(bk, idx, sess0, qmix, k=K,
                                           is_first=first)
    for b in range(B):
        if bool(first[b]):
            rv, ri, rs, rst = toploc.start(bk, idx, convs[b, 0], k=K)
        else:
            sb = jax.tree.map(lambda a: a[b], sess0)
            rv, ri, rs, rst = toploc.step(bk, idx, sb, convs[b, 1], k=K)
        assert bool((mv[b] == rv).all()) and bool((mi[b] == ri).all()), b
        for f in toploc.TurnStats._fields:
            assert bool((getattr(mst, f)[b] == getattr(rst, f)).all()), (b, f)
        for f in toploc.IVFSession._fields:
            assert bool((jax.tree.map(lambda a: a[b], msess)._asdict()[f]
                         == getattr(rs, f)).all()), (b, f)


# --------------------------------------------------------------- IVF-PQ

@pytest.mark.parametrize("alpha", [-1.0, 0.3])
def test_ivf_pq_batch_equals_sequential(ivf_pq_index, convs, alpha):
    idx = ivf_pq_index
    bk = IVFPQBackend(h=H, nprobe=NPROBE, alpha=alpha, rerank=32)
    sess, vs, is_, sts = [], [], [], []
    for b in range(B):
        v, i, s, st = toploc.start(bk, idx, convs[b, 0], k=K)
        sess.append(s)
        vs.append([v]); is_.append([i]); sts.append([st])
    for t in range(1, T):
        for b in range(B):
            v, i, s, st = toploc.step(bk, idx, sess[b], convs[b, t], k=K)
            sess[b] = s
            vs[b].append(v); is_[b].append(i); sts[b].append(st)

    bv, bi, bsess, bst = toploc.start_batch(bk, idx, convs[:, 0], k=K)
    assert bool((jnp.stack([vs[b][0] for b in range(B)]) == bv).all())
    assert bool((jnp.stack([is_[b][0] for b in range(B)]) == bi).all())
    assert _stats_equal([sts[b][0] for b in range(B)], bst)
    for t in range(1, T):
        bv, bi, bsess, bst = toploc.step_batch(bk, idx, bsess, convs[:, t],
                                               k=K)
        assert bool((jnp.stack([vs[b][t] for b in range(B)]) == bv).all()), t
        assert bool((jnp.stack([is_[b][t] for b in range(B)]) == bi).all()), t
        assert _stats_equal([sts[b][t] for b in range(B)], bst), t
    for f in toploc.IVFSession._fields:
        seq = jnp.stack([getattr(sess[b], f) for b in range(B)])
        assert bool((seq == getattr(bsess, f)).all()), f


def test_ivf_pq_mixed_first_and_followup_batch(ivf_pq_index, convs):
    idx = ivf_pq_index
    bk = IVFPQBackend(h=H, nprobe=NPROBE, alpha=0.3, rerank=32)
    _, _, sess0, _ = toploc.start_batch(bk, idx, convs[:, 0], k=K)
    first = jnp.asarray([True, False, True, False])
    qmix = jnp.where(first[:, None], convs[:, 0], convs[:, 1])
    mv, mi, msess, mst = toploc.step_batch(bk, idx, sess0, qmix, k=K,
                                           is_first=first)
    for b in range(B):
        if bool(first[b]):
            rv, ri, rs, rst = toploc.start(bk, idx, convs[b, 0], k=K)
        else:
            sb = jax.tree.map(lambda a: a[b], sess0)
            rv, ri, rs, rst = toploc.step(bk, idx, sb, convs[b, 1], k=K)
        assert bool((mv[b] == rv).all()) and bool((mi[b] == ri).all()), b
        for f in toploc.TurnStats._fields:
            assert bool((getattr(mst, f)[b] == getattr(rst, f)).all()), (b, f)
        for f in toploc.IVFSession._fields:
            assert bool((jax.tree.map(lambda a: a[b], msess)._asdict()[f]
                         == getattr(rs, f)).all()), (b, f)


# ----------------------------------------------------------------- HNSW

def test_hnsw_batch_equals_sequential(hnsw_index, convs):
    idx = hnsw_index
    bk = HNSWBackend(ef=EF, up=UP)
    sess, vs, is_, sts = [], [], [], []
    for b in range(B):
        v, i, s, st = toploc.start(bk, idx, convs[b, 0], k=K)
        sess.append(s)
        vs.append([v]); is_.append([i]); sts.append([st])
    for t in range(1, T):
        for b in range(B):
            v, i, s, st = toploc.step(bk, idx, sess[b], convs[b, t], k=K)
            sess[b] = s
            vs[b].append(v); is_[b].append(i); sts[b].append(st)

    bv, bi, bsess, bst = toploc.start_batch(bk, idx, convs[:, 0], k=K)
    assert bool((jnp.stack([vs[b][0] for b in range(B)]) == bv).all())
    assert bool((jnp.stack([is_[b][0] for b in range(B)]) == bi).all())
    assert _stats_equal([sts[b][0] for b in range(B)], bst)
    for t in range(1, T):
        bv, bi, bsess, bst = toploc.step_batch(bk, idx, bsess, convs[:, t],
                                               k=K)
        assert bool((jnp.stack([vs[b][t] for b in range(B)]) == bv).all()), t
        assert bool((jnp.stack([is_[b][t] for b in range(B)]) == bi).all()), t
        assert _stats_equal([sts[b][t] for b in range(B)], bst), t
    assert bool((jnp.stack([s.entry_point for s in sess])
                 == bsess.entry_point).all())


def test_hnsw_mixed_first_and_followup_batch(hnsw_index, convs):
    idx = hnsw_index
    bk = HNSWBackend(ef=EF, up=UP)
    _, _, sess0, _ = toploc.start_batch(bk, idx, convs[:, 0], k=K)
    first = jnp.asarray([False, True, False, True])
    qmix = jnp.where(first[:, None], convs[:, 0], convs[:, 1])
    mv, mi, msess, mst = toploc.step_batch(bk, idx, sess0, qmix, k=K,
                                           is_first=first)
    for b in range(B):
        if bool(first[b]):
            rv, ri, rs, rst = toploc.start(bk, idx, convs[b, 0], k=K)
        else:
            sb = jax.tree.map(lambda a: a[b], sess0)
            rv, ri, rs, rst = toploc.step(bk, idx, sb, convs[b, 1], k=K)
        assert bool((mv[b] == rv).all()) and bool((mi[b] == ri).all()), b
        assert int(mst.graph_dists[b]) == int(rst.graph_dists)
        assert bool(mst.refreshed[b]) == bool(rst.refreshed)
        assert int(msess.entry_point[b]) == int(rs.entry_point)
        assert int(msess.turn[b]) == int(rs.turn)


# --------------------------------------------------------- SessionStore

def test_session_store_slot_reuse_and_eviction(ivf_index):
    store = ivf_session_store(ivf_index, h=H, nprobe=NPROBE, n_slots=2)
    s0, new0 = store.acquire("a")
    s1, new1 = store.acquire("b")
    assert new0 and new1 and s0 != s1
    # reuse: same conv → same slot, not new
    assert store.acquire("a") == (s0, False)
    # full store: 'b' is now LRU ('a' was just touched) → 'c' evicts 'b'
    s2, new2 = store.acquire("c")
    assert new2 and s2 == s1
    assert store.evictions == 1
    assert store.lookup("b") is None
    # evicted conv returning is a fresh allocation (first-turn semantics)
    s3, new3 = store.acquire("b")
    assert new3
    # release returns the slot to the free list for reuse
    freed = store.release("c")
    s4, new4 = store.acquire("d")
    assert new4 and s4 == freed
    assert store.occupancy == 2


def test_session_store_gather_scatter_roundtrip(ivf_index):
    store = ivf_session_store(ivf_index, h=H, nprobe=NPROBE, n_slots=4)
    slots = [store.acquire(f"c{j}")[0] for j in range(3)]
    sess = store.gather(slots)
    bumped = sess._replace(turn=sess.turn + jnp.arange(3, dtype=jnp.int32))
    store.scatter(slots, bumped)
    back = store.gather(slots)
    assert bool((back.turn == jnp.arange(3)).all())
    # trash slot absorbs padded rows without touching live sessions
    pad_slots = [slots[0], store.trash_slot]
    pad = store.gather(pad_slots)
    store.scatter([store.trash_slot, store.trash_slot],
                  jax.tree.map(lambda a: a + 1 if a.dtype == jnp.int32
                               else a, pad))
    assert bool((store.gather(slots).turn == jnp.arange(3)).all())


def test_hnsw_session_store_layout(hnsw_index):
    store = hnsw_session_store(hnsw_index, n_slots=3)
    assert store.gather([0]).entry_point.shape == (1,)
    assert store.trash_slot == 3


def test_release_zeroes_slab_row_and_is_idempotent(ivf_index):
    """A released slot's slab row is wiped (no prior-conversation cache
    can leak to the next occupant) and double-release is a no-op — in
    particular the slot is never double-appended to the free list."""
    store = ivf_session_store(ivf_index, h=H, nprobe=NPROBE, n_slots=2)
    slot, _ = store.acquire("a")
    dirty = jax.tree.map(lambda a: a + 1 if a.dtype == jnp.int32 else a + 1.0,
                         store.gather([slot]))
    store.scatter([slot], dirty)
    assert int(store.gather([slot]).turn[0]) == 1
    freed = store.release("a")
    assert freed == slot
    row = store.gather([slot])
    for f in toploc.IVFSession._fields:
        assert bool((getattr(row, f) == 0).all()), f
    # idempotent: second release returns None and does not corrupt the
    # free list (a duplicate entry would hand one slot to two convs)
    n_free = len(store._free)
    assert store.release("a") is None
    assert len(store._free) == n_free
    s1, _ = store.acquire("x")
    s2, _ = store.acquire("y")
    assert s1 != s2


def test_eviction_zeroes_slab_row_before_slot_reuse(ivf_index):
    """LRU eviction is the other way a slot changes hands: the evicted
    conversation's row must be wiped before the new occupant sees it."""
    store = ivf_session_store(ivf_index, h=H, nprobe=NPROBE, n_slots=1)
    slot, _ = store.acquire("old")
    dirty = jax.tree.map(lambda a: a + 1 if a.dtype == jnp.int32 else a + 1.0,
                         store.gather([slot]))
    store.scatter([slot], dirty)
    new_slot, is_new = store.acquire("new")      # evicts "old"
    assert new_slot == slot and is_new and store.evictions == 1
    row = store.gather([new_slot])
    for f in toploc.IVFSession._fields:
        assert bool((getattr(row, f) == 0).all()), f


def test_slot_freed_listeners_fire_on_release_and_eviction(ivf_index):
    """Companion state (e.g. the result-cache slab) keys off the same
    slots; it must observe every slot hand-over."""
    store = ivf_session_store(ivf_index, h=H, nprobe=NPROBE, n_slots=1)
    freed = []
    store.add_slot_freed_listener(freed.append)
    slot, _ = store.acquire("a")
    store.release("a")
    assert freed == [slot]
    slot2, _ = store.acquire("b")
    store.acquire("c")                       # evicts "b"
    assert freed == [slot, slot2]
    store.release("nope")                    # no-op: no notification
    assert freed == [slot, slot2]


def test_release_then_reacquire_never_leaks_prior_cache(small_corpus,
                                                        ivf_index):
    """Engine-level: end_conversation() wipes the slot, so the next
    conversation landing on it starts from zeros even if a buggy caller
    were to skip the is_first rebuild."""
    wl = small_corpus
    cfg = ServingConfig(backend="ivf", strategy="toploc+", nprobe=NPROBE,
                        h=H, alpha=0.3, k=K)
    bat = BatchedConversationalSearchEngine(
        cfg, ivf_index=ivf_index, max_batch=2, max_wait_s=1e-4)
    bat.query("a", jnp.asarray(wl.conversations[0, 0]))
    bat.query("a", jnp.asarray(wl.conversations[0, 1]))
    slot = bat.store.lookup("a")
    bat.end_conversation("a")
    row = bat.store.gather([slot])
    for f in toploc.IVFSession._fields:
        assert bool((getattr(row, f) == 0).all()), f
    # the freed slot's next occupant is served as a clean first turn
    v, i = bat.query("b", jnp.asarray(wl.conversations[1, 0]))
    assert bat.store.lookup("b") == slot
    rv, ri, _, _ = toploc.start(IVFBackend(h=H, nprobe=NPROBE), ivf_index,
                                jnp.asarray(wl.conversations[1, 0]), k=K)
    np.testing.assert_array_equal(v, np.asarray(rv))
    np.testing.assert_array_equal(i, np.asarray(ri))


# ------------------------------------------------------ batched engine

@pytest.mark.parametrize("backend,strategy", [
    ("ivf", "toploc"), ("ivf", "toploc+"), ("ivf", "plain"),
    ("ivf_pq", "toploc"), ("ivf_pq", "toploc+"), ("ivf_pq", "plain"),
    ("hnsw", "toploc"),
])
def test_batched_engine_matches_sequential(small_corpus, ivf_index,
                                           ivf_pq_index, hnsw_index,
                                           backend, strategy):
    wl = small_corpus
    cfg = ServingConfig(backend=backend, strategy=strategy, nprobe=NPROBE,
                        h=H, alpha=0.3, ef_search=EF, up=UP, k=K, rerank=32)
    seq = ConversationalSearchEngine(cfg, ivf_index=ivf_index,
                                     ivf_pq_index=ivf_pq_index,
                                     hnsw_index=hnsw_index)
    bat = BatchedConversationalSearchEngine(
        cfg, ivf_index=ivf_index, ivf_pq_index=ivf_pq_index,
        hnsw_index=hnsw_index, max_batch=4, max_wait_s=1e-4)
    for t in range(T):
        futs = []
        for c in range(4):
            qv = jnp.asarray(wl.conversations[c, t])
            sv, si = seq.query(f"c{c}", qv)
            futs.append((sv, si, bat.submit(f"c{c}", qv)))
        bat.drain()
        for sv, si, fut in futs:
            bv, bi = fut.result(timeout=5)
            np.testing.assert_array_equal(sv, bv)
            np.testing.assert_array_equal(si, bi)
    # identical per-turn work accounting, order-independent
    def key(recs):
        return sorted((r.conv_id, r.turn, r.centroid_dists, r.list_dists,
                       r.graph_dists, r.code_dists, r.refreshed, r.i0)
                      for r in recs)
    assert key(seq.records) == key(bat.records)


def test_batched_engine_rejects_undersized_store(ivf_index):
    """A wave needs one live slot per conversation: n_slots < max_batch
    would evict a conversation acquired earlier in the same wave."""
    cfg = ServingConfig(backend="ivf", strategy="toploc", nprobe=NPROBE,
                        h=H, k=K)
    with pytest.raises(ValueError, match="n_slots"):
        BatchedConversationalSearchEngine(cfg, ivf_index=ivf_index,
                                          n_slots=4, max_batch=32)


def test_batched_engine_max_batch_beyond_buckets(small_corpus, ivf_index):
    """max_batch above the largest default bucket gets its own bucket
    instead of overflowing the padded arrays."""
    wl = small_corpus
    cfg = ServingConfig(backend="ivf", strategy="toploc", nprobe=NPROBE,
                        h=H, k=K)
    seq = ConversationalSearchEngine(cfg, ivf_index=ivf_index)
    bat = BatchedConversationalSearchEngine(
        cfg, ivf_index=ivf_index, n_slots=64, max_batch=64,
        max_wait_s=1e-4)
    assert bat.batcher.bucket(64) == 64
    futs = [bat.submit(f"c{c}", jnp.asarray(wl.conversations[c % 4, 0]))
            for c in range(40)]                  # one 40-row wave → bucket 64
    bat.drain()
    for c, fut in enumerate(futs):
        sv, si = seq.query(f"s{c}", jnp.asarray(wl.conversations[c % 4, 0]))
        bv, bi = fut.result(timeout=5)
        np.testing.assert_array_equal(si, bi)
        np.testing.assert_array_equal(sv, bv)


def test_batched_engine_waves_same_conversation(small_corpus, ivf_index):
    """Two turns of one conversation in a single flush are served in
    consecutive waves — the second sees the first's updated session."""
    wl = small_corpus
    cfg = ServingConfig(backend="ivf", strategy="toploc", nprobe=NPROBE,
                        h=H, k=K)
    seq = ConversationalSearchEngine(cfg, ivf_index=ivf_index)
    bat = BatchedConversationalSearchEngine(
        cfg, ivf_index=ivf_index, max_batch=8, max_wait_s=1e-4)
    futs = [bat.submit("c0", jnp.asarray(wl.conversations[0, t]))
            for t in range(3)]
    bat.drain()
    for t, fut in enumerate(futs):
        sv, si = seq.query("c0", jnp.asarray(wl.conversations[0, t]))
        bv, bi = fut.result(timeout=5)
        np.testing.assert_array_equal(si, bi)
        np.testing.assert_array_equal(sv, bv)
    assert [r.turn for r in bat.records] == [0, 1, 2]


@pytest.mark.parametrize("backend", ["ivf", "ivf_pq"])
def test_evicted_live_conversation_resumes_as_first_turn(
        small_corpus, ivf_index, ivf_pq_index, backend):
    """LRU-evicting a live conversation then resuming it must re-run the
    first-turn path: a fresh start on the *current* utterance, not a
    follow-up step against another conversation's slot contents."""
    wl = small_corpus
    cfg = ServingConfig(backend=backend, strategy="toploc+", nprobe=NPROBE,
                        h=H, alpha=0.3, k=K, rerank=32)
    bat = BatchedConversationalSearchEngine(
        cfg, ivf_index=ivf_index, ivf_pq_index=ivf_pq_index,
        n_slots=2, max_batch=2, max_wait_s=1e-4)
    if backend == "ivf":
        idx, bk = ivf_index, IVFBackend(h=H, nprobe=NPROBE)
    else:
        idx, bk = ivf_pq_index, IVFPQBackend(h=H, nprobe=NPROBE, rerank=32)

    qa0, qa1 = jnp.asarray(wl.conversations[0, 0]), \
        jnp.asarray(wl.conversations[0, 1])
    bat.query("a", qa0)                       # slot 0
    bat.query("b", jnp.asarray(wl.conversations[1, 0]))   # slot 1 (full)
    bat.query("c", jnp.asarray(wl.conversations[2, 0]))   # evicts LRU 'a'
    assert bat.store.evictions == 1
    assert bat.store.lookup("a") is None
    # 'a' returns mid-conversation: must be served as a first turn
    v, i = bat.query("a", qa1)
    rv, ri, _, rst = toploc.start(bk, idx, qa1, k=K)
    np.testing.assert_array_equal(v, np.asarray(rv))
    np.testing.assert_array_equal(i, np.asarray(ri))
    rec = bat.records[-1]
    assert rec.conv_id == "a" and rec.turn == 1      # host turn counter…
    assert rec.centroid_dists == idx.p               # …but first-turn work
    assert rec.refreshed and rec.i0 == -1
    # and the rebuilt session continues as a normal follow-up
    bat.query("a", jnp.asarray(wl.conversations[0, 2]))
    assert bat.records[-1].centroid_dists in (H, H + idx.p)


def test_trash_slot_never_leaks_into_live_rows(small_corpus, ivf_index):
    """A padded trash-slot row must never surface scores to a caller or
    mutate the stats/sessions of real rows."""
    wl = small_corpus
    cfg = ServingConfig(backend="ivf", strategy="toploc+", nprobe=NPROBE,
                        h=H, alpha=0.3, k=K)
    bat = BatchedConversationalSearchEngine(
        cfg, ivf_index=ivf_index, max_batch=4, max_wait_s=1e-4)
    # every flush of 3 pads to bucket 4 → one trash row per flush
    for t in range(3):
        for c in range(3):
            bat.submit(f"c{c}", jnp.asarray(wl.conversations[c, t]))
        bat.drain()
    # no records for the trash row: exactly 3 convs x 3 turns
    assert len(bat.records) == 9
    assert {r.conv_id for r in bat.records} == {"c0", "c1", "c2"}
    assert [r.turn for r in sorted(bat.records,
                                   key=lambda r: (r.conv_id, r.turn))] \
        == [0, 1, 2] * 3
    # live slab rows equal the sequential per-conversation sessions
    bk = IVFBackend(h=H, nprobe=NPROBE, alpha=0.3)
    seq_sess = {}
    for c in range(3):
        v, i, s, _ = toploc.start(bk, ivf_index,
                                  jnp.asarray(wl.conversations[c, 0]), k=K)
        for t in (1, 2):
            v, i, s, _ = toploc.step(bk, ivf_index, s,
                                     jnp.asarray(wl.conversations[c, t]),
                                     k=K)
        seq_sess[f"c{c}"] = s
    for c in range(3):
        slot = bat.store.lookup(f"c{c}")
        row = bat.store.gather([slot])
        for f in toploc.IVFSession._fields:
            assert bool((getattr(row, f)[0]
                         == getattr(seq_sess[f"c{c}"], f)).all()), (c, f)
    # the trash row itself was scattered to (turn bumped) but that state
    # is unreachable: no conversation maps to the trash slot
    assert bat.store.trash_slot not in [
        bat.store.lookup(f"c{c}") for c in range(3)]


def test_batched_engine_padding_never_corrupts_sessions(small_corpus,
                                                        ivf_index):
    """A batch of 3 pads to bucket 4; the padded row lands in the trash
    slot and follow-up turns stay bit-identical to sequential."""
    wl = small_corpus
    cfg = ServingConfig(backend="ivf", strategy="toploc+", nprobe=NPROBE,
                        h=H, alpha=0.3, k=K)
    seq = ConversationalSearchEngine(cfg, ivf_index=ivf_index)
    bat = BatchedConversationalSearchEngine(
        cfg, ivf_index=ivf_index, max_batch=4, max_wait_s=1e-4)
    for t in range(T):
        futs = []
        for c in range(3):                      # 3 → padded to 4
            qv = jnp.asarray(wl.conversations[c, t])
            futs.append((seq.query(f"c{c}", qv), bat.submit(f"c{c}", qv)))
        bat.drain()
        for (sv, si), fut in futs:
            bv, bi = fut.result(timeout=5)
            np.testing.assert_array_equal(si, bi)
            np.testing.assert_array_equal(sv, bv)


# --------------------------------------- accounting + continuous batching

def test_latency_accounting_splits_queue_wait_from_service(small_corpus,
                                                           ivf_index):
    """latency_s is service time in BOTH engines; queueing shows up only
    in the batched engine's queue_wait_s.  refresh_rate (the effectiveness
    proxy) matches between the two on identical interleaved traffic."""
    wl = small_corpus
    cfg = ServingConfig(backend="ivf", strategy="toploc+", nprobe=NPROBE,
                        h=H, alpha=0.3, k=K)
    seq = ConversationalSearchEngine(cfg, ivf_index=ivf_index)
    bat = BatchedConversationalSearchEngine(
        cfg, ivf_index=ivf_index, max_batch=4, max_wait_s=1e-4)
    for t in range(T):
        for c in range(3):
            qv = jnp.asarray(wl.conversations[c, t])
            seq.query(f"c{c}", qv)
            bat.submit(f"c{c}", qv)
        bat.drain()
    assert all(r.queue_wait_s == 0.0 for r in seq.records)
    assert all(r.queue_wait_s >= 0.0 and r.latency_s >= 0.0
               for r in bat.records)
    for s in (seq.summary(), bat.summary()):
        assert {"mean_queue_wait_ms", "p95_request_ms",
                "p95_latency_ms"} <= s.keys()
        # request time = wait + service, so the request p95 dominates
        assert s["p95_request_ms"] >= s["p95_latency_ms"]
    assert seq.summary()["refresh_rate"] == bat.summary()["refresh_rate"]


def test_refresh_rate_counts_followup_turns_only(small_corpus, ivf_index):
    """refresh_rate is the fraction of FOLLOW-UP turns that refreshed.
    With interleaved conversations the records list is not grouped by
    conversation, so 'skip the first record' would miscount — the fix
    filters on r.turn > 0."""
    wl = small_corpus
    cfg = ServingConfig(backend="ivf", strategy="toploc+", nprobe=NPROBE,
                        h=H, alpha=-1.0, k=K)   # alpha<0: never refresh
    eng = ConversationalSearchEngine(cfg, ivf_index=ivf_index)
    # interleave: c0 turn0, c1 turn0, c0 turn1, c1 turn1 — two of the
    # four records are first turns (refreshed=True by convention) and
    # neither sits at records[0]... records[1:] would count one of them
    for t in range(2):
        for c in range(2):
            eng.query(f"c{c}", jnp.asarray(wl.conversations[c, t]))
    first_flags = [(r.turn, r.refreshed) for r in eng.records]
    assert [t for t, _ in first_flags] == [0, 0, 1, 1]
    assert eng.summary()["refresh_rate"] == float(np.mean(
        [r.refreshed for r in eng.records if r.turn > 0]))
    assert eng.summary()["refresh_rate"] == 0.0


def test_two_in_flight_waves_preserve_order_and_identity(small_corpus,
                                                         ivf_index):
    """Continuous batching at the engine level: flush() launches a wave
    and returns before its results are fetched; a later flush of the
    SAME conversations gathers the updated slab rows (device-stream
    ordering through the slab), so repeated flush-without-sync stays
    bit-identical to sequential."""
    wl = small_corpus
    cfg = ServingConfig(backend="ivf", strategy="toploc+", nprobe=NPROBE,
                        h=H, alpha=0.3, k=K)
    seq = ConversationalSearchEngine(cfg, ivf_index=ivf_index)
    with BatchedConversationalSearchEngine(
            cfg, ivf_index=ivf_index, max_batch=4, max_wait_s=1e-4,
            max_inflight=2) as bat:
        futs = []
        for t in range(T):                   # one launched wave per turn,
            for c in range(4):               # never more than 2 retired
                qv = jnp.asarray(wl.conversations[c, t])
                futs.append((seq.query(f"c{c}", qv),
                             bat.submit(f"c{c}", qv)))
            assert bat.flush() == 4
            assert bat.batcher.inflight <= 2
        # with 4 launches and max_inflight=2, the first two waves were
        # retired by later launches — their futures already resolved
        assert futs[0][1].done() and futs[7][1].done()
        assert not futs[-1][1].done()
        bat.sync()
        assert bat.batcher.inflight == 0
        for (sv, si), fut in futs:
            bv, bi = fut.result(timeout=5)
            np.testing.assert_array_equal(si, bi)
            np.testing.assert_array_equal(sv, bv)
        # per-conversation turn order in the records is monotonic
        for c in range(4):
            turns = [r.turn for r in bat.records if r.conv_id == f"c{c}"]
            assert turns == sorted(turns)


def test_same_conversation_across_inflight_flushes(small_corpus, ivf_index):
    """Three turns of one conversation across three un-synced flushes:
    turn t+1's gather must see turn t's scatter even while both waves
    are in flight."""
    wl = small_corpus
    cfg = ServingConfig(backend="ivf", strategy="toploc", nprobe=NPROBE,
                        h=H, k=K)
    seq = ConversationalSearchEngine(cfg, ivf_index=ivf_index)
    with BatchedConversationalSearchEngine(
            cfg, ivf_index=ivf_index, max_batch=2, max_wait_s=1e-4,
            max_inflight=2) as bat:
        futs = []
        for t in range(3):
            futs.append(bat.submit("c0", jnp.asarray(wl.conversations[0, t])))
            bat.flush()
        bat.sync()
        for t, fut in enumerate(futs):
            sv, si = seq.query("c0", jnp.asarray(wl.conversations[0, t]))
            bv, bi = fut.result(timeout=5)
            np.testing.assert_array_equal(si, bi)
            np.testing.assert_array_equal(sv, bv)
        assert [r.turn for r in bat.records] == [0, 1, 2]


def test_end_conversation_waits_for_inflight_waves(small_corpus, ivf_index):
    """Releasing a session while its wave is still in flight must not
    wipe the slab row out from under the pending scatter: the engine
    syncs before release."""
    wl = small_corpus
    cfg = ServingConfig(backend="ivf", strategy="toploc+", nprobe=NPROBE,
                        h=H, alpha=0.3, k=K)
    with BatchedConversationalSearchEngine(
            cfg, ivf_index=ivf_index, max_batch=2, max_wait_s=1e-4,
            max_inflight=2) as bat:
        fut = bat.submit("c0", jnp.asarray(wl.conversations[0, 0]))
        bat.flush()                          # launched, not retired
        bat.end_conversation("c0")           # must sync first
        v, i = fut.result(timeout=5)
        rv, ri, _, _ = toploc.start(
            IVFBackend(h=H, nprobe=NPROBE, alpha=0.3), ivf_index,
            jnp.asarray(wl.conversations[0, 0]), k=K)
        np.testing.assert_array_equal(i, np.asarray(ri))
        np.testing.assert_array_equal(v, np.asarray(rv))
        assert bat.store.lookup("c0") is None


def test_clear_zeroes_many_slots_in_one_dispatch(ivf_index, monkeypatch):
    """clear() tiles the zero template over the batch and issues ONE
    jitted scatter (it used to dispatch once per slot — the result
    cache's tombstone sweep can hand it hundreds of rows at once), with
    slot-freed listener semantics unchanged: clear() itself never
    notifies, release/eviction still notify once after zeroing."""
    from repro.serving import sessions as SS

    store = ivf_session_store(ivf_index, h=H, nprobe=NPROBE, n_slots=8)
    slots = []
    for cid in "abcd":
        s, _ = store.acquire(cid)
        dirty = jax.tree.map(
            lambda a: a + 1 if a.dtype == jnp.int32 else a + 1.0,
            store.gather([s]))
        store.scatter([s], dirty)
        slots.append(s)

    calls = []
    real = SS._scatter_slab

    def counting(slab, idx, updates):
        calls.append(int(idx.shape[0]))
        return real(slab, idx, updates)

    monkeypatch.setattr(SS, "_scatter_slab", counting)
    store.clear(slots)
    assert calls == [len(slots)]             # one batched dispatch
    rows = store.gather(slots)
    for f in toploc.IVFSession._fields:
        assert bool((getattr(rows, f) == 0).all()), f
    store.clear([])                          # empty batch: no dispatch
    assert calls == [len(slots)]

    freed = []
    store.add_slot_freed_listener(freed.append)
    store.clear([slots[1]])
    assert freed == []                       # clear() is not a hand-over
    store.release("a")
    assert freed == [slots[0]]


# ------------------------------------------- the session store's wave program

def _parent_wave(store, slots, q, is_first, bk, index):
    """A wave as composed before the wave program: an eager ``a[idx]``
    gather of every slab field, ``toploc.step_batch``, the donating
    row scatter."""
    from repro.serving import sessions as SS
    idx = jnp.asarray(np.asarray(slots, np.int32))
    sess = jax.tree.map(lambda a: a[idx], store.slab)
    v, i, new, stats = toploc.step_batch(bk, index, sess, q, k=K,
                                         is_first=jnp.asarray(is_first))
    with store._lock:
        store._slab = SS._scatter_slab(store._slab, idx, new)
    return v, i, stats


@pytest.mark.parametrize("family", ["ivf", "ivf_pq", "hnsw"])
def test_wave_program_matches_gather_step_scatter(small_corpus, ivf_index,
                                                   ivf_pq_index, hnsw_index,
                                                   family):
    """``sessions.step_batch`` run through ``SessionStore.step`` is bit
    for bit the gather → step → scatter it replaced: scores, ids, every
    counter and the whole slab after each wave, over waves with padded
    trash rows, first turns, follow-ups, LRU evictions and a release."""
    from repro.serving import sessions as SS
    bk, index = {
        "ivf": (IVFBackend(h=H, nprobe=NPROBE, alpha=0.3), ivf_index),
        "ivf_pq": (IVFPQBackend(h=H, nprobe=NPROBE, alpha=0.3, rerank=32),
                   ivf_pq_index),
        "hnsw": (HNSWBackend(ef=EF, up=UP), hnsw_index)}[family]
    stores = [SS.store_for_backend(bk, index, n_slots=4) for _ in range(2)]
    old, new = stores
    qs = np.asarray(small_corpus.conversations, np.float32)
    # (conversation, turn) rows of each wave; 4 slots over 6
    # conversations evict, "c2" is released between waves
    waves = [[(0, 0), (1, 0), (2, 0)], [(0, 1), (1, 1), (2, 1), (3, 0)],
             [(3, 1), (0, 2)], [(4, 0), (2, 2), (5, 0)],
             [(1, 2), (4, 1), (0, 3)], [(5, 1)]]
    for w, wave in enumerate(waves):
        if w == 4:
            assert old.release("c2") == new.release("c2") is not None
        bb = 4 if len(wave) > 2 else 2
        slots = np.full((bb,), old.trash_slot, np.int32)
        is_first = np.zeros((bb,), bool)
        for row, (c, _) in enumerate(wave):
            got = [s.acquire(f"c{c}") for s in stores]
            assert got[0] == got[1]
            slots[row], is_first[row] = got[0]
        q = jnp.asarray(np.stack([qs[c % len(qs), t] for c, t in wave]
                                 + [np.zeros_like(qs[0, 0])]
                                 * (bb - len(wave))))
        want = _parent_wave(old, slots, q, is_first, bk, index)
        got = new.step(slots, index, q, jnp.asarray(is_first),
                       step=toploc.step_batch, backend=bk, k=K)
        assert got[3] is None and got[4] is None       # cache off
        np.testing.assert_array_equal(want[0], got[0])
        np.testing.assert_array_equal(want[1], got[1])
        for f in toploc.TurnStats._fields:
            np.testing.assert_array_equal(getattr(want[2], f),
                                          getattr(got[2], f), err_msg=f)
        for f, a, b in zip(old.slab._fields, old.slab, new.slab):
            np.testing.assert_array_equal(a, b, err_msg=f"wave {w}: {f}")
    assert old.stats() == new.stats()
    assert new.stats()["evictions"] > 0
