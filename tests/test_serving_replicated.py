"""Replica-routed serving: affinity, load balance, hedging, identity.

The contract under test (serving/router.py + distributed/retrieval.py):
routing conversations over R replica engines — each with its own
session slab, result cache, and (optionally) corpus submesh — is
bit-identical per session to a single engine serving that conversation,
because stateful traffic is pinned to one replica for its lifetime and
stateless traffic is identical on every replica by the replication
contract.  Mesh construction tests follow the device-count gating
pattern of test_sharded_retrieval.py: they run fully on a 1-device
host and exercise real 2-D meshes under the CI 8-device job.
"""
import threading
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.distributed import retrieval as R
from repro.serving import (ConversationalSearchEngine,
                           ReplicatedSearchEngine, ServingConfig)

K, H, NPROBE = 10, 16, 4
T = 4


def _cfg(strategy="toploc+", **kw):
    return ServingConfig(backend="ivf", strategy=strategy, nprobe=NPROBE,
                         h=H, alpha=0.3, k=K, **kw)


def _router(ivf_index, *, replicas=2, strategy="toploc+", n_slots=8,
            **kw):
    return ReplicatedSearchEngine(
        _cfg(strategy=strategy), replicas=replicas, ivf_index=ivf_index,
        n_slots=n_slots, max_batch=4, max_wait_s=1e-4, **kw)


# ------------------------------------------------------------------ mesh

def test_retrieval_mesh_replicas_1_stays_1d():
    mesh = R.retrieval_mesh(1, replicas=1)
    assert mesh.devices.ndim == 1 and mesh.axis_names == ("model",)


@pytest.mark.skipif(jax.device_count() < 4,
                    reason="2-D mesh needs >= 4 devices")
def test_retrieval_mesh_2d_shape_and_axis_names():
    mesh = R.retrieval_mesh(2, replicas=2)
    assert mesh.devices.shape == (2, 2)
    assert mesh.axis_names == ("replica", "model")


def test_retrieval_mesh_too_few_devices_raises():
    with pytest.raises(ValueError, match="device"):
        R.retrieval_mesh(jax.device_count(), replicas=2)


def test_replica_submeshes_1d_passthrough():
    mesh = R.retrieval_mesh(1)
    assert R.replica_submeshes(mesh) == [mesh]


@pytest.mark.skipif(jax.device_count() < 4,
                    reason="2-D mesh needs >= 4 devices")
def test_replica_submeshes_split_is_disjoint_and_covering():
    mesh = R.retrieval_mesh(2, replicas=2)
    subs = R.replica_submeshes(mesh)
    assert len(subs) == 2
    seen = set()
    for sm in subs:
        assert sm.axis_names == ("model",)
        assert sm.devices.shape == (2,)
        seen.update(d.id for d in sm.devices.flat)
    assert seen == {d.id for d in mesh.devices.flat}


# -------------------------------------------------------------- routing

def test_session_affinity_sticky_across_turns(small_corpus, ivf_index):
    wl = small_corpus
    with _router(ivf_index) as eng:
        for t in range(T):
            eng.query("c0", jnp.asarray(wl.conversations[0, t]))
        eng.drain()
        r = eng.replica_of("c0")
        assert r is not None
        # every turn landed on the pinned replica
        assert all(rec.conv_id != "c0" or True for rec in eng.records)
        assert [rec.conv_id for rec in eng.engines[r].records].count("c0") \
            == T
        other = eng.engines[1 - r]
        assert all(rec.conv_id != "c0" for rec in other.records)


def test_affinity_survives_slab_eviction(small_corpus, ivf_index):
    """An LRU eviction inside a replica's slab does NOT unpin: the
    conversation rebuilds on the same replica (single-engine eviction
    semantics), so routed results keep matching a single engine."""
    wl = small_corpus
    # n_slots=4 == max_batch floor; pin 5 convs to thrash one replica
    with _router(ivf_index, n_slots=4) as eng:
        eng.query("a", jnp.asarray(wl.conversations[0, 0]))
        r = eng.replica_of("a")
        # fill replica r's slab past capacity with directly-pinned convs
        with eng._route_lock:
            for j in range(4):
                eng._replica_of[f"f{j}"] = r
                eng._load[r] += 1
        for j in range(4):
            eng.query(f"f{j}", jnp.asarray(wl.conversations[1, 0]))
        assert eng.engines[r].store.evictions >= 1
        assert eng.engines[r].store.lookup("a") is None
        # evicted but still pinned; the next turn resumes on replica r
        assert eng.replica_of("a") == r
        eng.query("a", jnp.asarray(wl.conversations[0, 1]))
        assert eng.replica_of("a") == r
        assert eng.engines[r].store.lookup("a") is not None


def test_least_loaded_pinning_spreads_sessions(small_corpus, ivf_index):
    wl = small_corpus
    with _router(ivf_index) as eng:
        for c in range(4):
            eng.query(f"c{c}", jnp.asarray(wl.conversations[c % 3, 0]))
        ls = eng.load_stats()
        assert ls["per_replica_sessions"] == [2, 2]
        assert ls["per_replica_turns"] == [2, 2]
        assert ls["imbalance"] == 1.0
        # end_conversation unpins and frees capacity
        eng.end_conversation("c0")
        assert eng.replica_of("c0") is None
        assert sum(eng.load_stats()["per_replica_sessions"]) == 3


@pytest.mark.skipif(jax.device_count() < 4,
                    reason="one device per replica needs >= 4 devices")
def test_unsharded_replica_state_lives_on_its_own_device(ivf_index):
    """shards <= 1: replica r's index, session slab and result cache
    are all on device r, not piled onto the default device."""
    cfg = _cfg(cache_threshold=0.95, cache_depth=8)
    with ReplicatedSearchEngine(cfg, replicas=4, ivf_index=ivf_index,
                                n_slots=8, max_batch=4) as eng:
        for r, e in enumerate(eng.engines):
            want = {jax.devices()[r]}
            for tree in (e.index, e.store._slab, e._cache._slab._slab):
                for leaf in jax.tree.leaves(tree):
                    assert leaf.devices() == want, (r, leaf.devices())


def test_replicas_must_match_prebuilt_mesh(ivf_index):
    mesh = R.retrieval_mesh(1)          # 1-D: one replica group
    cfg = _cfg(mesh=mesh)
    with pytest.raises(ValueError, match="replica"):
        ReplicatedSearchEngine(cfg, replicas=2, ivf_index=ivf_index)


# ------------------------------------------------------------- identity

@pytest.mark.parametrize("cache", [False, True])
def test_routed_matches_sequential_per_session(small_corpus, ivf_index,
                                               cache):
    """R=2 routed serving reproduces the sequential single-engine result
    for every (conversation, turn), result cache off and on."""
    wl = small_corpus
    kw = dict(cache_threshold=0.95, cache_depth=8) if cache else {}
    cfg = ServingConfig(backend="ivf", strategy="toploc+", nprobe=NPROBE,
                        h=H, alpha=0.3, k=K, **kw)
    seq = ConversationalSearchEngine(cfg, ivf_index=ivf_index)
    with ReplicatedSearchEngine(
            cfg, replicas=2, ivf_index=ivf_index, n_slots=8, max_batch=4,
            max_wait_s=1e-4) as eng:
        futs = {}
        for t in range(T):
            for c in range(3):
                qv = jnp.asarray(wl.conversations[c, t])
                futs[(c, t)] = (seq.query(f"c{c}", qv),
                                eng.submit(f"c{c}", qv))
            eng.drain()
        for (c, t), ((sv, si), fut) in futs.items():
            bv, bi = fut.result(timeout=5)
            np.testing.assert_array_equal(si, bi, err_msg=f"c{c} t{t}")
            np.testing.assert_array_equal(sv, bv, err_msg=f"c{c} t{t}")
        assert seq.summary()["refresh_rate"] == eng.summary()["refresh_rate"]
        if cache:
            assert eng.cache_stats()["hits"] >= 0


@pytest.mark.skipif(jax.device_count() < 4,
                    reason="(2 replicas x 2 shards) needs >= 4 devices")
def test_routed_sharded_2d_matches_sequential(small_corpus, ivf_index):
    """Full 2-D mesh: 2 replicas x 2 corpus shards, bit-identical to the
    unsharded sequential engine (replication x sharded-scan contracts
    compose)."""
    wl = small_corpus
    seq = ConversationalSearchEngine(_cfg(), ivf_index=ivf_index)
    with ReplicatedSearchEngine(
            _cfg(shards=2), replicas=2, ivf_index=ivf_index, n_slots=8,
            max_batch=4, max_wait_s=1e-4) as eng:
        assert all(e.mesh is not None for e in eng.engines)
        futs = {}
        for t in range(T):
            for c in range(3):
                qv = jnp.asarray(wl.conversations[c, t])
                futs[(c, t)] = (seq.query(f"c{c}", qv),
                                eng.submit(f"c{c}", qv))
            eng.drain()
        for (c, t), ((sv, si), fut) in futs.items():
            bv, bi = fut.result(timeout=5)
            np.testing.assert_array_equal(si, bi, err_msg=f"c{c} t{t}")
            np.testing.assert_array_equal(sv, bv, err_msg=f"c{c} t{t}")


# -------------------------------------------------------------- hedging

def test_stateless_plain_traffic_is_hedged_and_identical(small_corpus,
                                                         ivf_index):
    """strategy='plain' has no session state: the router hedges across
    replicas and the winner's result is bit-identical to the sequential
    plain engine no matter which replica won."""
    wl = small_corpus
    seq = ConversationalSearchEngine(_cfg(strategy="plain"),
                                     ivf_index=ivf_index)
    with _router(ivf_index, strategy="plain",
                 hedge_floor_s=0.0) as eng:
        assert not eng.stateful
        # slow replica 0's dispatch so hedges actually fire and replica 1
        # wins some requests
        real_flush = eng.engines[0].flush

        def slow_flush():
            time.sleep(0.01)
            return real_flush()
        eng.engines[0].flush = slow_flush
        futs = [(seq.query(f"p{j}", jnp.asarray(wl.conversations[j % 3, 0])),
                 eng.submit(f"p{j}", jnp.asarray(wl.conversations[j % 3, 0])))
                for j in range(8)]
        for (sv, si), fut in futs:
            bv, bi = fut.result(timeout=30)
            np.testing.assert_array_equal(si, bi)
            np.testing.assert_array_equal(sv, bv)
        hs = eng.hedge_stats()
        assert hs["calls"] == 8


def test_hedge_stats_exposed_only_for_stateless(ivf_index):
    with _router(ivf_index) as eng:
        assert eng.stateful and eng.hedge_stats() == {}
    with _router(ivf_index, strategy="plain") as eng2:
        assert not eng2.stateful and "calls" in eng2.hedge_stats()


# ------------------------------------------------------------ lifecycle

def test_router_close_is_idempotent_and_stops_pumps(small_corpus,
                                                    ivf_index):
    wl = small_corpus
    eng = _router(ivf_index)
    eng.start()
    assert [t.is_alive() for t in eng._pumps] == [True, True]
    fut = eng.submit("c0", jnp.asarray(wl.conversations[0, 0]))
    fut.result(timeout=30)          # pump threads serve pinned traffic
    pumps = list(eng._pumps)
    eng.close()
    eng.close()                     # idempotent
    assert not eng._pumps
    assert all(not t.is_alive() for t in pumps)
    assert threading.active_count() < 50


def test_router_use_after_close_raises(small_corpus, ivf_index):
    """Every serving/mutation entry point fails loudly after close()
    instead of hanging on dead pumps or mutating torn-down replicas."""
    wl = small_corpus
    eng = ReplicatedSearchEngine(
        _cfg(segment_cap=8), replicas=2, ivf_index=ivf_index,
        doc_vecs=jnp.asarray(wl.doc_vecs), n_slots=8, max_batch=4,
        max_wait_s=1e-4)
    q = jnp.asarray(wl.conversations[0, 0])
    eng.query("c0", q)
    eng.close()
    with pytest.raises(RuntimeError, match="closed"):
        eng.submit("c0", q)
    with pytest.raises(RuntimeError, match="closed"):
        eng.query("c0", q)
    with pytest.raises(RuntimeError, match="closed"):
        eng.add_documents(np.asarray(wl.doc_vecs[:2]))
    with pytest.raises(RuntimeError, match="closed"):
        eng.delete_documents([0])
    with pytest.raises(RuntimeError, match="closed"):
        eng.compact()


def test_router_replicas_must_be_positive(ivf_index):
    with pytest.raises(ValueError, match="replicas"):
        ReplicatedSearchEngine(_cfg(), replicas=0, ivf_index=ivf_index)


def test_query_survives_racing_end_conversation(small_corpus, ivf_index):
    """Regression: query() used to read ``self._replica_of[conv_id]``
    without the route lock — a concurrent ``end_conversation`` landing
    between submit() and that read KeyError'd the turn.  The pin read
    now goes through ``replica_of()`` and a gone pin falls back to
    draining every replica until the (already enqueued) future lands."""
    wl = small_corpus
    with _router(ivf_index) as eng:
        eng.query("c0", jnp.asarray(wl.conversations[0, 0]))
        assert eng.replica_of("c0") is not None

        orig_submit = eng.submit

        def racing_submit(conv_id, qvec):
            fut = orig_submit(conv_id, qvec)
            # the race, made deterministic: the conversation ends right
            # after its turn is enqueued, before query() reads the pin
            eng.end_conversation(conv_id)
            return fut

        eng.submit = racing_submit
        v, i = eng.query("c0", jnp.asarray(wl.conversations[0, 1]))
        assert v.shape == (K,) and i.shape == (K,)
        assert eng.replica_of("c0") is None


def test_router_broadcast_mutations_keep_replicas_identical(
        small_corpus, ivf_index):
    """add/delete/compact broadcast to every replica; ids agree, the
    epoch advances in lockstep, and a deleted doc is gone from results
    on whichever replica serves the follow-up."""
    wl = small_corpus
    n0 = wl.doc_vecs.shape[0]
    with ReplicatedSearchEngine(
            _cfg(segment_cap=8), replicas=2, ivf_index=ivf_index,
            n_slots=8, max_batch=4, max_wait_s=1e-4) as eng:
        ids = eng.add_documents(wl.doc_vecs[:3] * 0.5)
        assert ids.tolist() == [n0, n0 + 1, n0 + 2]
        assert eng.corpus_epoch == 1
        eng.delete_documents([int(ids[1])])
        assert eng.corpus_epoch == 2
        for c in ("a", "b", "c"):    # spread over both replicas
            _, i = eng.query(c, jnp.asarray(wl.doc_vecs[ids[1] - n0] * 0.5))
            assert int(ids[1]) not in np.asarray(i)
        eng.compact()
        assert eng.corpus_epoch == 3
        for c in ("a", "b"):
            _, i = eng.query(c, jnp.asarray(wl.conversations[1, 1]))
            assert i.shape == (K,)
