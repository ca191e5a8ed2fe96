"""Conversational serving engine — TopLoc as a first-class feature.

Python-side session orchestration around the jitted core:
  * per-conversation TopLoc state (IVF centroid cache / HNSW entry
    point) held device-resident between turns;
  * the retrieval backend resolved ONCE from the ``core.backend``
    registry (``ServingConfig.backend`` is just the registry name) —
    both engines drive it exclusively through the generic
    ``toploc.start/step/plain(+_batch)`` drivers, so adding a backend
    to the registry adds it to serving with zero engine edits and zero
    ``backend == "..."`` branches;
  * an optional session-level historical-embedding **result cache**
    (``serving.result_cache``, Frieder et al.): when a turn's query is
    cosine-close to the session's cached query, the turn is answered
    from the cached documents without touching the backend;
  * work + latency accounting per turn (feeds benchmarks/table1.py).

Two engines share the accounting:

``ConversationalSearchEngine`` — one turn per dispatch, sessions in a
Python dict.  The reference implementation and the oracle the batched
path is tested against.

``BatchedConversationalSearchEngine`` — the serving-scale path: requests
enter a ``scheduler.MicroBatcher``; each flush drains up to ``max_batch``
requests, pads to the next shape bucket, and runs ONE jitted wave
program on the device-resident ``sessions.SessionStore`` slab
(``sessions.step_batch``): it reads the wave's session rows and runs the
batched TopLoc step (``toploc.step_batch``) with an ``is_first`` mask
for rows whose conversation has no cached state; the updated sessions
are scattered back in place.  A flush containing several turns of the
same conversation is split into consecutive waves (a later turn must
observe the earlier turn's updated cache), so one device batch never
holds a conversation twice.  With the result cache enabled, the wave
program adds one fused probe over the cache rows (same slot ids as the
session slab);
hit rows take the cached answer, keep their session untouched, and
report zero backend work — exactly what the sequential engine does when
it skips the dispatch, so the two engines stay bit-identical with the
cache on as well as off.  Per-turn ``TurnStats`` are recorded exactly
as the sequential engine records them (tests/test_serving_batched.py).

Sessions are sticky: at multi-host scale the router pins a conversation
to one data-parallel group so its cache stays local (DESIGN.md §2).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.core import backend as _backend
from repro.core import hnsw as _hnsw
from repro.core import ivf as _ivf
from repro.core import pq as _pq
from repro.core import segment as _segment
from repro.core import toploc
from repro.distributed import retrieval as _retrieval
from repro.serving import result_cache as _result_cache
from repro.serving import sessions as _sessions
from repro.serving import telemetry as _telemetry
from repro.serving.scheduler import MicroBatcher, Request


@dataclasses.dataclass
class ServingConfig:
    backend: str = "ivf"          # any core.backend registry name
    strategy: str = "toploc"      # "toploc" | "toploc+" | "plain"
    k: int = 10
    # IVF / IVF-PQ
    nprobe: int = 64
    h: int = 1024                 # cached centroids (TopLoc_IVF)
    alpha: float = 0.1            # refresh threshold (TopLoc_IVF+)
    rerank: int = 64              # exact re-rank depth (IVF-PQ)
    # fused single-dispatch turn (core.toploc.FusedTurn over
    # kernels.fused_turn): opt-in Pallas megakernel for the IVF family —
    # centroid scoring, probe selection, list scan/merge and re-rank in
    # ONE kernel dispatch.  ``precision`` picks the stage-1/2 scoring
    # dtype: "f32" is bit-identical to the 3-dispatch path; "bf16"/
    # "int8" score quantised but always exact-re-rank in float32
    # in-kernel (recall@k floored, benchmarks/fig8_fused.py).  Ignored
    # by backends that don't declare the knob (hnsw, exact).
    fused: bool = False
    precision: str = "f32"
    # HNSW
    ef_search: int = 64
    up: int = 2                   # first-turn ef upscaling
    # corpus sharding (distributed.retrieval): shards > 1 partitions the
    # posting lists / vector corpus over a device mesh; results stay
    # bit-identical to single-device (tests/test_sharded_retrieval.py)
    shards: int = 0               # 0/1 = single device
    mesh: Any = None              # prebuilt jax Mesh (overrides shards)
    shard_axis: str = "model"
    # session-level historical-embedding result cache
    # (serving/result_cache.py): a turn whose query reaches this cosine
    # similarity to the session's cached query is answered from the
    # cached documents without touching the backend.  <= 0 disables the
    # cache — runs are then bit-identical to a cache-absent engine.
    # cache_depth > k over-fetches the backend to that depth and caches
    # the deeper candidate pool (hits rescore it; only the top-k is ever
    # served/recorded); 0 caches exactly the top-k.  The depth is
    # clamped to the backend's fetch limit — the largest request that
    # still executes the plain-k program (nprobe·Lmax for IVF, the
    # re-rank depth for IVF-PQ, ef for HNSW) — so miss turns always
    # serve exactly the uncached top-k.
    cache_threshold: float = 0.0
    cache_depth: int = 0
    # mutable corpus (core.segment): > 0 wraps the backend in a
    # SegmentedBackend with a `segment_cap`-row delta segment, enabling
    # add_documents / delete_documents / compact() on the engine while
    # sessions are live.  0 (default) serves the frozen index exactly as
    # before — no wrapper, byte-identical programs.
    segment_cap: int = 0


@dataclasses.dataclass
class TurnRecord:
    conv_id: str
    turn: int
    # sequential engine: the whole turn, cache lookup to result.
    # Batched engine: from the end of the turn's wave's launch to the end
    # of its retirement, which follows the next launch (max_inflight=2)
    # or an idle tick's sync; it starts after the queue wait below ends
    latency_s: float
    centroid_dists: int
    list_dists: int
    graph_dists: int
    refreshed: bool
    i0: int
    code_dists: int = 0           # PQ ADC evaluations (ivf_pq backend)
    cache_hit: bool = False       # answered from the result cache
    # batched engine: from enqueue to the end of the turn's wave's
    # launch, so it holds the launch's host time and every earlier wave
    # of the same drain; 0 for the sequential engine, which has no
    # queue.  latency_s + queue_wait_s is enqueue -> result
    queue_wait_s: float = 0.0
    # the batched engine's wave id (the ``wave`` of its telemetry
    # spans); -1 for the sequential engine
    wave: int = -1


class _EngineAccounting:
    """Shared per-turn records + summary (sequential and batched engines)."""

    records: List[TurnRecord]

    def summary(self) -> Dict[str, float]:
        if not self.records:
            return {}
        lat = np.asarray([r.latency_s for r in self.records])
        wait = np.asarray([r.queue_wait_s for r in self.records])
        return {
            "turns": len(self.records),
            "mean_latency_ms": float(lat.mean() * 1e3),
            "p95_latency_ms": float(np.percentile(lat, 95) * 1e3),
            "mean_queue_wait_ms": float(wait.mean() * 1e3),
            # client-observed request latency: queue wait + service time
            "p95_request_ms": float(np.percentile(lat + wait, 95) * 1e3),
            "mean_centroid_dists": float(np.mean(
                [r.centroid_dists for r in self.records])),
            "mean_list_dists": float(np.mean(
                [r.list_dists for r in self.records])),
            "mean_graph_dists": float(np.mean(
                [r.graph_dists for r in self.records])),
            "mean_code_dists": float(np.mean(
                [r.code_dists for r in self.records])),
            # refresh is only defined from each conversation's second
            # turn on (turn 0 always runs the full scan) — exclude every
            # conversation's first turn, not just records[0]
            "refresh_rate": float(np.mean(
                [r.refreshed for r in self.records if r.turn > 0]
                or [0.0])),
            "cache_hit_rate": float(np.mean(
                [r.cache_hit for r in self.records])),
        }


class _EngineBase(_EngineAccounting):
    """Backend/index/mesh/cache resolution shared by both engines."""

    def _setup(self, config: ServingConfig, *, ivf_index, hnsw_index,
               ivf_pq_index, doc_vecs) -> None:
        self.cfg = config
        alpha = config.alpha if config.strategy == "toploc+" else -1.0
        fused = (toploc.FusedTurn(precision=config.precision)
                 if config.fused else None)
        self.backend = _backend.make(
            config.backend, h=config.h, nprobe=config.nprobe, alpha=alpha,
            rerank=config.rerank, ef=config.ef_search, up=config.up,
            fused=fused)
        provided = {"ivf_index": ivf_index, "hnsw_index": hnsw_index,
                    "ivf_pq_index": ivf_pq_index, "doc_vecs": doc_vecs}
        self.index = provided.get(self.backend.index_kwarg)
        if self.index is None:
            raise ValueError(f"{config.backend} backend needs "
                             f"{self.backend.index_kwarg}")
        self.doc_vecs = doc_vecs
        # corpus mesh: place the index, plug the sharded scan into the
        # backend; with no mesh both pass through untouched
        mesh = config.mesh
        if mesh is None and config.shards and config.shards > 1:
            mesh = _retrieval.retrieval_mesh(config.shards,
                                             axis=config.shard_axis)
        self.mesh = mesh
        # host-authoritative copies for the mutable-corpus path: segment
        # mutations and compaction run on the unsharded index, then the
        # result is re-placed on the mesh
        inner_plain, index_plain = self.backend, self.index
        self.backend, self.index = self._place(self.backend, self.index)
        # corpus epoch: bumped on every successful mutation (add /
        # delete / compact); cache invalidation and corpus refresh key
        # off it, and readers can use it to detect staleness
        self.corpus_epoch = 0
        self._seg_inner: Optional[_backend.RetrievalBackend] = None
        self._seg_host: Optional[_segment.SegmentedIndex] = None
        if config.segment_cap and config.segment_cap > 0:
            self._seg_inner = inner_plain
            self._seg_host = _segment.make_segmented(
                inner_plain, index_plain, cap=config.segment_cap)
            self.backend = _segment.SegmentedBackend(inner=self.backend)
            self.index = self._placed_segment(
                self._seg_host, base_dev=self.index)
        self.turn_count: Dict[str, int] = {}
        self.records: List[TurnRecord] = []

    def _place(self, backend, index):
        """Put ``index`` on the corpus mesh: returns (backend', index').

        A one-device mesh (the router's one-chip replicas) places the
        index on that device and keeps the single-device program; a
        wider mesh shards it and plugs in the sharded scan.  The
        backend lays the index out for its kernels (``backend.place``)
        on the device it is served from."""
        if self.mesh is None:
            return backend, backend.place(index)
        if self.mesh.size == 1:
            return backend, backend.place(jax.device_put(
                index, NamedSharding(self.mesh, PartitionSpec())))
        return _retrieval.shard_backend(self.mesh, backend,
                                        backend.place(index),
                                        axis=self.cfg.shard_axis)

    @property
    def _sessioned(self) -> bool:
        """Per-conversation state in play this deployment?"""
        return self.backend.stateful and self.cfg.strategy != "plain"

    # -- mutable corpus (core.segment) --------------------------------

    def _placed_segment(self, seg: "_segment.SegmentedIndex", *,
                        base_dev: Any) -> "_segment.SegmentedIndex":
        """Device view of the host-authoritative segment state: the
        (possibly sharded) base plus mesh-replicated delta/tombstone
        arrays."""
        if self.mesh is None:
            return seg._replace(base=base_dev)
        placed = _retrieval.place_segmented(self.mesh,
                                            seg._replace(base=base_dev))
        return placed._replace(base=base_dev)

    def _require_segmented(self) -> None:
        if self._seg_host is None:
            raise RuntimeError(
                "corpus mutation needs ServingConfig.segment_cap > 0 "
                "(the engine is serving a frozen index)")

    def _mutation_scope(self):
        """Engine hook: context under which a corpus mutation swaps the
        index.  The sequential engine needs none (one thread, no
        in-flight work); the batched engine overrides with
        ``batcher.paused()``, which retires in-flight waves AND holds
        the drain lock for the whole swap — a bare sync would leave a
        window where a concurrent flush launches a wave against the
        pre-mutation index, whose futures then resolve (and can serve a
        tombstoned doc) after the mutation returned."""
        return contextlib.nullcontext()

    def _after_mutation(self, *, base_changed: bool) -> None:
        """Re-place the mutated host state on the device/mesh, refresh
        the cache's historical-embedding corpus, and bump the epoch."""
        seg = self._seg_host
        base_dev = self.index.base
        if base_changed:
            # re-place (same plugin, new arrays); the returned backend
            # is discarded — the serving backend already carries it
            _, base_dev = self._place(self._seg_inner, seg.base)
        self.index = self._placed_segment(seg, base_dev=base_dev)
        self.corpus_epoch += 1
        if self._cache is not None:
            self._cache.corpus = self._cache_corpus()

    def add_documents(self, vectors) -> np.ndarray:
        """Ingest new documents into the delta segment (shape-stable:
        no recompilation); returns their assigned global ids."""
        self._require_segmented()
        with self._mutation_scope():
            self._seg_host, ids = _segment.add_documents(self._seg_host,
                                                         vectors)
            # existing cache entries stay valid: their candidate pools
            # simply predate the new docs (documented staleness, same as
            # a miss turn served just before the add)
            self._after_mutation(base_changed=False)
        return ids

    def delete_documents(self, ids) -> None:
        """Tombstone documents by global id; a cache hit can never
        serve them again (intersecting entries are invalidated)."""
        self._require_segmented()
        with self._mutation_scope():
            self._seg_host = _segment.delete_documents(self._seg_inner,
                                                       self._seg_host,
                                                       ids)
            self._after_mutation(base_changed=True)
            # the tombstone sweep must land inside the scope too: a wave
            # launched between the index swap and the sweep could
            # refresh a cache entry that still holds the dead doc
            if self._cache is not None:
                self._cache.invalidate_docs(ids)

    def compact(self, **build_kw) -> None:
        """Fold the delta segment into the base index (background
        maintenance; the one mutation that changes array shapes and so
        costs one retrace).  Results afterwards are bit-identical to a
        from-scratch rebuild (core.segment contract)."""
        self._require_segmented()
        with self._mutation_scope():
            self._compact_locked(**build_kw)

    def _compact_locked(self, **build_kw) -> None:
        if self.doc_vecs is not None:
            # compaction folds delta rows into the base id range; the
            # engine-provided flat corpus must grow with it so cache
            # re-scoring keeps covering ids 0..n_base-1
            fill = _segment.delta_fill(self._seg_host)
            self.doc_vecs = jnp.concatenate(
                [jnp.asarray(self.doc_vecs),
                 self._seg_host.delta_vecs[:fill]], axis=0)
        self._seg_host = _segment.compact(self._seg_inner,
                                          self._seg_host, **build_kw)
        self._after_mutation(base_changed=True)

    def _cache_corpus(self) -> Optional[jax.Array]:
        """Flat (n, d) corpus for historical-embedding re-scoring.

        The segmented path concatenates from the *host* mirror (the
        sharded base pads its row count, which would shift delta ids off
        their rows); delta rows sit at exactly ids n_base..n_base+cap-1.
        """
        if self._seg_host is not None:
            base = (self.doc_vecs if self.doc_vecs is not None
                    else self._seg_inner.corpus_vectors(
                        self._seg_host.base))
            if base is None:
                return None
            return jnp.concatenate(
                [jnp.asarray(base), self._seg_host.delta_vecs], axis=0)
        return (self.doc_vecs if self.doc_vecs is not None
                else self.backend.corpus_vectors(self.index))

    def _make_cache(self, n_slots: Optional[int] = None
                    ) -> Optional[_result_cache.ResultCache]:
        """Result cache iff enabled and the deployment is sessioned
        (the cache is session-level state — plain/stateless serving has
        no session to anchor an entry to)."""
        cfg = self.cfg
        if cfg.cache_threshold <= 0.0 or not self._sessioned:
            return None
        corpus = self._cache_corpus()
        # clamp the over-fetch to the backend's candidate pool: a wider
        # request would either be unsatisfiable (HNSW: top_k over an
        # ef-wide beam) or change which candidates the top-k is drawn
        # from (IVF-PQ: the re-rank pool widens with k)
        depth = min(max(cfg.cache_depth or cfg.k, cfg.k),
                    self.backend.fetch_limit(self.index))
        return _result_cache.ResultCache(
            d=self.backend.query_dim(self.index), k=cfg.k,
            threshold=cfg.cache_threshold, depth=depth,
            corpus=corpus, n_slots=n_slots, mesh=self.mesh)

    @property
    def _k_fetch(self) -> int:
        """Result depth requested from the backend: the cache depth when
        the cache is on (the entry stores the deeper pool; only the
        top-k is served), plain k otherwise — so disabled-cache runs
        execute the exact uncached program."""
        return self._cache.depth if self._cache is not None else self.cfg.k

    def cache_stats(self) -> Dict[str, float]:
        """Result-cache hit/miss counters ({} when the cache is off)."""
        return self._cache.stats() if self._cache is not None else {}


class ConversationalSearchEngine(_EngineBase):
    def __init__(self, config: ServingConfig, *,
                 ivf_index: Optional[_ivf.IVFIndex] = None,
                 hnsw_index: Optional[_hnsw.HNSWIndex] = None,
                 ivf_pq_index: Optional[_pq.IVFPQIndex] = None,
                 doc_vecs: Optional[jax.Array] = None):
        self._setup(config, ivf_index=ivf_index, hnsw_index=hnsw_index,
                    ivf_pq_index=ivf_pq_index, doc_vecs=doc_vecs)
        self.sessions: Dict[str, Any] = {}
        self._cache = self._make_cache()

    # -- public API ---------------------------------------------------

    def query(self, conv_id: str, qvec: jax.Array
              ) -> Tuple[np.ndarray, np.ndarray]:
        """One conversational turn. qvec (d,). Returns (scores, doc_ids)."""
        t0 = time.perf_counter()
        cfg = self.cfg
        turn = self.turn_count.get(conv_id, 0)

        cached = (self._cache.lookup(conv_id, qvec)
                  if self._cache is not None else None)
        if cached is not None:
            v, i = cached
            stats = toploc._zero_stats()
        elif not self._sessioned:
            v, i, stats = toploc.plain(self.backend, self.index, qvec,
                                       k=self._k_fetch)
        elif turn == 0 or conv_id not in self.sessions:
            v, i, sess, stats = toploc.start(self.backend, self.index,
                                             qvec, k=self._k_fetch)
            self.sessions[conv_id] = sess
        else:
            v, i, sess, stats = toploc.step(self.backend, self.index,
                                            self.sessions[conv_id], qvec,
                                            k=self._k_fetch)
            self.sessions[conv_id] = sess
        if cached is None and self._cache is not None:
            self._cache.update(conv_id, qvec, v, i)
            v, i = v[:cfg.k], i[:cfg.k]

        v = np.asarray(jax.device_get(v))
        i = np.asarray(jax.device_get(i))
        dt = time.perf_counter() - t0
        self.turn_count[conv_id] = turn + 1
        self.records.append(TurnRecord(
            conv_id, turn, dt,
            int(stats.centroid_dists), int(stats.list_dists),
            int(stats.graph_dists), bool(stats.refreshed),
            int(stats.i0), int(stats.code_dists),
            cache_hit=cached is not None))
        return v, i

    def end_conversation(self, conv_id: str) -> None:
        self.sessions.pop(conv_id, None)
        self.turn_count.pop(conv_id, None)
        if self._cache is not None:
            self._cache.invalidate(conv_id)


class BatchedConversationalSearchEngine(_EngineBase):
    """Continuously micro-batched multi-conversation serving front door.

    Requests flow ``submit() → MicroBatcher queue → flush → one padded
    device batch → scatter sessions → resolve futures``.  See the module
    docstring for the flush/wave semantics.

    Batches run as a **continuous-batching loop**: ``flush`` only
    *launches* the device work (jax async dispatch — every op in
    ``_launch_wave`` returns before the device finishes) and hands the
    MicroBatcher a completion thunk; with ``max_inflight=2`` the host
    drains, pads, and launches wave N+1 while wave N is still running on
    device, and wave N's futures/records are resolved when the batcher
    retires it.  Correctness under overlap comes from device-stream
    ordering through the session slab: wave N's write-back is enqueued
    before wave N+1's program, so a conversation appearing in consecutive
    launches still observes its own updated state, and the wave
    invariant (one device batch never holds a conversation twice) is
    enforced per drain exactly as before.

    ``n_slots`` bounds resident conversations; the LRU conversation is
    evicted when a new one arrives at full occupancy and is rebuilt
    (first-turn semantics) if it ever returns.
    """

    def __init__(self, config: ServingConfig, *,
                 ivf_index: Optional[_ivf.IVFIndex] = None,
                 hnsw_index: Optional[_hnsw.HNSWIndex] = None,
                 ivf_pq_index: Optional[_pq.IVFPQIndex] = None,
                 doc_vecs: Optional[jax.Array] = None,
                 n_slots: int = 256, max_batch: int = 32,
                 max_wait_s: float = 0.002,
                 buckets: Sequence[int] = (1, 2, 4, 8, 16, 32),
                 max_inflight: int = 2):
        self._setup(config, ivf_index=ivf_index, hnsw_index=hnsw_index,
                    ivf_pq_index=ivf_pq_index, doc_vecs=doc_vecs)
        # a wave holds up to max_batch distinct conversations, each
        # needing its own live slot — fewer slots would make acquire()
        # evict a conversation acquired earlier in the SAME wave and
        # scatter two rows into one slot (silent session corruption)
        if self.backend.stateful and n_slots < max_batch:
            raise ValueError(
                f"n_slots ({n_slots}) must be >= max_batch ({max_batch})")
        # ensure the bucket table covers max_batch so a full wave never
        # pads to a bucket smaller than itself
        buckets = tuple(sorted(set(buckets) | {max_batch}))
        # session slab replicates over the corpus mesh (sessions are the
        # replicated TopLoc state; only the corpus shards); stateless
        # backends get no store
        self.store = _sessions.store_for_backend(
            self.backend, self.index, n_slots=n_slots, mesh=self.mesh)
        self._cache = self._make_cache(n_slots=n_slots)
        if self._cache is not None:
            # a freed session slot must also drop its cache row, or the
            # slot's next conversation could hit another user's entry
            self.store.add_slot_freed_listener(self._cache.clear_slot)
        self.batcher = MicroBatcher(dispatch_batch=self._dispatch_batch,
                                    max_batch=max_batch,
                                    max_wait_s=max_wait_s, buckets=buckets,
                                    max_inflight=max_inflight)
        # spans of the serving loop (serving.telemetry); None is off.
        # Waves are numbered from 0 at launch
        self.telemetry: Optional[_telemetry.Telemetry] = None
        self._next_wave = 0

    def set_telemetry(self, tel: Optional[_telemetry.Telemetry]) -> None:
        """Record this engine's serving loop into ``tel`` (None: off)."""
        self.telemetry = self.batcher.telemetry = tel

    # -- public API ---------------------------------------------------

    def submit(self, conv_id: str, qvec: jax.Array):
        """Enqueue one conversational turn; resolves at the next flush.

        Returns a ``concurrent.futures.Future`` of (scores, doc_ids).
        """
        return self.batcher.submit(Request(conv_id, qvec))

    def flush(self) -> int:
        """Launch one micro-batch from the queue (serving-loop tick).

        Returns the number of requests launched; their futures resolve
        once the batch is retired (after ``max_inflight`` later
        launches, or at ``sync``/``drain``).
        """
        return self.batcher.flush_loop_once()

    def sync(self) -> None:
        """Retire every in-flight batch (resolves outstanding futures)."""
        self.batcher.sync()

    def drain(self) -> int:
        """Flush until the queue is empty and all launches retired;
        returns turns served."""
        served = 0
        while True:
            n = self.batcher.flush_loop_once()
            if n == 0:
                self.batcher.sync()
                if self.batcher.flush_loop_once() == 0:
                    return served
                continue
            served += n

    def query(self, conv_id: str, qvec: jax.Array
              ) -> Tuple[np.ndarray, np.ndarray]:
        """Synchronous single-turn convenience (submit + flush + sync)."""
        fut = self.submit(conv_id, qvec)
        while not fut.done():
            if self.batcher.flush_loop_once() == 0:
                self.batcher.sync()
        return fut.result()

    def close(self) -> None:
        """Quiesce: retire in-flight launches so no future is left
        pending.  Idempotent; also reachable as a context manager."""
        self.batcher.sync()

    def __enter__(self) -> "BatchedConversationalSearchEngine":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    def _mutation_scope(self):
        # a corpus mutation swaps self.index; paused() retires in-flight
        # waves and holds the drain lock for the whole swap, so no wave
        # is launched against the pre-mutation index while the swap (and
        # the cache's tombstone sweep) is mid-flight — a launched batch
        # never straddles two corpus epochs
        return self.batcher.paused()

    def end_conversation(self, conv_id: str) -> None:
        # release under the paused batcher: a launched wave's scatter
        # still targets this conversation's slot (freeing the slot now
        # could hand it to a conversation in the *next* launch before
        # the scatter executes), and turn_count is otherwise only
        # touched by launches under the drain lock
        with self.batcher.paused():
            if self.store is not None:
                self.store.release(conv_id)
            self.turn_count.pop(conv_id, None)

    # -- batch execution ----------------------------------------------

    def _dispatch_batch(self, reqs: List[Request]
                        ) -> Any:
        """MicroBatcher dispatch callback: launch a drained micro-batch.

        Splits the batch into waves holding at most one turn per
        conversation (turn t+1 must read the session state turn t
        wrote), launches each wave's device work without blocking,
        and returns a completion thunk that device_gets the results and
        writes the ``TurnRecord``s.  The batcher's trailing pad requests
        are dropped here — each wave re-pads itself to its own bucket
        with trash-slot rows, so pad rows never acquire a session slot
        or emit a ``TurnRecord``.
        """
        remaining = [(j, r) for j, r in enumerate(reqs)
                     if r.conv_id != MicroBatcher.PAD_ID]
        finishers = []
        while remaining:
            seen, wave, deferred = set(), [], []
            for item in remaining:
                if item[1].conv_id in seen:
                    deferred.append(item)
                else:
                    seen.add(item[1].conv_id)
                    wave.append(item)
            finishers.append(self._launch_wave(wave))
            remaining = deferred

        def complete() -> List[Any]:
            results: List[Any] = [None] * len(reqs)
            for finish in finishers:
                finish(results)
            return results
        return complete

    def _launch_wave(self, wave):
        """Enqueue one wave's device work (no host-side blocking) and
        return a ``finish(results)`` closure that materializes it.

        Everything up to the returned closure is async dispatch: the
        session store's wave program (``sessions.step_batch``: the
        wave's slab rows read, ``toploc.step_batch`` on them, the cache
        fuse when the cache is on) and the donating write-back of the
        rows enqueue onto the device stream and return.  The closure's
        ``device_get`` calls are the only blocking point — deferred
        until the batcher retires this launch, by which time the next
        wave's host assembly has already overlapped this wave's device
        execution.
        """
        tel = self.telemetry
        wid = self._next_wave
        self._next_wave += 1
        with _telemetry.span(tel, "wave.launch", wid):
            cfg = self.cfg
            b = len(wave)
            bb = self.batcher.bucket(b)          # padded (bucketed) batch size
            with _telemetry.span(tel, "wave.assemble", wid):
                qs = [np.asarray(r.payload, np.float32) for _, r in wave]
                q = jnp.asarray(np.stack(qs + [np.zeros_like(qs[0])]
                                         * (bb - b)))

            hit = None
            if not self._sessioned:
                with _telemetry.span(tel, "wave.step", wid):
                    v, i, stats = toploc.plain_batch(self.backend, self.index,
                                                     q, k=cfg.k)
            else:
                # padded rows run against the trash slot with
                # is_first=False: their zeroed trash session never trips the
                # drift check, so the batch-wide refresh/first-turn gates
                # stay closed on steady-state flushes (marking them first
                # would force the full scan on every non-bucket-exact
                # flush); the scatter writes them back to the trash row,
                # never a live session
                slots = np.full((bb,), self.store.trash_slot, np.int32)
                is_first = np.zeros((bb,), bool)
                with _telemetry.span(tel, "store.acquire", wid):
                    for row, (_, r) in enumerate(wave):
                        slots[row], is_first[row] = self.store.acquire(
                            r.conv_id)
                with _telemetry.span(tel, "wave.step", wid):
                    # with the cache on, the program also probes the cache
                    # rows: hit rows take the cached answer, zero their
                    # work counters, and keep the pre-step session (the
                    # sequential engine skips the dispatch entirely on a
                    # hit — same observable state)
                    cache = self._cache
                    v, i, stats, entries, hit = self.store.step(
                        slots, self.index, q, jnp.asarray(is_first),
                        None if cache is None else cache.gather(slots),
                        None if cache is None else cache.corpus,
                        step=toploc.step_batch, backend=self.backend,
                        k=self._k_fetch,
                        fuse=None if cache is None else cache.wave_fuse)
                    if cache is not None:
                        cache.scatter(slots, entries)

            # turn numbers are claimed at LAUNCH: a later launch holding the
            # same conversation must see this wave's increment even though
            # its records are written at retirement
            turns = []
            for _, r in wave:
                t = self.turn_count.get(r.conv_id, 0)
                self.turn_count[r.conv_id] = t + 1
                turns.append(t)
            t_dispatch = time.perf_counter()

            def finish(results) -> None:
                with _telemetry.span(tel, "wave.fetch", wid):
                    vh = np.asarray(jax.device_get(v))
                    ih = np.asarray(jax.device_get(i))
                    st = jax.tree.map(lambda a: np.asarray(jax.device_get(a)),
                                      stats)
                    hh = None
                    if hit is not None:
                        hh = np.asarray(jax.device_get(hit))
                        self._cache.count_hits(hh, b)
                with _telemetry.span(tel, "wave.records", wid):
                    now = time.perf_counter()
                    for row, ((j, r), turn) in enumerate(zip(wave, turns)):
                        rec = TurnRecord(
                            r.conv_id, turn, now - t_dispatch,
                            int(st.centroid_dists[row]),
                            int(st.list_dists[row]),
                            int(st.graph_dists[row]),
                            bool(st.refreshed[row]), int(st.i0[row]),
                            int(st.code_dists[row]),
                            cache_hit=bool(hh[row]) if hh is not None
                            else False,
                            queue_wait_s=t_dispatch - r.enqueue_t, wave=wid)
                        self.records.append(rec)
                        results[j] = (vh[row], ih[row])
            return finish
