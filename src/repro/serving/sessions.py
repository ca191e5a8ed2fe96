"""Device-resident session store for batched conversational serving.

The sequential engine keeps one ``IVFSession`` / ``HNSWSession`` pytree
per conversation in a Python dict — fine for one turn at a time, useless
for batched dispatch (B separate pytrees would need B gathers anyway).
``SessionStore`` instead keeps *one* struct-of-arrays slab per field with
a fixed slot count:

    cache_ids  (S+1, h)      int32      entry_point (S+1,) int32
    cache_vecs (S+1, h, d)   float32    turn        (S+1,) int32
    anchor_sel (S+1, np)     int32
    refreshes  (S+1,)        int32
    turn       (S+1,)        int32

so serving a micro-batch is one jitted wave program (``step_batch``)
that reads the wave's B rows and steps them, then one donating scatter
that writes the B rows back in place.  The slab never leaves the store:
``SessionStore.step`` hands it to the program and takes it back.  Slot
bookkeeping (conv_id → slot, free list, LRU eviction) is host-side
Python — it is O(B) dict work per flush and never touches device
memory.

Rows are read by one dynamic slice each, never by a gather: the TPU
compiler lowers a gather of whole (h, d) rows (``slab[idx]``,
``jnp.take``, ``.at[idx].get``) into a copy of the operand's column
chunks, which moves the whole 6.45 GB slab of the CAsT configuration to
read 32 rows of 3 MB.

Slot model
  * ``n_slots`` live slots are allocated from a free list; slot ids are
    stable for the lifetime of a conversation (sticky sessions).
  * One extra **trash slot** (index ``n_slots``) absorbs the padded rows
    of a partially-filled device batch: padded rows gather/scatter the
    trash row, so they can run the full batched program without ever
    corrupting a live session.
  * When the store is full, the least-recently-served conversation is
    evicted.  An evicted conversation that returns is treated as a first
    turn again (its C0 cache / entry point is rebuilt from the current
    utterance) — the same semantics as a TopLoc_IVF+ refresh, so
    effectiveness degrades gracefully rather than failing.

The store is **per-replica state**: on a 2-D ``(replica, shard)``
serving mesh each replica engine owns its own slab on its own device
group, and ``serving.router.ReplicatedSearchEngine`` pins a
conversation to one replica for its lifetime — a session gathered on
replica r must be scattered back to the same slab, and cross-replica
migration would lose the C0 cache (DESIGN.md §2).  When the *corpus*
is sharded over a device mesh (``distributed.retrieval``) the slab
replicates over that mesh — sessions are the replicated TopLoc state;
only posting lists / vector rows shard.

Continuous batching note: the engine launches wave N+1 before wave N's
results are fetched.  This is safe *because* every wave chains through
the slab on one device stream — wave N's program and its ``scatter``
(each consuming the donated slab) are enqueued before wave N+1's
program, so in-order stream execution gives wave N+1 the updated rows
and donation never frees a buffer a pending program still reads.
Release and eviction scatters join the same chain.
"""
from __future__ import annotations

import functools
import threading
import warnings
from collections import OrderedDict
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as _P

from repro.concurrency import guarded_by
from repro.core import hnsw as _hnsw
from repro.core import ivf as _ivf
from repro.core import pq as _pq


def _rows(a: jax.Array, idx: jax.Array) -> jax.Array:
    """Rows ``idx`` ((B,) int32, B static) of ``a``, one dynamic slice
    each, stacked: moves B rows where a gather would move the slab (see
    the module docstring).  Slots lie in ``[0, n_slots]``, so no slice
    is clamped."""
    return jnp.stack([lax.dynamic_index_in_dim(a, idx[r], keepdims=False)
                      for r in range(idx.shape[0])])


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("step", "backend", "k", "fuse"))
def step_batch(slab: Any, idx: jax.Array, index: Any, q: jax.Array,
               is_first: jax.Array, entries: Any = None,
               corpus: Optional[jax.Array] = None, *, step, backend,
               k: int, fuse=None):
    """One wave's device work on the session slab, as one program.

    Reads the rows ``idx`` of every slab field (``_rows``), runs the
    batched TopLoc ``step`` of ``backend`` on them, and, when the result
    cache is on, its ``fuse`` (``result_cache.WaveFuse``) over the
    wave's cache ``entries`` and ``corpus``.  ``step`` is
    ``toploc.step_batch`` as the engine finds it at each launch, so a
    wrapped or replaced step (tests, ``chipbench/limits.py``'s planted
    faults) runs from the next launch on.  The slab is donated and
    returned untouched, so the program neither copies it nor leaves a
    reader of it behind; ``SessionStore.step`` writes the new rows back.

    Returns (slab, sessions, v, i, stats, entries, hit); ``entries`` and
    ``hit`` are None with the cache off.
    """
    sess = jax.tree.map(lambda a: _rows(a, idx), slab)
    v, i, new_sess, stats = step(backend, index, sess, q, k=k,
                                 is_first=is_first)
    hit = None
    if fuse is not None:
        v, i, new_sess, stats, entries, hit = fuse(
            entries, q, v, i, sess, new_sess, stats, corpus)
    return slab, new_sess, v, i, stats, entries, hit


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_slab(slab: Any, idx: jax.Array, updates: Any) -> Any:
    """Scatter batched session rows into the slab.

    The old slab is donated: on TPU the row writes happen in place, so a
    flush costs O(B · row) instead of an O(S · row) slab copy.  (CPU jax
    ignores the donation and copies — correct either way.)
    """
    return jax.tree.map(lambda a, u: a.at[idx].set(u), slab, updates)


@guarded_by("_lock", "_slab", "_slot_of", "_free",
            "allocs", "evictions", "hits")
class SessionStore:
    """Fixed-capacity struct-of-arrays slab of per-conversation state.

    Thread safety: slot bookkeeping and the slab reference are guarded
    by an internal ``RLock`` (reentrant because ``acquire``/``release``/
    ``step`` scatter through ``self.scatter`` while holding it).  The
    batched engine serializes its wave path through the MicroBatcher's
    drain lock, but ``release`` arrives on *client* threads
    (``end_conversation``) — without the store lock, a release racing a
    wave's ``acquire`` could interleave the free-list append with an
    LRU eviction and hand one slot to two conversations.  Lock
    acquisition order is always batcher drain lock → store lock, never
    the reverse (the store calls nothing that flushes).
    """

    def __init__(self, template: Any, n_slots: int, *, mesh: Any = None):
        """``template``: a single-session pytree (no leading batch dim)
        whose leaf shapes/dtypes define the slab layout.

        ``mesh``: optional corpus mesh (distributed.retrieval) — the slab
        is *replicated* over it, matching the replicated TopLoc session
        state of the sharded scan paths (only the corpus shards).
        """
        if n_slots < 1:
            raise ValueError("n_slots must be >= 1")
        self.n_slots = n_slots
        self._slab = jax.tree.map(
            lambda a: jnp.zeros((n_slots + 1,) + jnp.shape(a),
                                jnp.asarray(a).dtype), template)
        # the all-zero row scattered over released slots (one row batch)
        self._zero_row = jax.tree.map(
            lambda a: jnp.zeros((1,) + jnp.shape(a), jnp.asarray(a).dtype),
            template)
        if mesh is not None:
            rep = lambda a: jax.device_put(a, NamedSharding(mesh, _P()))
            self._slab = jax.tree.map(rep, self._slab)
            self._zero_row = jax.tree.map(rep, self._zero_row)
        self._lock = threading.RLock()
        self._free = list(range(n_slots - 1, -1, -1))   # pop() → slot 0 first
        self._slot_of: "OrderedDict[str, int]" = OrderedDict()  # LRU order
        self._slot_freed_listeners: list = []
        self.allocs = 0
        self.evictions = 0
        self.hits = 0

    # -- slot bookkeeping (host) --------------------------------------

    @property
    def trash_slot(self) -> int:
        """Slot absorbing padded batch rows; never mapped to a conv."""
        return self.n_slots

    def add_slot_freed_listener(self, fn) -> None:
        """Register ``fn(slot)`` to run whenever a slot leaves its
        conversation (release or LRU eviction), *after* the slab row has
        been zeroed.  Companion per-slot state — e.g. the serving
        result cache's slab (``serving.result_cache``) — hooks in here
        so a recycled slot can never leak another conversation's
        entries."""
        self._slot_freed_listeners.append(fn)

    def _notify_slot_freed(self, slot: int) -> None:
        for fn in self._slot_freed_listeners:
            fn(slot)

    @property
    def occupancy(self) -> int:
        with self._lock:
            return len(self._slot_of)

    def lookup(self, conv_id: str) -> Optional[int]:
        with self._lock:
            return self._slot_of.get(conv_id)

    def acquire(self, conv_id: str) -> Tuple[int, bool]:
        """Slot for ``conv_id``; allocates (evicting LRU if full).

        Returns (slot, is_new) — ``is_new`` means the slot holds no state
        for this conversation and the caller must treat the turn as a
        first turn (full cache build).
        """
        with self._lock:
            slot = self._slot_of.get(conv_id)
            if slot is not None:
                self._slot_of.move_to_end(conv_id)
                self.hits += 1
                return slot, False
            if not self._free:
                lru_id, lru_slot = next(iter(self._slot_of.items()))
                del self._slot_of[lru_id]
                self._free.append(lru_slot)
                self.evictions += 1
                # same leak protection as release(): the evicted row is
                # wiped before the slot changes hands, so the new
                # occupant can never read the evicted conversation's
                # cache
                self.scatter([lru_slot], self._zero_row)
                self._notify_slot_freed(lru_slot)
            slot = self._free.pop()
            self._slot_of[conv_id] = slot
            self.allocs += 1
            return slot, True

    def release(self, conv_id: str) -> Optional[int]:
        """End a conversation; its slot returns to the free list.

        The released slab row is zeroed (the template row is scattered
        over it) so a freed slot can never leak the prior conversation's
        centroid cache / entry point to a later occupant — a misbehaving
        caller that skips the ``is_first`` rebuild reads zeros, not
        another user's state.  Idempotent: releasing an unknown or
        already-released ``conv_id`` is a no-op returning ``None`` (in
        particular the slot is never double-appended to the free list,
        which would hand one slot to two conversations).
        """
        with self._lock:
            slot = self._slot_of.pop(conv_id, None)
            if slot is not None:
                self._free.append(slot)
                self.scatter([slot], self._zero_row)
                self._notify_slot_freed(slot)
            return slot

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"n_slots": self.n_slots, "occupancy": self.occupancy,
                    "allocs": self.allocs, "evictions": self.evictions,
                    "hits": self.hits}

    # -- device slab access -------------------------------------------

    def step(self, slots: Sequence[int], *args, **kw) -> Tuple:
        """Run one wave's program on the rows ``slots``.

        ``step_batch(slab, idx, *args, **kw)`` takes the slab donated
        and hands it back; the wave's new sessions are scattered over
        ``slots`` (``scatter``'s rule applies) and the rest of its
        outputs, (v, i, stats, entries, hit), returned.  The write-back
        is ``scatter``, the one path by which every slab write (wave,
        release, eviction) reaches the device; on a v5e the second
        dispatch costs the wave no device time (PERF.md §6).
        """
        idx = jnp.asarray(np.asarray(slots, np.int32))
        with self._lock:
            self._slab, sessions, *out = step_batch(self._slab, idx, *args,
                                                    **kw)
            self.scatter(slots, sessions)
        return tuple(out)

    def gather(self, slots: Sequence[int]) -> Any:
        """Session pytree batch for ``slots`` (leading dim len(slots))."""
        idx = jnp.asarray(np.asarray(slots, np.int32))
        with self._lock:
            return jax.tree.map(lambda a: a[idx], self._slab)

    def scatter(self, slots: Sequence[int], sessions: Any) -> None:
        """Write a batched session pytree back into the slab rows.

        ``slots`` may repeat only on the trash slot (padded rows);
        live-slot rows must be unique within one call — the batched
        engine guarantees one turn per conversation per device batch.
        """
        idx = jnp.asarray(np.asarray(slots, np.int32))
        with self._lock, warnings.catch_warnings():
            # CPU backends warn that the donated slab was not consumed
            warnings.filterwarnings("ignore", message=".*[Dd]onat")
            self._slab = _scatter_slab(self._slab, idx, sessions)

    def clear(self, slots: Sequence[int]) -> None:
        """Zero the given slab rows in one scatter (the template row is
        tiled to the batch, not dispatched once per slot)."""
        slots = list(slots)
        if not slots:
            return
        tiled = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (len(slots),) + a.shape[1:]),
            self._zero_row)
        self.scatter(slots, tiled)

    @property
    def slab(self) -> Any:
        """The raw slab pytree (leading dim ``n_slots + 1``).  Read-only
        view for bulk inspection (e.g. the result cache's tombstone
        sweep); mutate only through ``scatter``/``clear``."""
        with self._lock:
            return self._slab


def store_for_backend(backend: Any, index: Any, *, n_slots: int,
                      mesh: Any = None) -> Optional[SessionStore]:
    """Slab sized by ``backend.session_template(index)`` — the generic
    constructor both engines use (``core.backend`` registry).  Returns
    None for stateless backends (no per-conversation state)."""
    template = backend.session_template(index)
    if template is None:
        return None
    return SessionStore(template, n_slots, mesh=mesh)


def ivf_session_store(index: "_ivf.IVFIndex | _pq.IVFPQIndex", *, h: int,
                      nprobe: int, n_slots: int,
                      mesh: Any = None) -> SessionStore:
    """Slab of ``toploc.IVFSession`` rows sized for ``index`` (reads
    only the ``.d``/``.centroids`` fields both index types share)."""
    from repro.core import backend as _backend
    return store_for_backend(_backend.IVFBackend(h=h, nprobe=nprobe),
                             index, n_slots=n_slots, mesh=mesh)


def ivf_pq_session_store(index: _pq.IVFPQIndex, *, h: int, nprobe: int,
                         n_slots: int, mesh: Any = None) -> SessionStore:
    """Slab for the IVF-PQ backend.

    TopLoc_IVFPQ reuses the ``IVFSession`` layout unchanged (the
    centroid cache is identical — only the list scan differs), so this
    delegates to the float-IVF store builder, which only reads the
    ``.d``/``.centroids`` fields both index types share.
    """
    return ivf_session_store(index, h=h, nprobe=nprobe, n_slots=n_slots,
                             mesh=mesh)


def hnsw_session_store(index: _hnsw.HNSWIndex, *, n_slots: int,
                       mesh: Any = None) -> SessionStore:
    """Slab of ``toploc.HNSWSession`` rows."""
    from repro.core import backend as _backend
    return store_for_backend(_backend.HNSWBackend(), index,
                             n_slots=n_slots, mesh=mesh)
