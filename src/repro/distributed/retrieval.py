"""Device-sharded TopLoc retrieval over a corpus mesh.

The paper's single-device corpus caps scale; this module partitions it
over a ``jax`` mesh (DESIGN.md §2 'Distribution'):

  * **IVF / IVF-PQ** — posting lists (float vectors or PQ codes) are
    sharded *by partition* over the ``model`` axis
    (``sharding.ivf_index_specs`` / ``ivf_pq_index_specs``); coarse
    centroids and PQ codebooks stay replicated.  Each shard ADC/float-
    scans the selection with only its owned lists unmasked, reduces to
    a local top-k, and one k-wide all-gather + ordered merge yields the
    global result — collective payload O(k·shards), independent of
    corpus size.
  * **HNSW** — the vector corpus is sharded *by document row*
    (``hnsw_index_specs``); the (integer) adjacency is replicated so the
    beam traversal itself stays local, and candidate scoring is
    owner-computes + ``psum`` (exactly one shard contributes a non-zero
    dot per candidate, so the sum is exact).
  * The IVF-PQ exact re-rank corpus is doc-row sharded the same way.

TopLoc session state (centroid cache, Eq. 1 drift proxy, refresh gate,
privileged entry point) stays **replicated**: the cheap per-turn
selection math runs identically on every device, only the corpus-sized
scans are distributed.

What sharding buys — and what it doesn't, yet: each device *stores*
only 1/S of the posting lists / code lists / vector corpus (the memory
term that caps single-device corpus size), and each real distance is
*owned* by exactly one shard (the per-device ``real``/``code_d`` work
counters shrink ~linearly — what a sparse scheduler would pay).  The
dense SPMD formulation itself, however, still gathers and multiplies
the full ``(B, nprobe, Lmax, d)`` selection on every shard with foreign
probes clipped-and-masked — per-device FLOPs of one scan dispatch are
not reduced, because skipping foreign probes needs data-dependent
shapes XLA cannot express.  Routing each probe to its owner shard
host-side (variable per-shard probe counts, padded to a static bound)
is the follow-up that converts the owned-work counters into dense
per-device FLOP savings.

Bit-identity contract: for all three backends, sharded results — scores,
ids, every ``TurnStats`` counter — equal the single-device path bit for
bit at any shard count.  Three mechanisms make this hold:

  1. per-candidate arithmetic is shaped exactly like the single-device
     scan (same gather → same einsum/multiply-reduce shapes), so each
     owned candidate's score has the same reduction order;
  2. cross-shard float merges either move *selected candidates* (never
     partial sums) or ``psum`` a single non-zero against exact zeros;
  3. top-k merges use ``core.topk.distributed_topk_ordered``, which
     breaks score ties by global flat candidate position — the same
     tie-break a single-device ``lax.top_k`` applies.

The scan callables below are frozen dataclasses (hashable on the mesh)
so they can ride through ``jax.jit`` as static arguments — they plug
into the ``scan=`` / ``search=`` hooks of ``core.toploc`` and the
serving engines' ``ServingConfig.shards`` knob.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import compat
from repro.core import hnsw as _hnsw
from repro.core import ivf as _ivf
from repro.core import pq as _pq
from repro.core.topk import distributed_topk_ordered
from repro.distributed import sharding as SH
from repro.kernels import ops as _kops


# ---------------------------------------------------------------------------
# mesh + index placement
# ---------------------------------------------------------------------------

def retrieval_mesh(shards: int, *, axis: str = "model",
                   replicas: int = 1,
                   replica_axis: str = "replica") -> Mesh:
    """A corpus mesh over the first ``replicas * shards`` local devices.

    ``replicas == 1`` (the default) keeps the historical 1-D ``(axis,)``
    mesh.  ``replicas > 1`` builds the 2-D ``(replica_axis, axis)`` mesh
    of the serving tier: every replica group holds a **full sharded
    corpus** — all index placement specs name only ``axis``, so
    corpus-sharded arrays auto-replicate along ``replica_axis`` and the
    existing shard_map scan plugins run unchanged on either mesh shape.
    The replica axis is consumed host-side by the
    ``serving.router.ReplicatedSearchEngine``, which slices the mesh
    into per-replica 1-D submeshes (``replica_submeshes``) so each
    replica engine owns a disjoint device group.
    """
    devs = jax.devices()
    if shards < 1 or replicas < 1 or replicas * shards > len(devs):
        raise ValueError(
            f"replicas={replicas} x shards={shards} needs "
            f"{max(replicas, 1) * max(shards, 1)} device(s) but "
            f"{len(devs)} available "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=N "
            "for a host-platform mesh)")
    if replicas == 1:
        return Mesh(np.asarray(devs[:shards]), (axis,))
    grid = np.asarray(devs[:replicas * shards]).reshape(replicas, shards)
    return Mesh(grid, (replica_axis, axis))


def replica_submeshes(mesh: Mesh, *,
                      replica_axis: str = "replica") -> list:
    """Split a 2-D ``(replica, shard)`` mesh into per-replica 1-D corpus
    meshes (one entry per replica group, disjoint devices, shard axis
    name preserved).  A mesh without ``replica_axis`` is already a
    single replica group and is returned as ``[mesh]``.
    """
    if replica_axis not in mesh.axis_names:
        return [mesh]
    ri = mesh.axis_names.index(replica_axis)
    rest = tuple(a for a in mesh.axis_names if a != replica_axis)
    return [Mesh(np.take(mesh.devices, r, axis=ri), rest)
            for r in range(mesh.shape[replica_axis])]


def _pad_dim0(x: jax.Array, mult: int, value) -> jax.Array:
    """Pad dim 0 to a multiple of ``mult`` (shardable row count)."""
    pad = (-x.shape[0]) % mult
    if not pad:
        return x
    widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, widths, constant_values=value)


def _put(mesh: Mesh, x: jax.Array, spec: P) -> jax.Array:
    return jax.device_put(x, NamedSharding(mesh, spec))


def shard_ivf_index(mesh: Mesh, index: _ivf.IVFIndex, *,
                    axis: str = "model") -> _ivf.IVFIndex:
    """Place an ``IVFIndex`` on the mesh per ``sharding.ivf_index_specs``.

    Posting-list arrays are padded with empty partitions (sizes 0, ids
    -1) up to a multiple of the shard count; padded partitions are never
    selected (centroids are *not* padded, so ``top_k`` over the p real
    centroids cannot reach them) and contribute no work.
    """
    s = mesh.shape[axis]
    specs = SH.ivf_index_specs(SH.Axes(model=axis))
    return _ivf.IVFIndex(
        centroids=_put(mesh, index.centroids, specs.centroids),
        list_vecs=_put(mesh, _pad_dim0(index.list_vecs, s, 0.0),
                       specs.list_vecs),
        list_ids=_put(mesh, _pad_dim0(index.list_ids, s, -1),
                      specs.list_ids),
        list_sizes=_put(mesh, _pad_dim0(index.list_sizes, s, 0),
                        specs.list_sizes),
    )


def shard_ivf_pq_index(mesh: Mesh, index: _pq.IVFPQIndex, *,
                       axis: str = "model") -> _pq.IVFPQIndex:
    """Place an ``IVFPQIndex`` on the mesh per ``ivf_pq_index_specs``.

    Code lists pad like the float lists; the re-rank corpus pads with
    zero rows (only ever gathered through real candidate ids).
    """
    s = mesh.shape[axis]
    specs = SH.ivf_pq_index_specs(SH.Axes(model=axis))
    return _pq.IVFPQIndex(
        centroids=_put(mesh, index.centroids, specs.centroids),
        codewords=_put(mesh, index.codewords, specs.codewords),
        list_codes=_put(mesh, _pad_dim0(index.list_codes, s, 0),
                        specs.list_codes),
        list_ids=_put(mesh, _pad_dim0(index.list_ids, s, -1),
                      specs.list_ids),
        list_sizes=_put(mesh, _pad_dim0(index.list_sizes, s, 0),
                        specs.list_sizes),
        doc_vecs=_put(mesh, _pad_dim0(index.doc_vecs, s, 0.0),
                      specs.doc_vecs),
    )


def shard_hnsw_index(mesh: Mesh, index: _hnsw.HNSWIndex, *,
                     axis: str = "model") -> _hnsw.HNSWIndex:
    """Place an ``HNSWIndex`` on the mesh per ``hnsw_index_specs``.

    Vector rows pad with zeros (adjacency only references real nodes,
    so padded rows are unreachable); adjacency stays replicated.
    """
    s = mesh.shape[axis]
    specs = SH.hnsw_index_specs(SH.Axes(model=axis))
    return _hnsw.HNSWIndex(
        vectors=_put(mesh, _pad_dim0(index.vectors, s, 0.0),
                     specs.vectors),
        adj0=_put(mesh, index.adj0, specs.adj0),
        upper_adj=_put(mesh, index.upper_adj, specs.upper_adj),
        entry_point=_put(mesh, index.entry_point, specs.entry_point),
        node_level=_put(mesh, _pad_dim0(index.node_level, s, 0),
                        specs.node_level),
        # tombstones replicate (gathered per candidate id, like adjacency)
        deleted=(None if index.deleted is None
                 else _put(mesh, index.deleted, P(None))),
    )


def place_segmented(mesh: Mesh, seg, *, axis: str = "model"):
    """Replicate a ``core.segment.SegmentedIndex``'s mutable arrays
    (delta buffer + tombstone mask) over the mesh.

    The delta segment is scanned *exactly* on every device — it is tiny
    (``cap`` rows) and its scan must merge with the already-replicated
    base top-k, so replication is the right placement; sharding it would
    add a collective for O(cap) work.  The wrapped base index inside
    ``seg.base`` is placed separately by ``shard_backend`` before
    wrapping.
    """
    return seg._replace(
        delta_vecs=_put(mesh, seg.delta_vecs, P(None, None)),
        delta_ids=_put(mesh, seg.delta_ids, P(None)),
        tombstone=_put(mesh, seg.tombstone, P(None)))


# ---------------------------------------------------------------------------
# sharded scan callables (static-arg plugins for core.toploc / engines)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardedIVFScan:
    """Drop-in for ``ivf._scan_lists`` over partition-sharded lists.

    Each shard gathers the selected lists it owns (foreign probes are
    clipped to a valid local row and id-masked to -1, so their scores
    never merge), scans them with the exact single-device einsum shape,
    reduces to a local top-k, and the ordered k-wide merge produces the
    global top-k.  ``real`` work counters psum exactly (int32).

    ``fused`` (a ``toploc.FusedTurn``) routes the local gather + scan +
    top-k through the single-dispatch fused kernel; its flat scan
    positions use the same selection-relative numbering as the dense
    path, so the ordered merge — and with it f32 bit-identity to the
    single-device scan — is preserved.
    """
    mesh: Mesh
    axis: str = "model"
    fused: Optional[object] = None

    def __call__(self, index: _ivf.IVFIndex, queries: jax.Array,
                 sel: jax.Array, k: int
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        axis = self.axis
        fused = self.fused

        def local(lv, li, ls, q, s):
            p_local = lv.shape[0]
            lo = jax.lax.axis_index(axis) * p_local
            s_local = s - lo
            own = (s_local >= 0) & (s_local < p_local)       # (B, np)
            ss = jnp.clip(s_local, 0, p_local - 1)
            b = q.shape[0]
            if fused is not None:
                v, ids, pos = _kops.fused_scan(
                    q, lv, li, ss, k, own=own.astype(jnp.int32),
                    over=fused.over, precision=fused.precision,
                    mode=fused.mode)
            else:
                lvs = lv[ss]                                  # (B,np,L,d)
                lis = jnp.where(own[..., None], li[ss], -1)
                scores = jnp.einsum("bd,bnld->bnl", q, lvs,
                                    precision=_ivf.F32)
                flat_v = jnp.where(lis >= 0, scores,
                                   -jnp.inf).reshape(b, -1)
                flat_i = lis.reshape(b, -1)
                v, pos = jax.lax.top_k(flat_v, k)
                ids = jnp.take_along_axis(flat_i, pos, axis=-1)
            top_v, top_i = distributed_topk_ordered(v, pos, ids, k, axis)
            real = jax.lax.psum(
                jnp.sum(jnp.where(own, ls[ss], 0), axis=-1), axis)
            return top_v, top_i, real.astype(jnp.int32)

        fn = compat.shard_map(
            local, mesh=self.mesh,
            in_specs=(P(axis, None, None), P(axis, None), P(axis),
                      P(None, None), P(None, None)),
            out_specs=(P(None, None), P(None, None), P(None)),
            check_vma=False)
        return fn(index.list_vecs, index.list_ids, index.list_sizes,
                  queries, sel)


@dataclasses.dataclass(frozen=True)
class ShardedPQScan:
    """Drop-in for ``toploc._scan_lists_pq`` over a sharded PQ corpus.

    ADC lookup tables build replicated (tiny); each shard ADC-scans its
    owned code lists with the ``pq.adc_scores_masked`` formulation (bit-
    identical to the single-device reference scan), local top-R merges
    ordered into the global ADC candidate list, and the exact re-rank is
    owner-computes + psum over the doc-row-sharded float corpus.

    ``fused`` routes the local ADC scan + top-R through the fused kernel
    (``fuse_rerank=False`` — the exact re-rank must stay in the
    owner-computes psum, as candidate doc rows live on other shards);
    flat positions share the dense path's numbering so the ordered
    candidate merge is unchanged.
    """
    mesh: Mesh
    axis: str = "model"
    fused: Optional[object] = None

    def __call__(self, index: _pq.IVFPQIndex, queries: jax.Array,
                 sel: jax.Array, k: int, rerank: int
                 ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
        from repro.core import toploc as _toploc
        axis = self.axis
        fused = self.fused
        nprobe = sel.shape[1]
        r = max(k, min(rerank, nprobe * index.lmax))
        tables = _toploc._adc_tables(index, queries)          # replicated

        def local(lc, li, ls, dv, tab, q, s):
            p_local = lc.shape[0]
            shard = jax.lax.axis_index(axis)
            lo = shard * p_local
            s_local = s - lo
            own = (s_local >= 0) & (s_local < p_local)
            ss = jnp.clip(s_local, 0, p_local - 1)
            b = q.shape[0]
            if fused is not None:
                cv, cids, cpos = _kops.fused_scan_pq(
                    tab, q, lc, li, ss, dv, k, rerank=rerank,
                    own=own.astype(jnp.int32), precision=fused.precision,
                    fuse_rerank=False, mode=fused.mode)
            else:
                codes = lc[ss].astype(jnp.int32)              # (B,np,L,m)
                ids = jnp.where(own[..., None], li[ss], -1)
                flat_c = codes.reshape(b, -1, codes.shape[-1])
                flat_i = ids.reshape(b, -1)
                scores = _pq.adc_scores_masked(tab, flat_c, flat_i)
                cv, cpos = jax.lax.top_k(scores, r)
                cids = jnp.take_along_axis(flat_i, cpos, axis=-1)
            cand_v, cand_ids = distributed_topk_ordered(cv, cpos, cids,
                                                        r, axis)
            # exact re-rank: owner computes the single-device multiply-
            # reduce, foreign shards contribute exact zeros to the psum
            n_local = dv.shape[0]
            d_local = cand_ids - shard * n_local
            own_doc = (d_local >= 0) & (d_local < n_local) & (cand_ids >= 0)
            rows = dv[jnp.clip(d_local, 0, n_local - 1)]      # (B, r, d)
            ex = jnp.sum(rows * q[:, None, :], axis=-1)
            exact = jax.lax.psum(jnp.where(own_doc, ex, 0.0), axis)
            exact = jnp.where(cand_ids >= 0, exact, -jnp.inf)
            top_v, pos = jax.lax.top_k(exact, k)
            top_i = jnp.take_along_axis(cand_ids, pos, axis=-1)
            code_d = jax.lax.psum(
                jnp.sum(jnp.where(own, ls[ss], 0), axis=-1), axis)
            rerank_d = jnp.sum((cand_ids >= 0), axis=-1)
            return (top_v, top_i, code_d.astype(jnp.int32),
                    rerank_d.astype(jnp.int32))

        fn = compat.shard_map(
            local, mesh=self.mesh,
            in_specs=(P(axis, None, None), P(axis, None), P(axis),
                      P(axis, None), P(None, None, None), P(None, None),
                      P(None, None)),
            out_specs=(P(None, None), P(None, None), P(None), P(None)),
            check_vma=False)
        return fn(index.list_codes, index.list_ids, index.list_sizes,
                  index.doc_vecs, tables, queries, sel)


@dataclasses.dataclass(frozen=True)
class ShardedHNSWSearch:
    """Drop-in for ``hnsw.search`` over a doc-row-sharded vector corpus.

    The traversal (``hnsw._search_impl``) runs replicated inside
    ``shard_map`` — every shard walks the identical beam because every
    score it branches on is the exact psum-merged dot — while each
    candidate's vector row is read from exactly one shard.
    """
    mesh: Mesh
    axis: str = "model"

    def __call__(self, index: _hnsw.HNSWIndex, queries: jax.Array, *,
                 ef: int, k: int,
                 entry_override: Optional[jax.Array] = None,
                 use_entry_override: bool = False
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        axis = self.axis
        n_pad = index.vectors.shape[0]
        top_level = index.top_level
        if entry_override is None:
            entry_override = jnp.zeros((queries.shape[0],), jnp.int32)

        def local(vec_l, adj0, upper, entry_pt, q, override, *dead):
            n_local = vec_l.shape[0]
            lo = jax.lax.axis_index(axis) * n_local

            def factory(qrow):
                def dots_at(ids):
                    loc = ids - lo
                    own = (loc >= 0) & (loc < n_local)
                    rows = vec_l[jnp.clip(loc, 0, n_local - 1)]
                    s = jnp.where(own, _hnsw._dots(rows, qrow), 0.0)
                    return jax.lax.psum(s, axis)
                return dots_at

            return _hnsw._search_impl(
                factory, n_pad, top_level, adj0, upper, entry_pt, q,
                override, ef=ef, k=k,
                use_entry_override=use_entry_override,
                deleted=dead[0] if dead else None)

        # the tombstone mask rides along (replicated) only when present,
        # keeping the no-deletions program byte-identical to before
        in_specs = (P(axis, None), P(None, None), P(None, None, None),
                    P(), P(None, None), P(None))
        operands = [index.vectors, index.adj0, index.upper_adj,
                    index.entry_point, queries, entry_override]
        if index.deleted is not None:
            in_specs = in_specs + (P(None),)
            operands.append(index.deleted)
        fn = compat.shard_map(
            local, mesh=self.mesh,
            in_specs=in_specs,
            out_specs=(P(None, None), P(None, None), P(None)),
            check_vma=False)
        return fn(*operands)


# ---------------------------------------------------------------------------
# backend-registry wiring
#
# A data-driven table — not string branching — maps each registered
# ``core.backend`` name to (index placement fn, scan plugin class, the
# backend dataclass field the plugin plugs into).  ``shard_backend`` is
# the one call the serving engines make; a new backend becomes sharded
# by one ``register_sharding`` call, with zero engine edits.
# ---------------------------------------------------------------------------

_SHARDING_REGISTRY: dict = {}


def register_sharding(name: str, shard_index, plugin_cls,
                      field: str = "scan") -> None:
    """Teach ``shard_backend`` how to place backend ``name`` on a mesh."""
    _SHARDING_REGISTRY[name] = (shard_index, plugin_cls, field)


register_sharding("ivf", shard_ivf_index, ShardedIVFScan, "scan")
register_sharding("ivf_pq", shard_ivf_pq_index, ShardedPQScan, "scan")
register_sharding("hnsw", shard_hnsw_index, ShardedHNSWSearch, "search")


def shard_backend(mesh: Mesh, backend, index, *, axis: str = "model"):
    """Place ``index`` on ``mesh`` and plug the matching sharded scan
    into ``backend``.  Backends with no registered sharding (e.g. the
    stateless exact backend) pass through unchanged.

    Returns (backend', index').
    """
    entry = _SHARDING_REGISTRY.get(backend.name)
    if entry is None:
        return backend, index
    shard_index, plugin_cls, field = entry
    plugin = plugin_cls(mesh, axis)
    fused = getattr(backend, "fused", None)
    if fused is not None and any(f.name == "fused"
                                 for f in dataclasses.fields(plugin)):
        plugin = dataclasses.replace(plugin, fused=fused)
    return (dataclasses.replace(backend, **{field: plugin}),
            shard_index(mesh, index, axis=axis))


# ---------------------------------------------------------------------------
# diagnostics (benchmarks/fig4_sharded.py)
# ---------------------------------------------------------------------------

def per_shard_list_work(list_sizes: np.ndarray, sel: np.ndarray,
                        n_shards: int) -> np.ndarray:
    """Per-device posting-list scan work for a probe selection.

    ``list_sizes`` (p,) real list sizes; ``sel`` any shape of selected
    partition ids; shard s owns the contiguous partition block
    ``[s·⌈p/S⌉, (s+1)·⌈p/S⌉)`` — the same mapping the sharded scans use.
    Returns (S,) int64 — real float/code distances each device computes.
    """
    sizes = np.asarray(list_sizes)
    sel = np.asarray(sel).reshape(-1)
    p_local = -(-len(sizes) // n_shards)
    owner = sel // p_local
    work = np.zeros(n_shards, np.int64)
    for s in range(n_shards):
        work[s] = sizes[sel[owner == s]].sum()
    return work
