"""Shared benchmark fixtures: workloads, indexes, timing helpers.

Two synthetic conversation sets mirror the paper's datasets:
  * "cast19-like" — low drift, no topic shifts (the easy set where the
    paper sees no effectiveness loss);
  * "cast20-like" — higher drift + mid-conversation topic shifts (the
    hard set where the refresh mechanism of TopLoc_IVF+ matters).

Index builds are cached on disk (artifacts/bench_cache) — HNSW
construction is the slow part.  The cache directory is gitignored:
every fixture regenerates *deterministically* on first use (fixed-seed
workloads, k-means keys, PQ codebooks, HNSW insertion order), so a
fresh checkout rebuilds byte-equivalent fixtures instead of shipping
binary blobs in the repo.
"""
from __future__ import annotations

import os
import pickle
import time
from typing import Callable, Dict

import numpy as np
import jax
import jax.numpy as jnp

from repro import compile_cache
from repro.core import hnsw as HN
from repro.core import ivf as IV
from repro.core import pq as PQ
from repro.data import synthetic as SY

# every benchmark imports this module before it compiles anything
compile_cache.enable()

CACHE = os.environ.get("BENCH_CACHE", "artifacts/bench_cache")

# All knobs are env-overridable so the CI benchmark-smoke step (and any
# laptop run) can shrink the corpus without editing this file.
N_DOCS = int(os.environ.get("BENCH_DOCS", 20000))
DIM = 64
N_TOPICS = 64
# Paper regime: p is 5-40x above the sqrt(n) heuristic (2^15..2^18 for a
# 38.6M corpus) so the CENTROID SCAN dominates per-query cost — that is
# the term TopLoc eliminates. Scaled to 20k docs: p=2048 (~10 docs/list).
PARTITIONS = int(os.environ.get("BENCH_PARTITIONS", 2048))
CONVS = int(os.environ.get("BENCH_CONVS", 12))
TURNS = int(os.environ.get("BENCH_TURNS", 8))
PQ_M = int(os.environ.get("BENCH_PQ_M", 8))      # PQ subquantizers
HNSW_M = int(os.environ.get("BENCH_HNSW_M", 16))
HNSW_EFC = int(os.environ.get("BENCH_HNSW_EFC", 64))


def workload(kind: str) -> SY.Workload:
    # difficulty calibrated to the paper's sets: CAsT'19 — conversations
    # hold their topic (TopLoc loses ~nothing); CAsT'20 — moderate drift
    # + occasional topic shifts (static caches degrade, the |I0| refresh
    # recovers at a bounded refresh rate)
    if kind == "cast19":
        cfg = SY.WorkloadConfig(
            n_docs=N_DOCS, d=DIM, n_topics=N_TOPICS,
            n_conversations=CONVS, turns_per_conversation=TURNS,
            query_drift=0.10, walk_step=0.015, shift_prob=0.0, seed=19)
    elif kind == "cast20":
        cfg = SY.WorkloadConfig(
            n_docs=N_DOCS, d=DIM, n_topics=N_TOPICS,
            n_conversations=CONVS, turns_per_conversation=TURNS,
            query_drift=0.15, walk_step=0.05, shift_prob=0.10, seed=20)
    else:
        raise ValueError(kind)
    return _cached(f"workload_{kind}_{N_DOCS}_{CONVS}_{TURNS}",
                   lambda: SY.make_workload(cfg))


def _cached(name: str, build: Callable):
    os.makedirs(CACHE, exist_ok=True)
    path = os.path.join(CACHE, name + ".pkl")
    if os.path.exists(path):
        with open(path, "rb") as f:
            return pickle.load(f)
    obj = build()
    with open(path, "wb") as f:
        pickle.dump(jax.tree.map(np.asarray, obj), f)
    return obj


def ivf_index(kind: str) -> IV.IVFIndex:
    wl = workload(kind)
    raw = _cached(f"ivf_{kind}_{N_DOCS}_{PARTITIONS}",
                  lambda: IV.build(jnp.asarray(wl.doc_vecs), p=PARTITIONS,
                                   iters=8, key=jax.random.PRNGKey(0)))
    return IV.IVFIndex(*[jnp.asarray(x) for x in raw])


def ivf_pq_index(kind: str) -> PQ.IVFPQIndex:
    """IVF geometry of ``ivf_index`` + PQ-compressed posting lists."""
    idx = ivf_index(kind)
    wl = workload(kind)
    raw = _cached(
        f"ivfpq_{kind}_{N_DOCS}_{PARTITIONS}_{PQ_M}",
        lambda: PQ.build_ivf_pq(idx, jnp.asarray(wl.doc_vecs), m=PQ_M,
                                iters=8, key=jax.random.PRNGKey(0)))
    return PQ.IVFPQIndex(*[jnp.asarray(x) for x in raw])


def hnsw_index(kind: str) -> HN.HNSWIndex:
    wl = workload(kind)
    raw = _cached(f"hnsw_{kind}_{N_DOCS}_{HNSW_M}_{HNSW_EFC}",
                  lambda: HN.build(wl.doc_vecs, m=HNSW_M,
                                   ef_construction=HNSW_EFC, seed=0))
    # `deleted` is None on a pristine build — asarray would NaN it
    return HN.HNSWIndex(*[None if x is None else jnp.asarray(x)
                          for x in raw])


def time_fn(fn: Callable, *args, warmup: int = 1, repeat: int = 3
            ) -> float:
    """Median wall seconds of fn(*args) with block_until_ready."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def eval_conversations(run_ids: np.ndarray, wl: SY.Workload
                       ) -> Dict[str, float]:
    return SY.evaluate_run(run_ids, wl, k=10)
