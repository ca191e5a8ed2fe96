"""Serving driver: the full paper pipeline on the synthetic workload.

Builds the topic corpus, the IVF and HNSW indexes, then serves every
conversation through the selected strategy, reporting the paper's
metrics (MRR@10 / NDCG@3 / NDCG@10), wall-clock, and the
hardware-independent work counters.

  PYTHONPATH=src python -m repro.launch.serve --backend ivf \
      --strategy toploc+ --n-docs 20000 --nprobe 16 --h 64
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro import compile_cache
from repro.core import hnsw as HN
from repro.core import ivf as IV
from repro.data import synthetic as SY
from repro.serving.engine import ConversationalSearchEngine, ServingConfig


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="ivf",
                    choices=["ivf", "hnsw", "exact"])
    ap.add_argument("--strategy", default="toploc+",
                    choices=["plain", "toploc", "toploc+"])
    ap.add_argument("--n-docs", type=int, default=20000)
    ap.add_argument("--d", type=int, default=64)
    ap.add_argument("--n-topics", type=int, default=64)
    ap.add_argument("--partitions", type=int, default=128)
    ap.add_argument("--nprobe", type=int, default=8)
    ap.add_argument("--h", type=int, default=32)
    ap.add_argument("--alpha", type=float, default=0.1)
    ap.add_argument("--ef", type=int, default=32)
    ap.add_argument("--up", type=int, default=2)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--conversations", type=int, default=10)
    ap.add_argument("--turns", type=int, default=8)
    ap.add_argument("--shift-prob", type=float, default=0.1)
    args = ap.parse_args()
    compile_cache.enable()

    print(f"[serve] building workload: {args.n_docs} docs, "
          f"{args.conversations}x{args.turns} turns")
    wl = SY.make_workload(SY.WorkloadConfig(
        n_docs=args.n_docs, d=args.d, n_topics=args.n_topics,
        n_conversations=args.conversations,
        turns_per_conversation=args.turns, shift_prob=args.shift_prob))

    kw = {}
    if args.backend == "ivf":
        t0 = time.time()
        kw["ivf_index"] = IV.build(jnp.asarray(wl.doc_vecs),
                                   p=args.partitions, iters=8,
                                   key=jax.random.PRNGKey(0))
        print(f"[serve] IVF built in {time.time()-t0:.1f}s "
              f"(p={args.partitions}, Lmax={kw['ivf_index'].lmax})")
    elif args.backend == "hnsw":
        t0 = time.time()
        kw["hnsw_index"] = HN.build(wl.doc_vecs, m=16, ef_construction=64)
        print(f"[serve] HNSW built in {time.time()-t0:.1f}s")
    else:
        kw["doc_vecs"] = jnp.asarray(wl.doc_vecs)

    eng = ConversationalSearchEngine(ServingConfig(
        backend=args.backend, strategy=args.strategy, k=args.k,
        nprobe=args.nprobe, h=args.h, alpha=args.alpha,
        ef_search=args.ef, up=args.up), **kw)

    run = np.zeros((args.conversations, args.turns, args.k), np.int64)
    t0 = time.time()
    for c in range(args.conversations):
        for t in range(args.turns):
            _, ids = eng.query(f"conv{c}",
                               jnp.asarray(wl.conversations[c, t]))
            run[c, t] = ids
        eng.end_conversation(f"conv{c}")
    wall = time.time() - t0

    metrics = SY.evaluate_run(run, wl, k=args.k)
    s = eng.summary()
    print(f"[serve] {args.backend}/{args.strategy}: "
          f"MRR@10={metrics['mrr@10']:.3f} NDCG@3={metrics['ndcg@3']:.3f} "
          f"NDCG@10={metrics['ndcg@10']:.3f}")
    print(f"[serve] wall {wall:.2f}s "
          f"({1e3*wall/(args.conversations*args.turns):.2f} ms/turn); "
          f"work: centroid={s['mean_centroid_dists']:.0f} "
          f"list={s['mean_list_dists']:.0f} graph={s['mean_graph_dists']:.0f} "
          f"refresh_rate={s['refresh_rate']:.2f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
