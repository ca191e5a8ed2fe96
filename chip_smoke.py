#!/usr/bin/env python3
"""Bring-up smoke of the TopLoc serving path on a TPU at CAsT width.

    python chip_smoke.py              # one chip: build, serve, check
    python chip_smoke.py --chips 4    # four chips: replica router and
                                      # 4-way sharded corpus only

One process.  Everything is generated on the device from ``--seed``
following ``repro.data.synthetic``'s generative shapes: a topic-clustered
unit-norm corpus of 2^20 768-d documents over 4096 zipf-popular topics,
and 64 drifting conversations of 8 turns with topic shifts.  The index is
built on the chip (``ivf.build`` at p = 2^14, ``pq.build_ivf_pq`` at
m = 48) and every turn is served through the public engines.

One chip serves all 512 turns four ways — (a) IVF TopLoc+, (b) IVF
plain, (c) IVF-PQ TopLoc+ (Pallas ADC kernel), (d) (a) through the fused
f32 megakernel — and checks that (a) equals the sequential engine on
every turn of 8 conversations, that (d) equals (a) on every turn, and
that TopLoc+ recall@10 (against an exact top-10 computed at HIGHEST
precision) holds 0.9x plain, and IVF-PQ holds 0.9x IVF.

Four chips serve the same workload through ``ReplicatedSearchEngine``
with one chip per replica, and through a batched engine whose corpus is
sharded 4 ways; both must equal the sequential engine on every turn.

Any failed check exits non-zero.  So does a platform that is not a TPU:
there is no CPU fallback.  The seconds printed are this run's wall time
(compilation included where labelled), not benchmark numbers.  The last
line of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import resource
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))


@dataclasses.dataclass(frozen=True)
class Sizes:
    n_docs: int = 1 << 20
    d: int = 768                  # the paper's embedding width
    n_topics: int = 4096
    convs: int = 64
    turns: int = 8
    shift_prob: float = 0.1
    p: int = 1 << 14
    capacity_factor: float = 1.3
    # Lloyd iterations of the IVF build (FAISS's default niter).  At
    # p = 2^14, 10 iterations leave the centroids unconverged, and
    # TopLoc+, which caches the centroids nearest the first turn, loses
    # most from that: 0.895x plain at 10, 0.941x at 25 (v5e, this data)
    kmeans_iters: int = 25
    pq_m: int = 48
    pq_iters: int = 8
    nprobe: int = 64
    h: int = 1024
    alpha: float = 0.1
    # IVF-PQ exact re-rank depth.  48 one-byte 16-d codes keep ~0.5 bit
    # per dimension of this corpus, and at R = 64 the ADC top-R misses
    # too many of the float path's top-10 to hold the 0.9x floor (CPU
    # f32, 2^19 docs at these ratios: 0.834x); R = 128 holds it (0.940x)
    rerank: int = 128
    k: int = 10
    max_batch: int = 32
    seq_convs: int = 8            # conversations replayed sequentially
    exact_block: int = 1 << 16    # corpus rows per exact-search block


# synthetic.py's spreads are set at d = 64; noise norms grow with
# sqrt(d), so they are scaled by sqrt(64 / d) to keep its geometry
DOC_SPREAD, QUERY_DRIFT, WALK_STEP, ZIPF = 0.35, 0.15, 0.05, 0.7


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def _normalize(x):
    import jax.numpy as jnp
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True),
                           1e-9)


def make_data(seed: int, s: Sizes):
    """(docs (n, d), queries (convs, turns, d)), generated on device."""
    import jax
    import jax.numpy as jnp

    scale = math.sqrt(64.0 / s.d)

    @jax.jit
    def corpus(key):
        kc, kt, kn = jax.random.split(key, 3)
        centers = _normalize(jax.random.normal(kc, (s.n_topics, s.d)))
        logits = -ZIPF * jnp.log(jnp.arange(1, s.n_topics + 1,
                                            dtype=jnp.float32))
        topic = jax.random.categorical(kt, logits, shape=(s.n_docs,))
        noise = jax.random.normal(kn, (s.n_docs, s.d))
        return centers, _normalize(centers[topic]
                                   + DOC_SPREAD * scale * noise)

    @jax.jit
    def conversations(key, centers):
        k0, kt = jax.random.split(key)
        topic0 = jax.random.randint(k0, (s.convs,), 0, s.n_topics)

        def turn(carry, kk):
            topic, anchor, t = carry
            ks, kn, kw, kq = jax.random.split(kk, 4)
            shift = (t > 0) & (jax.random.uniform(ks, (s.convs,))
                               < s.shift_prob)
            topic = jnp.where(shift, jax.random.randint(
                kn, (s.convs,), 0, s.n_topics), topic)
            anchor = jnp.where(shift[:, None], centers[topic], anchor)
            anchor = _normalize(anchor + WALK_STEP * scale
                                * jax.random.normal(kw, anchor.shape))
            q = _normalize(anchor + QUERY_DRIFT * scale
                           * jax.random.normal(kq, anchor.shape))
            return (topic, anchor, t + 1), q

        _, qs = jax.lax.scan(turn, (topic0, centers[topic0], 0),
                             jax.random.split(kt, s.turns))
        return jnp.swapaxes(qs, 0, 1)

    kd, kq = jax.random.split(jax.random.PRNGKey(seed))
    centers, docs = corpus(kd)
    return docs, conversations(kq, centers)


def exact_topk(docs, queries, s: Sizes):
    """Exact top-k ids of every query, at HIGHEST precision, blocked
    over the corpus so only a (Q, block) score tile is live."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(docs, q):
        nb = docs.shape[0] // s.exact_block

        def body(carry, i):
            v, ids = carry
            blk = jax.lax.dynamic_slice_in_dim(docs, i * s.exact_block,
                                               s.exact_block)
            sc = jnp.einsum("qd,nd->qn", q, blk,
                            precision=jax.lax.Precision.HIGHEST)
            bv, bi = jax.lax.top_k(sc, s.k)
            cv = jnp.concatenate([v, bv], axis=1)
            ci = jnp.concatenate([ids, bi + i * s.exact_block], axis=1)
            tv, pos = jax.lax.top_k(cv, s.k)
            return (tv, jnp.take_along_axis(ci, pos, axis=1)), None

        init = (jnp.full((q.shape[0], s.k), -jnp.inf),
                jnp.full((q.shape[0], s.k), -1, jnp.int32))
        (_, ids), _ = jax.lax.scan(body, init, jnp.arange(nb))
        return ids

    assert docs.shape[0] % s.exact_block == 0
    return run(docs, queries.reshape(-1, s.d))


def recall(ids, exact) -> float:
    import numpy as np
    ids = np.asarray(ids).reshape(-1, ids.shape[-1])
    exact = np.asarray(exact).reshape(-1, exact.shape[-1])
    return float(np.mean([len(set(a.tolist()) & set(b.tolist())) / b.size
                          for a, b in zip(ids, exact)]))


def release() -> None:
    """Free a closed engine's device state now.  An engine and its
    batcher reference each other, so dropping the last name leaves the
    pair (and the index it placed) to the cycle collector."""
    gc.collect()


def memory(dev) -> str:
    st = dev.memory_stats() or {}
    return (f"bytes_in_use={st.get('bytes_in_use', 'n/a')} "
            f"peak_bytes_in_use={st.get('peak_bytes_in_use', 'n/a')}")


def serve_batched(eng, queries, s: Sizes, label: str):
    """Serve every turn, interleaved across conversations (turn-major
    submission), through a batched engine or the router.  Returns ids
    (convs, turns, k).  The first wave is timed alone: its wall time is
    mostly compilation."""
    import numpy as np
    order = [(c, t) for t in range(s.turns) for c in range(s.convs)]
    futs = {}
    t0 = time.perf_counter()
    for c, t in order[:s.max_batch]:
        futs[c, t] = eng.submit(f"c{c}", queries[c, t])
    eng.drain()
    t1 = time.perf_counter()
    for c, t in order[s.max_batch:]:
        futs[c, t] = eng.submit(f"c{c}", queries[c, t])
    eng.drain()
    t2 = time.perf_counter()
    log(f"{label}: first wave (compile incl.) {t1 - t0:.3f}s, remaining "
        f"{len(order) - s.max_batch} turns {t2 - t1:.3f}s (wall time)")
    ids = np.zeros((s.convs, s.turns, s.k), np.int64)
    for (c, t), f in futs.items():
        ids[c, t] = np.asarray(f.result()[1])
    return ids


def serve_sequential(eng, queries, convs: int, s: Sizes, label: str):
    import numpy as np
    ids = np.zeros((convs, s.turns, s.k), np.int64)
    t0 = time.perf_counter()
    for c in range(convs):
        for t in range(s.turns):
            ids[c, t] = np.asarray(eng.query(f"c{c}", queries[c, t])[1])
    log(f"{label}: {convs * s.turns} turns one at a time "
        f"{time.perf_counter() - t0:.3f}s (wall time, compile incl.)")
    return ids


def same_ids(name: str, got, want, failures: list) -> None:
    import numpy as np
    bad = np.argwhere(np.any(np.asarray(got) != np.asarray(want), axis=-1))
    if len(bad):
        failures.append(f"{name}: ids differ on {len(bad)} of "
                        f"{got.shape[0] * got.shape[1]} turns, first "
                        f"(conv, turn) {[tuple(b) for b in bad[:5]]}")
        log(f"FAIL {failures[-1]}")
    else:
        log(f"ok   {name}: ids identical on all "
            f"{got.shape[0] * got.shape[1]} turns")


def build_ivf(docs, s: Sizes, seed: int):
    import jax
    from repro.core import ivf
    t0 = time.perf_counter()
    index = ivf.build(docs, s.p, iters=s.kmeans_iters,
                      key=jax.random.PRNGKey(seed + 1),
                      capacity_factor=s.capacity_factor)
    jax.block_until_ready(index)
    log(f"ivf.build: {time.perf_counter() - t0:.3f}s (wall, compile "
        f"incl.) p={index.p} Lmax={index.lmax} docs={index.n_docs}")
    return index


def one_chip(dev, s: Sizes, seed: int, failures: list) -> None:
    import jax
    import numpy as np
    from repro.core import pq
    from repro.serving import (BatchedConversationalSearchEngine,
                               ConversationalSearchEngine, ServingConfig)

    t0 = time.perf_counter()
    docs, queries = make_data(seed, s)
    jax.block_until_ready((docs, queries))
    log(f"generate: {time.perf_counter() - t0:.3f}s (wall) "
        f"n_docs={docs.shape[0]} d={docs.shape[1]} "
        f"queries={queries.shape[0]}x{queries.shape[1]}")

    index = build_ivf(docs, s, seed)
    t0 = time.perf_counter()
    pq_index = pq.build_ivf_pq(index, docs, s.pq_m, iters=s.pq_iters,
                               key=jax.random.PRNGKey(seed + 2))
    jax.block_until_ready(pq_index)
    log(f"pq.build_ivf_pq: {time.perf_counter() - t0:.3f}s (wall, "
        f"compile incl.) m={pq_index.m}")
    log(f"after build: {memory(dev)}")

    t0 = time.perf_counter()
    exact = np.asarray(exact_topk(docs, queries, s)).reshape(
        s.convs, s.turns, s.k)
    log(f"exact top-{s.k} (HIGHEST): {time.perf_counter() - t0:.3f}s "
        f"(wall, compile incl.)")
    qh = np.asarray(queries)

    base = dict(backend="ivf", strategy="toploc+", k=s.k,
                nprobe=s.nprobe, h=s.h, alpha=s.alpha, rerank=s.rerank)
    eng_kw = dict(n_slots=s.convs, max_batch=s.max_batch)
    cfg = {
        "a ivf toploc+": ServingConfig(**base),
        "b ivf plain": ServingConfig(**{**base, "strategy": "plain"}),
        "c ivf_pq toploc+": ServingConfig(**{**base,
                                             "backend": "ivf_pq"}),
        "d ivf toploc+ fused f32": ServingConfig(**base, fused=True,
                                                 precision="f32"),
    }
    ids = {}
    for name, c in cfg.items():
        if c.fused:
            # the fused engine places a kernel-length copy of the lists
            # (6.4 GB): the PQ index and the corpus it re-ranks from
            # are done with, so they leave HBM first
            del pq_index, docs
        kw = ({"ivf_pq_index": pq_index} if c.backend == "ivf_pq"
              else {"ivf_index": index})
        log(f"{name}: before placing, {memory(dev)}")
        with BatchedConversationalSearchEngine(c, **kw, **eng_kw) as eng:
            ids[name] = serve_batched(eng, qh, s, name)
        del eng, kw
        release()
        log(f"{name}: recall@{s.k}={recall(ids[name], exact):.6f}")
    seq = serve_sequential(
        ConversationalSearchEngine(cfg["a ivf toploc+"], ivf_index=index),
        qh, s.seq_convs, s, "sequential a")
    log(f"after serving: {memory(dev)}")

    same_ids("batched (a) == sequential engine",
             ids["a ivf toploc+"][:s.seq_convs], seq, failures)
    same_ids("fused f32 (d) == XLA (a)", ids["d ivf toploc+ fused f32"],
             ids["a ivf toploc+"], failures)
    r = {n: recall(v, exact) for n, v in ids.items()}
    for name, num, den in [
            ("TopLoc+ recall >= 0.9 x plain", "a ivf toploc+",
             "b ivf plain"),
            ("IVF-PQ TopLoc+ recall >= 0.9 x IVF TopLoc+",
             "c ivf_pq toploc+", "a ivf toploc+")]:
        ok = r[num] >= 0.9 * r[den]
        msg = f"{name}: {r[num]:.6f} vs {r[den]:.6f}"
        if not ok:
            failures.append(msg)
        log(f"{'ok  ' if ok else 'FAIL'} {msg}")


def four_chips(s: Sizes, seed: int, failures: list) -> None:
    import jax
    import numpy as np
    from repro.serving import (BatchedConversationalSearchEngine,
                               ConversationalSearchEngine,
                               ReplicatedSearchEngine, ServingConfig)

    docs, queries = make_data(seed, s)
    index = build_ivf(docs, s, seed)
    del docs
    qh = np.asarray(queries)
    cfg = ServingConfig(backend="ivf", strategy="toploc+", k=s.k,
                        nprobe=s.nprobe, h=s.h, alpha=s.alpha)
    seq = serve_sequential(ConversationalSearchEngine(cfg, ivf_index=index),
                           qh, s.convs, s, "sequential (device 0)")

    with ReplicatedSearchEngine(cfg, replicas=4, ivf_index=index,
                                n_slots=s.convs,
                                max_batch=s.max_batch) as router:
        devs = []
        for r, e in enumerate(router.engines):
            d = {str(x) for x in e.index.list_vecs.devices()}
            slab = {str(x) for leaf in jax.tree.leaves(e.store._slab)
                    for x in leaf.devices()}
            log(f"replica {r}: index on {sorted(d)}, slab on "
                f"{sorted(slab)}")
            devs.append(tuple(sorted(d | slab)))
        if len(set(devs)) != 4 or any(len(x) != 1 for x in devs):
            failures.append(f"replica devices not distinct: {devs}")
            log(f"FAIL {failures[-1]}")
        got = serve_batched(router, qh, s, "router replicas=4")
    del router, e
    release()
    same_ids("router (4 replicas) == sequential engine", got, seq,
             failures)

    with BatchedConversationalSearchEngine(
            dataclasses.replace(cfg, shards=4), ivf_index=index,
            n_slots=s.convs, max_batch=s.max_batch) as eng:
        log(f"shards=4: lists on "
            f"{len(eng.index.list_vecs.devices())} devices")
        got = serve_batched(eng, qh, s, "batched shards=4")
    same_ids("batched shards=4 == sequential engine", got, seq, failures)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    try:
        from repro import compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({e})", file=sys.stderr)
        return 2
    compile_cache.enable()
    import jax

    devs = jax.devices()
    dev = devs[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)} jax={jax.__version__}")
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; this smoke has no CPU fallback",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devs)}", file=sys.stderr)
        return 1

    failures: list = []
    t0 = time.perf_counter()
    if args.chips == 1:
        one_chip(dev, Sizes(), args.seed, failures)
    else:
        four_chips(Sizes(), args.seed, failures)
    log(f"total: {time.perf_counter() - t0:.3f}s (wall); host peak RSS "
        f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss} KiB")
    if failures:
        for f in failures:
            print(f"chip_smoke: FAILED {f}", file=sys.stderr)
        return 1
    # the chips this run used: a host may expose more than it takes
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
