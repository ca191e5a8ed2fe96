#!/usr/bin/env python3
"""Find a cell's knee: the highest offered rate the engine keeps up with.

    python3 chipbench/sweep.py --workload <cell> --seed 5 \\
        --rates 600 900 1200 1500 --seconds 8

One process: the cell's index and engine are built once, then each rate
runs its own open-loop window (a fresh schedule of the cell's traffic
mix at that rate, its live conversations replayed first).  A rate is
kept up with when the turns answered inside the window reach 98% of
those offered and the latency of the window's second half is within
1.5x of its first half's (the backlog does not grow).  One JSON line
per rate; the knee is the highest rate kept up with below the first
that is not.  A traffic file takes its rate from this once, by hand.
"""
from __future__ import annotations

import time

PROC_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)

    import numpy as np
    from chipbench import gen, harness, registry, window
    from repro import compile_cache
    compile_cache.enable()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    bench = registry.benchmark()
    cell = registry.cell(bench, args.workload)
    cfg = registry.config(cell["config"])
    traffic = registry.traffic(cell["traffic"])

    centers, docs = gen.corpus(cfg, args.seed)
    index, kw = harness.build_index(cfg, docs, args.seed)
    del docs
    router = harness.make_engine(cfg, **{kw: index})
    del index
    # the pump serves set-up's closed batches too (the batcher's drain
    # lock serialises it with set-up's own drains)
    router.start()
    for step, rate in enumerate(args.rates):
        t = dict(traffic, rate_turns_per_s=rate)
        sched = gen.schedule(t, args.seed + step, args.seconds,
                             cfg["n_slots"], prefix=f"s{step}")
        rows = sched.n_convs + cfg["n_slots"] + 8
        q = np.asarray(jax.device_get(gen.conversations(
            cfg, centers, args.seed + step, rows, sched.turns,
            t["shift_prob"])))
        harness.warm_up(router, sched, q, cfg["max_batch"])
        res = harness.drive(router, sched, q, args.seconds)
        harness.wait_all(res["futures"], res["t0"] + args.seconds + 30)
        due, done = res["due"], res["done"]
        half = due < args.seconds / 2
        e2e = window.summary(due, done, args.seconds)
        lat = window.latencies(due, done)
        p95 = [window.percentile(lat[m], 95) * 1e3 for m in (half, ~half)]
        offered = len(due) / args.seconds
        kept = (e2e["turns_per_s"] >= 0.98 * offered
                and p95[1] <= 1.5 * p95[0])
        print(json.dumps({"workload": args.workload, "rate": rate,
                          "offered": offered, **e2e,
                          "p95_first_half_ms": p95[0],
                          "p95_second_half_ms": p95[1],
                          "kept_up": kept}), flush=True)
    router.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
