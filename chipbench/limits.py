#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 chipbench/limits.py --workload <cell> --mode <mode> \\
        --seeds 11 12 13 [--seconds 5]

``--mode control`` puts the reference in the program's place, computed
in three bfloat16 passes, and reads the compared numbers for the same
sampled turns a run at that seed compares.  ``--mode state`` and
``--mode answer`` run the cell with a fault planted in the program: the
session slab never written (a step that returns its state unchanged),
or each served id moved to the next document (an answer altered where
it is produced).  ``--mode order`` reads sound float32 scores summed in
other orders than the chip's matrix unit sums them, the room a sound
change of the program's scan has under the limit.  One JSON line per
seed; the benchmark's own runs never run these.
"""
from __future__ import annotations

import time

PROC_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def control_readings(cfg, traffic, seed: int, seconds: float):
    """The compared numbers of the control at ``seed``."""
    from chipbench import gen, harness, reference
    sched, docs, queries = harness.inputs(cfg, traffic, seed, seconds)
    q = harness.sampled_queries(sched, queries,
                                gen.sample(sched, seed, cfg["check_turns"]))
    v, i = reference.control_answers(docs, q, cfg["k"])
    return reference.compare(docs, q, v, i, cfg["k"])


def order_readings(cfg, traffic, seed: int, seconds: float):
    """The compared numbers of sound float32 scores summed in other
    orders: the exact top-k ids of the sampled turns rescored by an
    elementwise product that XLA reduces (``sum``), and by one float32
    accumulator taking the d products in turn (``sequential``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench import gen, harness, reference
    sched, docs, queries = harness.inputs(cfg, traffic, seed, seconds)
    q = harness.sampled_queries(sched, queries,
                                gen.sample(sched, seed, cfg["check_turns"]))
    ids = reference.exact_topk(docs, q, cfg["k"])
    rows = jnp.take(docs, jnp.asarray(ids), axis=0)        # (Q, k, d)
    qj = jnp.asarray(q)

    def sequential(r, qv):
        rt, qt = jnp.moveaxis(r, -1, 0), qv.T              # d first
        return jax.lax.fori_loop(
            0, rt.shape[0], lambda i, acc: acc + rt[i] * qt[i][:, None],
            jnp.zeros(r.shape[:2], jnp.float32))

    paths = {"sum": lambda r, qv: jnp.sum(r * qv[:, None, :], axis=-1),
             "sequential": sequential}
    out = {}
    for name, f in paths.items():
        scores = np.asarray(jax.jit(f)(rows, qj))
        got = reference.compare(docs, q, scores, ids, cfg["k"])
        out.update({f"{name}_{k}": v for k, v in got.items()})
    return out


@contextlib.contextmanager
def planted(fault: str, n_docs: int):
    """The program with one fault planted, for the enclosed block."""
    import jax.numpy as jnp
    from repro.core import toploc
    from repro.serving import sessions
    if fault == "state":
        target, name = sessions.SessionStore, "scatter"
        broken = lambda self, slots, sess: None   # noqa: E731
    elif fault == "answer":
        target, name = toploc, "step_batch"
        real = toploc.step_batch

        def broken(*a, **kw):
            v, i, sess, stats = real(*a, **kw)
            return v, jnp.where(i >= 0, (i + 1) % n_docs, i), sess, stats
    else:
        raise ValueError(f"unknown fault {fault!r}")
    saved = getattr(target, name)
    setattr(target, name, broken)
    try:
        yield
    finally:
        setattr(target, name, saved)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--mode", required=True,
                    choices=("control", "order", "state", "answer"))
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)

    from chipbench import harness, registry
    from repro import compile_cache
    compile_cache.enable()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    bench = registry.benchmark()
    cell = registry.cell(bench, args.workload)
    cfg = registry.config(cell["config"])
    traffic = registry.traffic(cell["traffic"])
    for seed in args.seeds:
        if args.mode == "control":
            got = control_readings(cfg, traffic, seed, args.seconds)
        elif args.mode == "order":
            got = order_readings(cfg, traffic, seed, args.seconds)
        else:
            with planted(args.mode, cfg["n_docs"]):
                out = harness.run_cell(
                    cell, cfg, traffic, seed=seed, seconds=args.seconds,
                    trace=False, end_to_end=[], per_layer=[],
                    out_dir=os.path.join(HERE, "out", "limits"),
                    proc_start=time.perf_counter())
            got = {k: v["value"] for k, v in out["compared"].items()}
            got["correct"] = out["correct"]
        print(json.dumps({"workload": args.workload, "mode": args.mode,
                          "seed": seed, **got}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
