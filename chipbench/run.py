#!/usr/bin/env python3
"""Run one cell of the chip benchmark.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

from the root of a checkout, on a machine whose JAX sees a TPU.  The
cell, its configuration and its traffic mix are found by name
(``BENCHMARK.json``, ``chipbench/configs/``, ``chipbench/traffic/``).
The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device``, and last the
numbers compared with their limits (``compared``), which also close
standard error.  On any platform but a TPU it exits 1 and prints no
result: there is no CPU fallback.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the TPU runtime's logs stay inside the checkout, not at /tmp
    if "TPU_LOG_DIR" not in os.environ:
        os.environ["TPU_LOG_DIR"] = os.path.join(HERE, "out", "tpu_logs")
        os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)

    from chipbench import registry
    try:
        bench = registry.benchmark()
        cell = registry.cell(bench, args.workload)
        cfg = registry.config(cell["config"])
        traffic = registry.traffic(cell["traffic"])
        from repro import compile_cache
    except (OSError, KeyError, ImportError) as e:
        print(f"chipbench: cannot set up {args.workload}: {e}",
              file=sys.stderr)
        return 2

    compile_cache.enable()
    import jax
    # cache every program, however quickly it compiled, so that only
    # a cell's first run in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = jax.devices()
    dev = devs[0]
    print(f"[chipbench] device: platform={dev.platform} "
          f"kind={dev.device_kind} count={len(devs)} jax={jax.__version__}",
          file=sys.stderr, flush=True)
    if dev.platform != "tpu":
        print("chipbench: no TPU found; the benchmark has no CPU fallback",
              file=sys.stderr)
        return 1
    if len(devs) < cell["chips"]:
        print(f"chipbench: {args.workload} needs {cell['chips']} chips, "
              f"found {len(devs)}", file=sys.stderr)
        return 1

    from chipbench import harness
    out = harness.run_cell(
        cell, cfg, traffic, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace),
        end_to_end=registry.metrics_of(bench, cell["name"], "end_to_end"),
        per_layer=registry.metrics_of(bench, cell["name"], "per_layer"),
        out_dir=os.path.join(HERE, "out", args.workload),
        proc_start=PROC_START)
    for name, r in out["compared"].items():
        print(f"[chipbench] compared {name}: {r['value']!r} "
              f"(limit {r['limit']!r})", file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
