"""Benchmark entry point: kernel micro-benchmarks plus one harness per
paper table/figure, discovered automatically.

Every ``benchmarks/fig*.py`` and ``benchmarks/table*.py`` module is
picked up by glob — adding a new figure file makes it runnable here
with no registration step.  Each module owns a ``main()`` and honours
the uniform ``--smoke`` contract: shrink the workload, assert the
figure's headline claim, print a ``SMOKE OK`` line and exit zero.

  PYTHONPATH=src python -m benchmarks.run              # everything
  PYTHONPATH=src python -m benchmarks.run --only table1
  PYTHONPATH=src python -m benchmarks.run --only fig8
  PYTHONPATH=src python -m benchmarks.run --smoke      # all smokes

Modules run as subprocesses: several check ``--smoke`` at import time
to shrink env-derived constants, so in-process imports cannot apply
the contract uniformly.  The kernel micro-benchmarks (``kernels.py``)
run as a child too: this parent never starts a JAX backend, because on
a TPU host the first process to do so holds the chip and its children
could not open it.
"""
from __future__ import annotations

import argparse
import glob
import os
import subprocess
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(BENCH_DIR)


def discover() -> list:
    """All fig*/table* benchmark modules, sorted by name."""
    paths = (glob.glob(os.path.join(BENCH_DIR, "fig*.py"))
             + glob.glob(os.path.join(BENCH_DIR, "table*.py")))
    return sorted(os.path.splitext(os.path.basename(p))[0] for p in paths)


def _matches(stem: str, only: str) -> bool:
    """--only accepts a full stem (fig1_ivf_sweep) or its short prefix
    (fig1, table1)."""
    return stem == only or stem.split("_")[0] == only


def run_module(stem: str, smoke: bool) -> int:
    """Run one benchmark module as a subprocess; returns its exit code."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(REPO_ROOT, "src"), REPO_ROOT,
                    env.get("PYTHONPATH")) if p)
    cmd = [sys.executable, os.path.join(BENCH_DIR, stem + ".py")]
    if smoke:
        cmd.append("--smoke")
    print(f"### {stem}{' --smoke' if smoke else ''}", flush=True)
    return subprocess.call(cmd, env=env, cwd=REPO_ROOT)


def main() -> None:
    modules = discover()
    shorts = sorted({m.split("_")[0] for m in modules})
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="all",
                    help="all, kernels, or a module name/prefix: "
                         + ", ".join(shorts))
    ap.add_argument("--smoke", action="store_true",
                    help="run each selected module with --smoke and "
                         "fail if any smoke gate fails")
    ap.add_argument("--list", action="store_true",
                    help="list discovered benchmark modules and exit")
    args, _ = ap.parse_known_args()

    if args.list:
        for m in modules:
            print(m)
        return

    selected = (modules if args.only in ("all", "kernels")
                else [m for m in modules if _matches(m, args.only)])
    if args.only not in ("all", "kernels") and not selected:
        ap.error(f"--only {args.only!r} matched no module "
                 f"(discovered: {', '.join(modules)})")

    t0 = time.time()
    failed = []
    if args.only in ("all", "kernels") and run_module("kernels", False):
        failed.append("kernels")
    if args.only == "kernels":
        sys.exit(1 if failed else 0)

    for stem in selected:
        if run_module(stem, args.smoke) != 0:
            failed.append(stem)
            print(f"### {stem} FAILED", file=sys.stderr, flush=True)

    status = "ok" if not failed else f"FAILED: {', '.join(failed)}"
    print(f"# {len(selected)} benchmark modules in {time.time()-t0:.1f}s "
          f"({status})", file=sys.stderr)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
