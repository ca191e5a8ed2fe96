import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any jax import (jax locks the device
count at first init); 512 host devices back both production meshes:
16x16 (single pod) and 2x16x16 (two pods).

Per cell this driver:
  1. builds the StepBundle (ShapeDtypeStruct args — zero allocation),
  2. ``jax.jit(step, in_shardings=…, out_shardings=…).lower().compile()``,
  3. records ``memory_analysis()`` (fits-per-device proof) and
     ``cost_analysis()`` + parsed collective bytes (§Roofline inputs),
  4. for layer-scanned LMs, runs the 1/2-layer unrolled *calibration*
     lowers so scan-body costs are counted exactly
     (launch/analysis.py docstring),
  5. appends a JSON record to the output log.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --mesh both \
      --out artifacts/dryrun.jsonl
  ... --arch grok-1-314b --shape train_4k --mesh single   (one cell)
  ... --include-skips    (also lower rule-skipped cells, e.g. long_500k)
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import common as cc

from repro.configs import get as get_arch, list_archs
from repro.distributed import sharding as SH
from repro.launch import analysis
from repro.launch import mesh as mesh_lib


def to_shardings(mesh, spec_tree):
    """PartitionSpec pytree (possibly a prefix tree) → NamedSharding tree."""
    if spec_tree is None:
        return None
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s) if isinstance(s, P) else s,
        spec_tree, is_leaf=lambda s: s is None or isinstance(s, P))


def lower_and_compile(bundle: cc.StepBundle, mesh):
    in_sh = tuple(to_shardings(mesh, s) for s in bundle.in_specs)
    out_sh = to_shardings(mesh, bundle.out_specs)
    kwargs: Dict[str, Any] = {"in_shardings": in_sh}
    if out_sh is not None:
        kwargs["out_shardings"] = out_sh
    if bundle.donate_argnums:
        kwargs["donate_argnums"] = bundle.donate_argnums
    jitted = jax.jit(bundle.step_fn, **kwargs)
    # `with mesh:` backs PartitionSpec-based sharding constraints;
    # jax.set_mesh additionally backs shard_map with mesh=None (the
    # distributed top-k serving paths)
    with jax.set_mesh(mesh):
        with mesh:
            lowered = jitted.lower(*bundle.arg_structs)
            compiled = lowered.compile()
    return lowered, compiled


def _lm_calibration(arch, shape, axes, mesh, n_dp: int):
    """Per-layer cost coefficients from unrolled 1- and 2-layer lowers."""
    import dataclasses as dc
    base = arch.make_config()
    vals = {}
    for L in (1, 2):
        cfg = dc.replace(base, n_layers=L, unroll=True, remat=False,
                         loss_chunk=cc.LM_SHAPE_PARAMS[shape]["seq_len"])
        # microbatches=1: the mb scan is another once-counted while body;
        # total math is identical, so the linear model stays exact
        bundle = arch.build_bundle(cfg, shape, axes, n_dp=n_dp,
                                   shape_overrides={"microbatches": 1})
        _, compiled = lower_and_compile(bundle, mesh)
        cost = analysis.analyze_compiled(compiled, trip_count=1)
        vals[L] = cost
    per_flops = vals[2].flops - vals[1].flops
    per_bytes = vals[2].hbm_bytes - vals[1].hbm_bytes
    per_coll = vals[2].coll_bytes - vals[1].coll_bytes
    return (per_flops, per_bytes, per_coll)


def run_cell(arch_id: str, shape: str, mesh_name: str, *,
             smoke: bool = False, calibrate: bool = True
             ) -> Dict[str, Any]:
    arch = get_arch(arch_id)
    multi = mesh_name == "multi"
    mesh = mesh_lib.make_production_mesh(multi_pod=multi)
    axes = SH.from_mesh(mesh)
    n_dp = 1
    for a in axes.data:
        n_dp *= mesh.shape[a]
    chips = mesh.size

    cfg = arch.make_smoke_config() if smoke else arch.make_config()
    rec: Dict[str, Any] = {
        "arch": arch_id, "shape": shape, "mesh": mesh_name,
        "chips": chips, "ts": time.time(),
    }
    t0 = time.time()
    try:
        bundle = arch.build_bundle(cfg, shape, axes, n_dp=n_dp,
                                   smoke=smoke)
        lowered, compiled = lower_and_compile(bundle, mesh)
        trip = int(bundle.meta.get("scan_trip_count", 1))
        calib = None
        if calibrate and arch.family == "lm" and trip > 1 and not smoke:
            calib = _lm_calibration(arch, shape, axes, mesh, n_dp)
        cost = analysis.analyze_compiled(
            compiled, trip_count=trip,
            default_group=mesh.shape[axes.model], calibration=calib)
        roof = analysis.roofline_terms(
            cost, chips=chips,
            model_flops=float(bundle.meta.get("model_flops", 0.0)))
        rec.update(
            status="ok",
            compile_s=round(time.time() - t0, 1),
            kind=bundle.kind,
            meta={k: v for k, v in bundle.meta.items()
                  if isinstance(v, (int, float, str))},
            cost=dataclasses.asdict(cost),
            roofline=roof.as_dict(),
            calibrated=calib is not None,
        )
    except Exception as e:  # record failures — they are bugs to fix
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:],
                   compile_s=round(time.time() - t0, 1))
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi",
                                                       "both"])
    ap.add_argument("--out", default="artifacts/dryrun.jsonl")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--include-skips", action="store_true")
    ap.add_argument("--no-calibration", action="store_true")
    args = ap.parse_args()

    archs = list_archs() if args.arch == "all" else [args.arch]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)

    failures = 0
    with open(args.out, "a") as f:
        for arch_id in archs:
            arch = get_arch(arch_id)
            shapes = (arch.shapes if args.shape == "all"
                      else [args.shape])
            for shape in shapes:
                if shape in arch.skip_shapes and not args.include_skips:
                    rec = {"arch": arch_id, "shape": shape,
                           "mesh": "-", "status": "skipped",
                           "reason": arch.skip_shapes[shape]}
                    print(f"[skip] {arch_id:24s} {shape:16s} "
                          f"{arch.skip_shapes[shape][:60]}")
                    f.write(json.dumps(rec) + "\n")
                    f.flush()
                    continue
                for mesh_name in meshes:
                    rec = run_cell(arch_id, shape, mesh_name,
                                   smoke=args.smoke,
                                   calibrate=not args.no_calibration)
                    ok = rec["status"] == "ok"
                    failures += 0 if ok else 1
                    if ok:
                        r = rec["roofline"]
                        print(f"[ ok ] {arch_id:24s} {shape:16s} "
                              f"{mesh_name:6s} compile={rec['compile_s']:6.1f}s "
                              f"dom={r['dominant']:10s} "
                              f"c={r['compute_s']:.3e} m={r['memory_s']:.3e} "
                              f"x={r['collective_s']:.3e}")
                    else:
                        print(f"[FAIL] {arch_id:24s} {shape:16s} "
                              f"{mesh_name:6s} {rec['error'][:100]}")
                    f.write(json.dumps(rec) + "\n")
                    f.flush()
    print(f"dry-run complete; {failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
