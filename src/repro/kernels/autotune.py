"""Tile autotuner for the fused retrieval megakernel.

The fused turn (``kernels.fused_turn``) exposes three tiling knobs —
``blk_p`` (centroid tile rows), ``max_tile`` (posting-list tile cap fed
to ``tiling.list_tile``) and ``over`` (quantised candidate depth
``r = k·over``).  The right setting depends on the turn *shape*
(batch, p, Lmax, d, nprobe, k, precision, family): small batches want
wide list tiles to amortise per-step overhead, large d hits the VMEM
byte cap first, quantised paths trade re-rank rows against recall.

This module sweeps the knob grid for a shape, scores every candidate
with a roofline model (compute vs HBM vs per-step overhead, mirroring
the dry-run's cost accounting), optionally validates the top candidates
empirically against the live op, and caches the winner as JSON under
``artifacts/autotune/`` keyed by shape + device kind.  The cache is an
artifact, not source — it is gitignored and regenerates
deterministically (the model is pure arithmetic; validation re-times).

``benchmarks/roofline_report.py --autotune`` renders the cached entries
and judges autotuned vs static-default predicted times.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Dict, List, Optional, Tuple

import jax

from repro.kernels import tiling

CACHE_DIR = os.environ.get("AUTOTUNE_CACHE", "artifacts/autotune")

_ITEM = {"f32": 4, "bf16": 2, "int8": 1}


@dataclasses.dataclass(frozen=True)
class TileConfig:
    """One point of the fused-kernel tuning grid (hashable, jit-static).

    ``blk_p``/``max_tile`` are *requests* — the binding tile split is
    whatever ``tiling.centroid_tile``/``list_tile`` resolve them to, so
    two configs that clamp to the same tiles are the same program.
    """
    blk_p: int = 512
    max_tile: int = 2048
    over: int = 2


DEFAULT = TileConfig()


@dataclasses.dataclass(frozen=True)
class TurnShape:
    """Static shape of one fused turn — the autotune cache key."""
    b: int
    p: int
    lmax: int
    d: int
    nprobe: int
    k: int
    precision: str = "f32"
    family: str = "ivf"          # "ivf" | "pq"
    m: int = 0                   # PQ subquantizers (family == "pq")
    rerank: int = 0              # PQ exact re-rank depth (backend knob)

    def key(self) -> str:
        return (f"{self.family}_b{self.b}_p{self.p}_L{self.lmax}"
                f"_d{self.d}_np{self.nprobe}_k{self.k}_m{self.m}"
                f"_r{self.rerank}_{self.precision}")


@dataclasses.dataclass(frozen=True)
class DeviceModel:
    """Coarse roofline terms for the target device.

    Absolute numbers only need to be the right order of magnitude — the
    autotuner ranks *relative* candidate times on one device, and the
    fig8 judge compares predictions made with the same model.
    """
    name: str
    flops: float                 # peak MXU FLOP/s
    hbm_bw: float                # HBM bytes/s
    dispatch_s: float            # per-kernel-launch host overhead
    step_s: float                # per-grid-step sequencing overhead
    sort_flop: float = 4.0       # compare-exchange cost multiplier


#: Published per-chip peaks, keyed by ``jax.Device.device_kind``.
#: TPU v5e: 197 TFLOP/s bf16 and 819 GB/s HBM (Google Cloud
#: documentation, "TPU v5e").  The per-dispatch and per-grid-step
#: overheads are model constants, not published figures.
TPU_MODELS = {
    "TPU v5 lite": DeviceModel(name="TPU v5 lite", flops=1.97e14,
                               hbm_bw=8.19e11, dispatch_s=5e-6,
                               step_s=1.5e-7),
}
CPU_MODEL = DeviceModel(name="cpu", flops=5e10, hbm_bw=2e10,
                        dispatch_s=3e-5, step_s=1e-6)


def device_model() -> DeviceModel:
    """Roofline terms of ``jax.devices()[0]``.  A TPU whose kind has no
    entry in ``TPU_MODELS`` raises: a guessed peak would make every
    prediction for it wrong without saying so."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return CPU_MODEL
    try:
        return TPU_MODELS[dev.device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks recorded for TPU kind "
            f"{dev.device_kind!r}; add them to autotune.TPU_MODELS"
        ) from None


# ---------------------------------------------------------------------------
# roofline model
# ---------------------------------------------------------------------------


def _log2(n: int) -> int:
    return max(int(n).bit_length() - 1, 0)


def resolve(shape: TurnShape, cfg: TileConfig
            ) -> Dict[str, int]:
    """The binding tile split for (shape, cfg) — exactly the numbers
    ``ops.fused_turn``/``fused_turn_pq`` derive before calling the
    kernel, so predictions and the live program can never disagree."""
    s, c = shape, cfg
    np_pad = tiling.next_pow2(s.nprobe)
    if s.family == "pq":
        want = s.rerank or s.k
    elif s.precision == "f32":
        want = s.k
    else:
        want = s.k * c.over
    r = max(s.k, min(want, s.nprobe * s.lmax))
    r_pad = tiling.next_pow2(r)
    blk, p_pad = tiling.centroid_tile(s.p, np_pad, blk_p=c.blk_p)
    row_bytes = s.m if s.family == "pq" else s.d * _ITEM["f32"]
    blk_l, lpad = tiling.list_tile(s.lmax, row_bytes, kp=r_pad,
                                   max_tile=c.max_tile)
    return dict(np_pad=np_pad, r=r, r_pad=r_pad, blk=blk, p_pad=p_pad,
                blk_l=blk_l, lpad=lpad, row_bytes=row_bytes,
                kp=tiling.next_pow2(s.k))


def vmem_bytes(shape: TurnShape, cfg: TileConfig) -> int:
    """Fused-kernel VMEM residency under (shape, cfg): blocked operands
    + scratch, with the streamed list tile double-buffered.  Mirrors the
    scratch list of ``fused_turn._turn_call`` (the ``kernel_budget``
    pass audits the same accounting against the traced kernel)."""
    s = shape
    t = resolve(shape, cfg)
    by = 0
    by += 2 * s.b * s.d * 4                   # q blocks (B, d), (B, 1, d)
    by += t["blk"] * s.d * 4                  # centroid tile
    if s.family == "pq":
        by += s.b * s.m * 256 * 4             # ADC tables (≤256 codes)
    by += 2 * s.b * t["np_pad"] * 4           # run_pv/run_pi
    by += 3 * s.b * t["r_pad"] * 4            # run_cv/ci/cp
    by += 2 * t["blk_l"] * t["row_bytes"]     # lbuf, double-buffered
    by += 2 * t["blk_l"] * 4                  # ibuf
    if s.precision != "f32" or s.family == "pq":
        by += t["r_pad"] * 8 * s.d * 4        # re-rank 8-row tile gather
    by += 2 * s.b * t["kp"] * 4               # out_v/out_i blocks
    by += s.b * t["np_pad"] * 4               # out_sel block
    return by


def feasible(shape: TurnShape, cfg: TileConfig) -> bool:
    t = resolve(shape, cfg)
    if t["np_pad"] > t["blk"] or t["r_pad"] > t["blk_l"]:
        return False
    return vmem_bytes(shape, cfg) <= tiling.VMEM_BUDGET_BYTES


def predict_fused_s(shape: TurnShape, cfg: TileConfig,
                    hw: Optional[DeviceModel] = None) -> float:
    """Modeled wall time of ONE fused dispatch for (shape, cfg)."""
    hw = hw or device_model()
    s = shape
    t = resolve(shape, cfg)
    rerank = s.precision != "f32" or s.family == "pq"

    # stage 1 — centroid tiles: MXU dots + one (blk → np_pad) tie-merge
    steps1 = t["p_pad"] // t["blk"]
    fl1 = 2.0 * s.b * t["p_pad"] * s.d
    fl1 += hw.sort_flop * s.b * t["p_pad"] * _log2(t["blk"]) ** 2
    mem1 = t["p_pad"] * s.d * 4.0             # centroid stream

    # stage 2 — probed list tiles: DMA'd (double-buffered), scored,
    # tie-merged into the running (r_pad) candidate set
    steps2 = s.b * s.nprobe * (t["lpad"] // t["blk_l"])
    rows2 = float(s.b * s.nprobe * t["lpad"])
    if s.family == "pq":
        fl2 = rows2 * s.m                     # ADC table-sums
    else:
        fl2 = 2.0 * rows2 * s.d
    fl2 += hw.sort_flop * rows2 * _log2(t["blk_l"]) ** 2
    mem2 = rows2 * (t["row_bytes"] + 4.0)     # codes/vecs + ids

    # stage 3 — in-kernel exact re-rank of the r survivors
    fl3 = mem3 = 0.0
    if rerank:
        fl3 = 2.0 * s.b * t["r"] * s.d
        fl3 += hw.sort_flop * s.b * t["r_pad"] * _log2(t["r_pad"]) ** 2
        mem3 = s.b * t["r_pad"] * s.d * 4.0   # candidate row gathers

    compute = (fl1 + fl2 + fl3) / hw.flops
    memory = (mem1 + mem2 + mem3) / hw.hbm_bw
    steps = steps1 + steps2 + (s.b if rerank else 0)
    return hw.dispatch_s + steps * hw.step_s + max(compute, memory)


def predict_3dispatch_s(shape: TurnShape,
                        hw: Optional[DeviceModel] = None) -> float:
    """Modeled wall time of the classic 3-dispatch turn at the static
    default tiling: the same stage arithmetic, but three kernel
    launches and the stage-boundary intermediates (probe ids, ADC
    candidates) round-tripping through HBM."""
    hw = hw or device_model()
    s = shape
    t = resolve(shape, DEFAULT)
    rerank = s.precision != "f32" or s.family == "pq"
    one = predict_fused_s(shape, DEFAULT, hw)
    # extra launches: centroid top-k, list scan, (re-rank or merge)
    extra = 2 * hw.dispatch_s
    # stage-boundary traffic: sel (B, np) write+read, candidate ids +
    # scores (B, r) write+read, re-rank gather issued from a cold kernel
    boundary = 2.0 * s.b * (t["np_pad"] + (2 * t["r_pad"] if rerank
                                           else 0)) * 4.0
    return one + extra + 2.0 * boundary / hw.hbm_bw


# ---------------------------------------------------------------------------
# sweep + cache
# ---------------------------------------------------------------------------

BLK_P_GRID = (128, 256, 512, 1024)
MAX_TILE_GRID = (256, 512, 1024, 2048, 4096, 8192)
OVER_GRID = (1, 2, 4)


def candidates(shape: TurnShape) -> List[TileConfig]:
    """Feasible, program-distinct configs for a shape (deduped by the
    binding tile split — requests past the clamps collapse)."""
    overs = OVER_GRID if (shape.precision != "f32"
                          and shape.family == "ivf") else (DEFAULT.over,)
    seen, out = set(), []
    for bp in BLK_P_GRID:
        for mt in MAX_TILE_GRID:
            for ov in overs:
                cfg = TileConfig(blk_p=bp, max_tile=mt, over=ov)
                if not feasible(shape, cfg):
                    continue
                t = resolve(shape, cfg)
                key = (t["blk"], t["blk_l"], t["r"])
                if key in seen:
                    continue
                seen.add(key)
                out.append(cfg)
    return out


def _cache_path(shape: TurnShape, hw: DeviceModel,
                cache_dir: Optional[str] = None) -> str:
    return os.path.join(cache_dir or CACHE_DIR,
                        f"{hw.name}_{shape.key()}.json")


def autotune(shape: TurnShape, *, hw: Optional[DeviceModel] = None,
             cache_dir: Optional[str] = None, validate: bool = False,
             measure=None, top: int = 3,
             refresh: bool = False) -> TileConfig:
    """Best TileConfig for ``shape`` on this device (cached).

    Every feasible candidate is scored with the roofline model; with
    ``validate=True`` the ``top`` model picks are additionally timed
    through ``measure(cfg) -> seconds`` (e.g. the live fused op) and
    the measured best wins.  The result is cached as JSON keyed by
    shape + device kind; ``refresh=True`` re-sweeps.
    """
    hw = hw or device_model()
    path = _cache_path(shape, hw, cache_dir)
    if not refresh and os.path.exists(path):
        with open(path) as f:
            saved = json.load(f)
        return TileConfig(**saved["config"])

    cand = candidates(shape)
    if not cand:
        raise ValueError(f"no feasible tile config for {shape}")
    scored = sorted(cand, key=lambda c: predict_fused_s(shape, c, hw))
    best, measured = scored[0], None
    if validate and measure is not None:
        timed = []
        for cfg in scored[:top]:
            timed.append((measure(cfg), cfg))
        measured, best = min(timed, key=lambda t: t[0])

    record = {
        "shape": dataclasses.asdict(shape),
        "device": hw.name,
        "config": dataclasses.asdict(best),
        "predicted_s": predict_fused_s(shape, best, hw),
        "default_predicted_s": predict_fused_s(shape, DEFAULT, hw),
        "dispatch3_predicted_s": predict_3dispatch_s(shape, hw),
        "measured_s": measured,
        "vmem_bytes": vmem_bytes(shape, best),
        "n_candidates": len(cand),
        "timestamp": time.time(),
    }
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    return best


def load_records(cache_dir: Optional[str] = None) -> List[Dict]:
    """All cached autotune records (for the roofline-report judge)."""
    d = cache_dir or CACHE_DIR
    if not os.path.isdir(d):
        return []
    out = []
    for name in sorted(os.listdir(d)):
        if name.endswith(".json"):
            with open(os.path.join(d, name)) as f:
                out.append(json.load(f))
    return out
