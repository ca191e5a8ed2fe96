"""Arithmetic shared by the per-layer metric readers (``metrics/``).

Each reader takes an ``harness.Observations`` and returns a number, or
None where it finds nothing to read.  Shares are in percent.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

#: the jitted module of one engine wave, as named in the device trace
STEP_MODULE = "jit_step_batch"
F32_BYTES = 4


def turn_p95_ms(obs) -> Optional[float]:
    return obs.window.get("turn_p95_ms")


def queue_wait_p95_ms(obs) -> Optional[float]:
    waits = [r.queue_wait_s for r in obs.records]
    return float(np.percentile(waits, 95) * 1e3) if waits else None


def batch_fill(obs) -> Optional[float]:
    padded = sum(obs.padded_sizes)
    return 100.0 * sum(obs.batch_sizes) / padded if padded else None


def full_scan_share(obs) -> Optional[float]:
    """Turns that scored every centroid: first turns (p) and refreshes
    (h + p)."""
    if not obs.records:
        return None
    p = obs.config["p"]
    return 100.0 * float(np.mean([r.centroid_dists >= p
                                  for r in obs.records]))


def _steps(obs):
    """Device times of every chip's ``step_batch`` executions."""
    if obs.trace is None:
        return []
    return [s for c in obs.trace["chips"]
            for s in c["modules"].get(STEP_MODULE, [])]


def step_device_ms(obs) -> Optional[float]:
    steps = _steps(obs)
    return float(np.mean(steps) * 1e3) if steps else None


def device_idle_share(obs) -> Optional[float]:
    """The idle share of each chip's plane, averaged over the cell's
    chips (one chip: its own)."""
    chips = [c for c in (obs.trace or {}).get("chips", [])
             if c["window_s"] > 0]
    if not chips:
        return None
    return 100.0 * float(np.mean([1.0 - c["busy_s"] / c["window_s"]
                                  for c in chips]))


def replica_imbalance(obs) -> Optional[float]:
    """The busiest replica's turns over the mean of the configuration's
    replicas, %: 100 is an even spread, 100 x R all on one."""
    if not obs.records:
        return None
    counts = np.bincount(obs.replica, minlength=obs.config.get("replicas", 1))
    return 100.0 * float(counts.max() / counts.mean())


def step_roofline(obs) -> Optional[float]:
    """Least time to read the probed lists' real rows at the chip's peak
    bandwidth over the ``step_batch`` device time of every chip, for the
    waves traced.
    Counts rows per turn: a scan that reads one list once for several
    turns of a wave could read above 100%."""
    steps = _steps(obs)
    if not steps or not obs.traced_records or not obs.peaks:
        return None
    rows = sum(r.list_dists for r in obs.traced_records)
    least = rows * obs.config["d"] * F32_BYTES / obs.peaks["hbm_bytes_per_s"]
    return 100.0 * least / float(np.sum(steps))


def adc_roofline(obs) -> Optional[float]:
    """Least time for the ADC kernel's bytes at peak bandwidth (each
    scanned code row, m bytes, and its id, 4 bytes; each query's lookup
    table, m x 256 float32) over the kernel's device time."""
    if obs.trace is None or not obs.traced_records or not obs.peaks:
        return None
    kernel_s = obs.trace["kernel_s"].get("pq_adc", 0.0)
    if kernel_s <= 0:
        return None
    m = obs.config["pq_m"]
    codes = sum(r.code_dists for r in obs.traced_records)
    luts = len(obs.traced_records) * m * 256 * F32_BYTES
    least = (codes * (m + 4) + luts) / obs.peaks["hbm_bytes_per_s"]
    return 100.0 * least / kernel_s
