"""Arithmetic shared by the per-layer metric readers (``metrics/``).

Each reader takes an ``harness.Observations`` and returns a number, or
None where it finds nothing to read.  Shares are in percent.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

#: the jitted module of one engine wave, as named in the device trace
STEP_MODULE = "jit_step_batch"
#: the session gather's programs (one per slab field), run eagerly
#: before each wave's step
GATHER_MODULE = "jit_gather"
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
    if obs.trace is None:
        return []
    return obs.trace["modules"].get(STEP_MODULE, [])


def step_device_ms(obs) -> Optional[float]:
    steps = _steps(obs)
    return float(np.mean(steps) * 1e3) if steps else None


def gather_device_ms(obs) -> Optional[float]:
    """Device time of the session gather per wave: the ``jit_gather``
    executions' time over the number of ``step_batch`` executions."""
    steps = _steps(obs)
    if not steps:
        return None
    gathers = obs.trace["modules"].get(GATHER_MODULE, [])
    return float(np.sum(gathers) / len(steps) * 1e3) if gathers else None


def device_idle_share(obs) -> Optional[float]:
    t = obs.trace
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def step_roofline(obs) -> Optional[float]:
    """Least time to read the probed lists' real rows at the chip's peak
    bandwidth over the ``step_batch`` device time, for the waves traced.
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
