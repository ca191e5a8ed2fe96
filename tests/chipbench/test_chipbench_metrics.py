"""Every per-layer metric reader, on observations made by hand."""
import pathlib
import sys
from types import SimpleNamespace as NS

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))
from chipbench import registry  # noqa: E402
from chipbench.harness import Observations  # noqa: E402

PEAKS = {"hbm_bytes_per_s": 819e9}
CFG = {"p": 16384, "d": 768, "pq_m": 48}


def rec(wait_s=0.001, centroid=1024, lists=4000, codes=0):
    return NS(conv_id="c0", turn=1, queue_wait_s=wait_s,
              centroid_dists=centroid, list_dists=lists, code_dists=codes)


def obs(records=(), trace=None, traced=None, batches=(), padded=()):
    o = Observations(CFG, list(records), list(batches), list(padded),
                     12.5, PEAKS, trace)
    o.traced_records = list(records if traced is None else traced)
    return o


def trace(busy=0.4, window=1.0, steps=(0.004, 0.006), adc=0.0,
          gathers=()):
    mods = {"jit_step_batch": list(steps)}
    if gathers:
        mods["jit_gather"] = list(gathers)
    return {"busy_s": busy, "window_s": window, "modules": mods,
            "kernel_s": {"pq_adc": adc}}


def read(name, o):
    return registry.reader(name)(o)


def test_turn_p95_is_the_windows():
    o = obs()
    o.window = {"turn_p95_ms": 123.5, "turn_p50_ms": 90.0}
    assert read("turn_p95_ms", o) == 123.5


def test_queue_wait_p95():
    o = obs([rec(wait_s=w / 1000) for w in range(1, 101)])
    assert read("queue_wait_p95_ms", o) == pytest.approx(95.05)


def test_batch_fill():
    o = obs(batches=[32, 20, 1], padded=[32, 32, 1])
    assert read("batch_fill.overload", o) == pytest.approx(100 * 53 / 65)


def test_full_scan_share_counts_first_turns_and_refreshes():
    o = obs([rec(centroid=16384), rec(centroid=1024 + 16384),
             rec(centroid=1024), rec(centroid=1024)])
    assert read("full_scan_share", o) == pytest.approx(50.0)


def test_step_device_ms_and_idle_share():
    o = obs([rec()], trace(busy=0.25, window=2.0))
    assert read("step_device_ms", o) == pytest.approx(5.0)
    assert read("device_idle_share", o) == pytest.approx(87.5)
    assert read("device_idle_share.overload", o) == pytest.approx(87.5)


def test_gather_device_ms_is_per_wave():
    # two waves, each gathering the slab's five fields
    o = obs([rec()], trace(gathers=[0.004] * 10))
    assert read("gather_device_ms", o) == pytest.approx(20.0)
    assert read("gather_device_ms", obs([rec()], trace())) is None


def test_step_roofline_counts_real_rows_at_peak_bandwidth():
    rows = [rec(lists=4096) for _ in range(64)]
    o = obs(rows, trace(steps=(0.002, 0.002)))
    least = 64 * 4096 * 768 * 4 / 819e9
    assert read("step_roofline", o) == pytest.approx(100 * least / 0.004)
    assert read("step_roofline.overload", o) == read("step_roofline", o)


def test_adc_roofline():
    rows = [rec(codes=4096) for _ in range(32)]
    o = obs(rows, trace(adc=0.003))
    least = (32 * 4096 * (48 + 4) + 32 * 48 * 256 * 4) / 819e9
    assert read("adc_roofline", o) == pytest.approx(100 * least / 0.003)


def test_build_s():
    assert read("build_s", obs()) == 12.5


@pytest.mark.parametrize("name", ["turn_p95_ms", "queue_wait_p95_ms",
                                  "batch_fill.overload",
                                  "full_scan_share", "step_device_ms",
                                  "gather_device_ms",
                                  "device_idle_share", "step_roofline",
                                  "adc_roofline"])
def test_nothing_to_read_gives_nothing(name):
    assert read(name, obs()) is None


def test_a_trace_without_the_kernel_gives_no_adc_share():
    assert read("adc_roofline", obs([rec(codes=10)], trace(adc=0.0))) is None
