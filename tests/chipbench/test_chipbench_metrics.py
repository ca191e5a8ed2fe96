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


def obs(records=(), trace=None, traced=None, batches=(), padded=(),
        config=CFG, replica=()):
    o = Observations(config, list(records),
                     list(replica) or [0] * len(records), list(batches),
                     list(padded), 12.5, PEAKS, trace)
    o.traced_records = list(records if traced is None else traced)
    return o


def trace(busy=0.4, window=1.0, steps=(0.004, 0.006), adc=0.0,
          chips=None):
    out = {"busy_s": busy, "window_s": window,
           "modules": {"jit_step_batch": list(steps)},
           "kernel_s": {"pq_adc": adc}}
    out["chips"] = chips if chips is not None else [dict(out)]
    return out


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


def test_idle_share_of_four_chips_is_their_mean():
    chips = [{"busy_s": b, "window_s": 2.0} for b in (1.0, 1.5, 1.9, 2.0)]
    o = obs([rec()], trace(busy=1.0, window=2.0, chips=chips))
    # (50 + 25 + 5 + 0) / 4, not chip 0's own 50
    assert read("device_idle_share", o) == pytest.approx(20.0)
    # one chip: its own share
    o = obs([rec()], trace(busy=0.25, window=2.0))
    assert read("device_idle_share", o) == pytest.approx(87.5)


def test_replica_imbalance_is_the_busiest_over_the_mean():
    four = dict(CFG, replicas=4)
    o = obs([rec()] * 10, config=four, replica=[0] * 4 + [1, 1, 2, 2, 3, 3])
    assert read("replica_imbalance", o) == pytest.approx(160.0)
    even = obs([rec()] * 8, config=four, replica=[0, 1, 2, 3] * 2)
    assert read("replica_imbalance", even) == pytest.approx(100.0)
    # a replica that served nothing counts in the mean
    idle = obs([rec()] * 4, config=four, replica=[0, 0, 1, 1])
    assert read("replica_imbalance", idle) == pytest.approx(200.0)
    # one replica, records not tagged
    assert read("replica_imbalance", obs([rec()] * 3)) == pytest.approx(100.0)


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
                                  "device_idle_share", "step_roofline",
                                  "adc_roofline", "replica_imbalance"])
def test_nothing_to_read_gives_nothing(name):
    assert read(name, obs()) is None


def test_a_trace_without_the_kernel_gives_no_adc_share():
    assert read("adc_roofline", obs([rec(codes=10)], trace(adc=0.0))) is None
