"""Window arithmetic (chipbench/window.py) and the window's stall watch."""
import math
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))
from chipbench import window  # noqa: E402


def test_latency_runs_from_the_send_time():
    due = np.array([0.0, 1.0, 2.0])
    done = np.array([0.5, 1.1, np.nan])
    lat = window.latencies(due, done)
    np.testing.assert_allclose(lat[:2], [0.5, 0.1])
    assert math.isinf(lat[2])


def test_percentiles_are_nearest_rank():
    lat = np.arange(1, 101) / 1000.0          # 1 .. 100 ms
    assert window.percentile(lat, 50) == pytest.approx(0.050)
    assert window.percentile(lat, 95) == pytest.approx(0.095)
    assert window.percentile(lat, 100) == pytest.approx(0.100)
    assert math.isnan(window.percentile(np.array([]), 95))


def test_unanswered_turns_count_against_the_tail():
    due = np.zeros(100)
    done = np.full(100, 0.010)
    done[:4] = np.nan                           # 4% never answered
    s = window.summary(due, done, 10.0)
    assert s["turn_p95_ms"] == pytest.approx(10.0)
    assert s["unanswered"] == 4
    done[:6] = np.nan                           # 6%: the p95 is lost
    assert math.isinf(window.summary(due, done, 10.0)["turn_p95_ms"])


def test_turns_per_s_counts_answers_inside_the_window():
    due = np.linspace(0, 9.9, 100)
    done = due + 0.05
    done[-1] = 10.3                             # answered after the close
    done[0] = np.nan
    s = window.summary(due, done, 10.0)
    assert s["turns_per_s"] == pytest.approx(98 / 10.0)
    assert s["unanswered"] == 1


def test_a_stop_that_holds_the_interpreter_is_caught():
    import sys
    import time
    from chipbench.harness import StallWatch
    w = StallWatch()
    w.start(time.perf_counter())
    time.sleep(0.2)
    old = sys.getswitchinterval()
    sys.setswitchinterval(5.0)
    try:
        t = time.perf_counter()
        while time.perf_counter() - t < 0.6:   # holds the lock throughout
            pass
    finally:
        sys.setswitchinterval(old)
    time.sleep(0.2)
    w.close()
    assert len(w.stalls) == 1
    at, length = w.stalls[0]
    assert 0.1 < at < 0.4 and 0.3 < length < 0.9
