"""The reduction from a device trace to numbers (chipbench/tracing.py)."""
import gzip
import json
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))
from chipbench import reduce as rd  # noqa: E402
from chipbench import tracing  # noqa: E402

RECORDED = pathlib.Path(__file__).with_name("data") / "trace_slice.json.gz"


def synthetic():
    ms = 1e6
    ops = [("fusion.1", 0 * ms, 2 * ms), ("fusion.2", 1 * ms, 2 * ms),
           ("pq_adc_scan.3", 5 * ms, 1 * ms), ("copy.4", 8 * ms, 1 * ms)]
    mods = [("jit_step_batch(17)", 0, 3 * ms),
            ("jit_step_batch(17)", 5 * ms, 1 * ms),
            ("jit__scatter_slab(9)", 8 * ms, 1 * ms)]
    host = {"pump": [("PjitFunction(step_batch)", 3.5 * ms, 1 * ms),
                     ("wait", 3 * ms, 5 * ms)]}
    return {"device": {tracing.OPS_LINE: ops, tracing.MODULES_LINE: mods},
            "host": host}


def test_union_merges_overlaps():
    assert tracing.union([(3, 4), (0, 2), (1, 3), (6, 7)]) == [(0, 4),
                                                               (6, 7)]


def test_reduce_on_a_synthetic_trace():
    r = tracing.reduce(synthetic(), kernels=("pq_adc",))
    assert r["busy_s"] == pytest.approx(0.005)        # 0-3, 5-6, 8-9 ms
    assert r["window_s"] == pytest.approx(0.009)      # first to last event
    assert r["modules"]["jit_step_batch"] == pytest.approx([0.003, 0.001])
    assert r["kernel_s"]["pq_adc"] == pytest.approx(0.001)
    ops = dict((n, v) for n, v in r["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.002)
    gaps = dict((n, v) for n, v in r["breakdown"]["idle_gaps"])
    # 3-5 ms: the step's dispatch is the shortest host event over 4 ms;
    # 6-8 ms: only the long wait covers 7 ms
    assert gaps == pytest.approx({"PjitFunction(step_batch)": 0.002,
                                  "wait": 0.002})


def test_a_trace_without_device_operations_is_an_error():
    with pytest.raises(ValueError):
        tracing.reduce({"device": {}, "host": {}})


def test_module_names_drop_their_id():
    assert tracing.module_name("jit_step_batch(1234)") == "jit_step_batch"
    assert tracing.module_name("jit_step_batch") == "jit_step_batch"


def recorded():
    with gzip.open(RECORDED, "rt") as f:
        return json.load(f)["events"]


def test_reduce_on_a_recorded_chip_trace():
    """150 ms of a traced ``ivf-conv-rate`` run on a v5e chip: four
    waves, each a session gather and one ``step_batch``."""
    r = tracing.reduce(recorded(), kernels=("pq_adc",))
    assert 0.14 < r["window_s"] < 0.2
    assert 0 < r["busy_s"] <= r["window_s"]
    steps = r["modules"]["jit_step_batch"]
    assert len(steps) == 4
    assert all(0.010 < s < 0.030 for s in steps)
    assert len(r["modules"]["jit_gather"]) == 20       # 5 slab fields x 4
    assert r["kernel_s"]["pq_adc"] == 0.0              # no ADC in IVF
    ops = r["breakdown"]["device_ops"]
    assert len(ops) == 10 and ops[0][1] >= ops[-1][1]
    assert all(len(n) <= tracing.OP_NAME_CHARS for n, _ in ops)
    gaps = r["breakdown"]["idle_gaps"]
    assert sum(v for _, v in gaps) <= r["window_s"] - r["busy_s"] + 1e-9


def test_metrics_from_the_recorded_trace():
    from types import SimpleNamespace as NS
    from chipbench.harness import Observations
    r = tracing.reduce(recorded())
    rec = [NS(list_dists=4000, conv_id="c", turn=1, queue_wait_s=0.0,
              centroid_dists=1024, code_dists=0)] * 128
    o = Observations({"p": 16384, "d": 768}, rec, [], [], 0.0,
                     {"hbm_bytes_per_s": 819e9}, r)
    o.traced_records = rec
    assert 0 <= rd.device_idle_share(o) < 100
    assert rd.step_device_ms(o) == pytest.approx(
        1e3 * sum(r["modules"]["jit_step_batch"]) / 4)
    assert 0 < rd.step_roofline(o) < 100
    assert rd.gather_device_ms(o) == pytest.approx(
        1e3 * sum(r["modules"]["jit_gather"]) / 4)
