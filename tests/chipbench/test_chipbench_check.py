"""The check that decides ``correct``: a sound run passes it, the
control and each fault the cells can have fail it.

Runs the harness at a tiny size on the CPU: the look for a chip is
skipped (it lives in ``run.py``), the rest of a run is driven as on the
chip, with the configuration's own limits.
"""
import json
import os
import pathlib
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))
from chipbench import gen, harness, limits, reference, registry  # noqa: E402

# one row per wave: the CPU's float32 list scan sums a batch of two or
# more in another order than the chip's, reading a mean score gap of
# about 7.8e-8 against 9.4e-9 at one row (the chip: 8e-9 at 32 rows),
# too near the control's 1.35e-7 to hold the configuration's limit
TINY = dict(n_docs=1 << 13, n_topics=64, p=64, h=32, nprobe=8, n_slots=64,
            max_batch=1, kmeans_iters=5, check_turns=64)
TRAFFIC = {"rate_turns_per_s": 120, "turns_min": 2, "turns_max": 6,
           "live_population": 24, "shift_prob": 0.1}
SEED = 2 ** 33 + 77


def tiny(name, **extra):
    cfg = registry.config(name)
    cfg.update(TINY, **extra)
    return cfg


def run(cfg, seed=SEED):
    return harness.run_cell({"name": "tiny"}, cfg, TRAFFIC, seed=seed,
                            seconds=1.5, trace=False, end_to_end=[],
                            per_layer=[], out_dir="unused",
                            proc_start=time.perf_counter())


CONFIGS = [tiny("cast-ivf-f32"),
           tiny("cast-ivfpq-m48", pq_iters=3, rerank=32)]
# four replicas behind the router: each on a CPU device of its own where
# there are four or more, all on the first otherwise
ROUTER = tiny("cast-ivf-f32-router4")


@pytest.mark.parametrize("cfg", CONFIGS, ids=lambda c: c["name"])
def test_a_sound_run_is_correct(cfg):
    out = run(cfg)
    assert out["correct"], out["compared"]
    assert out["attempted"] == 180 and out["failed"] == 0
    assert list(out)[-1] == "compared"
    assert set(out["compared"]) == {"unanswered", *cfg["limits"]}


def served_by_four_replicas():
    """A tiny run of the router configuration, and what it left: the
    devices its replicas served from, the replicas each conversation met,
    and the window's turns by the replica tag the metric readers got."""
    made, seen = [], []
    real_engine, real_obs = harness.make_engine, harness.Observations
    harness.make_engine = lambda cfg, **kw: (made.append(
        real_engine(cfg, **kw)) or made[-1])
    harness.Observations = lambda *a, **kw: (seen.append(
        real_obs(*a, **kw)) or seen[-1])
    try:
        out = run(ROUTER)
    finally:
        harness.make_engine, harness.Observations = real_engine, real_obs
    (router,), (obs,) = made, seen
    where = {}
    for k, eng in enumerate(router.engines):
        for r in eng.records:
            where.setdefault(r.conv_id, set()).add(k)
    return {"out": out, "chips": [str(d) for d in harness.chips(router)],
            "tags": np.bincount(obs.replica, minlength=4).tolist(),
            "imbalance": registry.reader("replica_imbalance")(obs),
            "replicas_met": sorted({len(ks) for ks in where.values()})}


def check_four_replicas(got, devices):
    out = got["out"]
    assert out["correct"], out["compared"]
    assert out["attempted"] == 180 and out["failed"] == 0
    # a chip of its own for each replica where there are four
    assert out["device"]["count"] == len(got["chips"]) == min(4, devices)
    # every window turn tagged, every replica serving some
    assert sum(got["tags"]) == 180 and min(got["tags"]) > 0, got["tags"]
    assert got["imbalance"] == pytest.approx(
        100.0 * max(got["tags"]) / (180 / 4))
    assert got["replicas_met"] == [1]


def test_four_replicas_answer_every_turn_each_conversation_on_one():
    check_four_replicas(served_by_four_replicas(), jax.device_count())


FOUR_DEVICES = """
import json, sys
sys.path.insert(0, {here!r})
import test_chipbench_check as t
got = t.served_by_four_replicas()
print(json.dumps(dict(got, devices=t.jax.device_count())))
"""


def test_four_replicas_on_four_devices_each_serve_from_their_own():
    """The same run in a process that sees four CPU devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    here = str(pathlib.Path(__file__).parent)
    proc = subprocess.run([sys.executable, "-c",
                           FOUR_DEVICES.format(here=here)],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["devices"] == 4
    check_four_replicas(got, 4)
    assert len(set(got["chips"])) == 4


class Chip:
    def __init__(self, in_use, peak):
        self.stats = {"bytes_in_use": in_use, "peak_bytes_in_use": peak}

    def memory_stats(self):
        return self.stats


def test_memory_is_read_on_the_fullest_chip():
    # each number from the chip that holds the most of it
    chips = [Chip(5, 9), Chip(7, 8), Chip(6, 12), Chip(1, 2)]
    assert harness.fullest(chips) == (7, 12)
    assert harness.fullest(chips[:1]) == (5, 9)


class NoStats:
    def memory_stats(self):
        return None


def test_a_backend_without_memory_counts_reads_nothing():
    assert harness.fullest([NoStats(), NoStats()]) == (0, 0)


@pytest.mark.parametrize("fault", ["state", "answer"])
@pytest.mark.parametrize("cfg", CONFIGS + [ROUTER], ids=lambda c: c["name"])
def test_a_planted_fault_is_not_correct(cfg, fault):
    with limits.planted(fault, cfg["n_docs"]):
        out = run(cfg)
    assert not out["correct"], out["compared"]


@pytest.mark.parametrize("cfg", CONFIGS + [ROUTER], ids=lambda c: c["name"])
def test_the_control_is_not_correct(cfg):
    got = limits.control_readings(cfg, TRAFFIC, SEED, 1.5)
    assert got["score_err"] > cfg["limits"]["score_err"], got
    assert got["recall_miss"] == 0.0


def test_order_readings_rescore_the_exact_answers():
    got = limits.order_readings(CONFIGS[0], TRAFFIC, SEED, 1.5)
    for path in ("sum", "sequential"):
        assert got[f"{path}_recall_miss"] == 0.0
        assert 0 < got[f"{path}_score_err"] < 1e-6


def test_an_answer_that_never_came_is_not_correct():
    cfg = CONFIGS[0]
    _, docs = gen.corpus(cfg, 1)
    q = np.asarray(docs[:3])
    ids = np.tile(np.arange(10), (3, 1))
    answers = [(np.asarray(reference.served_scores_f64(docs, q[:1],
                                                       ids[:1]))[0], ids[0]),
               None, None]
    r = harness.check(docs, q, answers, cfg)
    assert r["unanswered"]["value"] == 2 > r["unanswered"]["limit"]


def test_an_id_served_twice_is_a_wrong_answer():
    cfg = CONFIGS[0]
    _, docs = gen.corpus(cfg, 1)
    q = np.asarray(docs[:2])
    ids = np.tile(np.arange(10), (2, 1))
    ids[1, 3] = ids[1, 2]
    scores = reference.served_scores_f64(docs, q, ids)
    got = reference.compare(docs, q, scores, ids, 10)
    assert got["score_err_max"] == reference.WRONG_ANSWER
    assert got["score_err"] > cfg["limits"]["score_err"]
