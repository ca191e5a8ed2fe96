"""The check that decides ``correct``: a sound run passes it, the
control and each fault the cells can have fail it.

Runs the harness at a tiny size on the CPU: the look for a chip is
skipped (it lives in ``run.py``), the rest of a run is driven as on the
chip, with the configuration's own limits.
"""
import pathlib
import sys
import time

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


@pytest.mark.parametrize("cfg", CONFIGS, ids=lambda c: c["name"])
def test_a_sound_run_is_correct(cfg):
    out = run(cfg)
    assert out["correct"], out["compared"]
    assert out["attempted"] == 180 and out["failed"] == 0
    assert list(out)[-1] == "compared"
    assert set(out["compared"]) == {"unanswered", *cfg["limits"]}


@pytest.mark.parametrize("fault", ["state", "answer"])
@pytest.mark.parametrize("cfg", CONFIGS, ids=lambda c: c["name"])
def test_a_planted_fault_is_not_correct(cfg, fault):
    with limits.planted(fault, cfg["n_docs"]):
        out = run(cfg)
    assert not out["correct"], out["compared"]


@pytest.mark.parametrize("cfg", CONFIGS, ids=lambda c: c["name"])
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
