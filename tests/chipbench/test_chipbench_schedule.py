"""The seeded open-loop schedule (chipbench/gen.py)."""
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))
from chipbench import gen, harness, registry  # noqa: E402

CONV = {"rate_turns_per_s": 1000, "turns_min": 6, "turns_max": 12,
        "live_population": 1024, "shift_prob": 0.1}
SINGLE = dict(CONV, turns_min=1, turns_max=1, live_population=0)
BIG = 2 ** 33 + 12345


def test_same_seed_same_schedule():
    a = gen.schedule(CONV, BIG, 20.0, 2048)
    b = gen.schedule(CONV, BIG, 20.0, 2048)
    np.testing.assert_array_equal(a.window, b.window)
    np.testing.assert_array_equal(a.replay, b.replay)
    assert (a.n_fill, a.n_live) == (b.n_fill, b.n_live)


@pytest.mark.parametrize("other", [BIG + 1, BIG + 2 ** 32, BIG - 2 ** 32])
def test_other_seed_other_order_same_work(other):
    a = gen.schedule(CONV, BIG, 20.0, 2048)
    b = gen.schedule(CONV, other, 20.0, 2048)
    assert not np.array_equal(a.window[:, 0], b.window[:, 0])
    # the same conversations, turn counts and think times, and exactly
    # rate x seconds turns due in the window
    assert a.n_convs == b.n_convs
    assert len(a.window) == len(b.window) == 20_000
    assert abs(a.n_live - b.n_live) < 0.05 * a.n_live


def test_send_times_fixed_in_advance_and_ordered():
    s = gen.schedule(CONV, 3, 20.0, 2048)
    t = s.window[:, 0]
    assert np.all(np.diff(t) >= 0) and t[0] >= 0 and t[-1] < 20.0
    assert np.all(s.replay[:, 0] < 0)
    # each conversation's turns come in order
    for table in (s.window, s.replay):
        last = {}
        for _, c, k in table:
            assert k > last.get(c, -1)
            last[c] = k


@pytest.mark.parametrize("seed", [1, 2, BIG])
def test_population_is_stationary(seed):
    s = gen.schedule(CONV, seed, 20.0, 2048)
    t = s.window[:, 0]
    # the offered rate, in each half of the window
    for lo, hi in ((0, 10), (10, 20)):
        n = np.sum((t >= lo) & (t < hi))
        assert abs(n / 10.0 - 1000) < 60, n
    # first turns are one in ~9 throughout, not a ramp at the opening
    first = s.window[:, 2] == 0
    for lo, hi in ((0, 5), (15, 20)):
        m = (t >= lo) & (t < hi)
        assert 0.07 < first[m].mean() < 0.16
    # about live_population * 8 / 9 conversations are live at t = 0, and
    # the slab is full of them and the fillers when the window opens
    assert 800 < s.n_live < 1000
    assert s.n_live + s.n_fill == 2048 + 8


def test_single_turn_mix():
    s = gen.schedule(SINGLE, 4, 10.0, 2048)
    assert len(s.replay) == 0 and s.n_live == 0
    assert s.n_fill == 2048 + 8
    assert np.all(s.window[:, 2] == 0)
    assert len(set(s.window[:, 1].tolist())) == len(s.window)
    assert len(s.window) == 10_000


def test_replay_order_puts_fillers_first():
    s = gen.schedule(CONV, 5, 5.0, 2048)
    order = gen.replay_order(s)
    assert all(c >= s.n_convs and k == 0 for c, k in order[:s.n_fill])
    assert len(order) == s.n_fill + len(s.replay)
    assert s.conv_id(order[0][0]).startswith("f")
    assert s.conv_id(0) == "c0"


def test_sample_is_a_fixed_draw():
    s = gen.schedule(CONV, 6, 10.0, 2048)
    a, b = gen.sample(s, 6, 512), gen.sample(s, 6, 512)
    np.testing.assert_array_equal(a, b)
    assert len(set(a.tolist())) == 512
    assert not np.array_equal(a, gen.sample(s, 7, 512))


def _cv(x):
    return float(np.std(x) / np.mean(x))


@pytest.mark.parametrize("seed", [8, BIG])
def test_arrivals_are_poisson(seed):
    # single-turn: every turn a conversation's arrival, so the gaps
    # between sends are exponential (coefficient of variation 1), not
    # the smoothed gaps of one arrival per equal slice (about 0.41)
    s = gen.schedule(SINGLE, seed, 10.0, 2048)
    assert 0.95 < _cv(np.diff(s.window[:, 0])) < 1.05
    # conversational: the first turns due in the window arrive so too
    c = gen.schedule(CONV, seed, 60.0, 2048)
    firsts = c.window[c.window[:, 2] == 0, 0]
    assert 0.9 < _cv(np.diff(firsts)) < 1.1


def test_fillers_fill_every_replicas_slab():
    cfg = registry.config("cast-ivf-f32-router4")
    assert harness.replicas(cfg) == 4
    assert harness.slots(cfg) == 4 * cfg["n_slots"] == 8192
    conv4 = dict(CONV, live_population=4096)
    s = gen.schedule(conv4, BIG, 20.0, harness.slots(cfg))
    # about a quarter of the four slabs live, the rest fillers, and a
    # few more than fit, so that every replica evicts in set-up
    assert 3200 < s.n_live < 4000
    assert s.n_live + s.n_fill == 8192 + 8


class Recorder:
    """A router that keeps what set-up sends it, drain by drain."""

    def __init__(self):
        self.sent, self.drains = [], []

    def submit(self, conv_id, qvec):
        self.sent.append(conv_id)

    def drain(self):
        self.drains.append(len(self.sent) - sum(self.drains))
        return self.drains[-1]


def test_one_replica_runs_as_before():
    """A configuration without ``replicas`` gets the schedule, fillers
    and set-up order it got before replicas were taken: the schedule of
    its own slab (these counts are what that gave for this seed), one
    wave of each bucket size, then waves of ``max_batch``."""
    cfg = registry.config("cast-ivf-f32")
    assert "replicas" not in cfg and harness.replicas(cfg) == 1
    assert harness.slots(cfg) == cfg["n_slots"] == 2048
    traffic = registry.traffic("conv-open-500")
    s = gen.schedule(traffic, 4200000137, 40.0, harness.slots(cfg))
    assert (s.n_convs, s.n_fill, s.n_live) == (5977, 1107, 949)
    assert (len(s.window), len(s.replay), s.turns) == (20000, 4474, 12)
    assert s.window[:3, 1:].astype(int).tolist() == [[2650, 10], [3577, 2],
                                                     [3047, 5]]
    order = gen.replay_order(s)
    r = Recorder()
    served = harness.warm_up(r, s, np.zeros((s.n_convs + 2056, 12, 1)),
                             cfg["max_batch"], harness.replicas(cfg))
    assert served == len(order) == 5581
    assert r.sent == [s.conv_id(c) for c, _ in order]
    full, rest = divmod(len(order) - 63, 32)
    assert r.drains == [1, 2, 4, 8, 16, 32] + [32] * full + [rest]


def test_four_replicas_warm_every_bucket_on_each():
    s = gen.schedule(dict(CONV, live_population=4096), 5, 5.0, 8192)
    r = Recorder()
    harness.warm_up(r, s, np.zeros((s.n_convs + 8200, 12, 1)), 32, 4)
    # fillers first, b x 4 at a time: the router pins new conversations
    # in turn, so each replica gets one wave of b
    assert r.drains[:6] == [4, 8, 16, 32, 64, 128]
    assert set(r.drains[6:-1]) == {128}
    assert all(c.startswith("f") for c in r.sent[:252])
