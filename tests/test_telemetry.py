"""Spans of the serving loop (``serving/telemetry.py``).

Telemetry is off by default and then records nothing; on, it changes
no result, its spans nest as the serving loop runs them, every
``TurnRecord`` names a recorded wave, and a wave's records give the
step's batch-wide refresh gate.
"""
import jax
import numpy as np
import pytest

from repro.core import toploc
from repro.serving import (BatchedConversationalSearchEngine,
                           ReplicatedSearchEngine, ServingConfig, Telemetry,
                           telemetry)

K, H, NPROBE = 10, 16, 4
C, T = 4, 6

#: the span each span opens inside, by name (None: at the top)
PARENTS = {
    "pump.drain": {None},
    "wave.launch": {None},
    "wave.assemble": {"wave.launch"},
    "store.acquire": {"wave.launch"},
    "wave.step": {"wave.launch"},
    "batch.retire": {None, "pump.sync"},
    "wave.fetch": {"batch.retire"},
    "wave.records": {"batch.retire"},
    "batch.resolve": {"batch.retire"},
    "pump.sync": {None},
}


def _cfg(strategy, **kw):
    return ServingConfig(backend="ivf", strategy=strategy, nprobe=NPROBE,
                         h=H, alpha=0.3, k=K, **kw)


def _engine(ivf_index, strategy="toploc+", **kw):
    # max_batch 8 over 4 conversations: a drain of every queued turn
    # holds two turns of each, so one launch splits into two waves
    return BatchedConversationalSearchEngine(
        _cfg(strategy, **kw), ivf_index=ivf_index, n_slots=8, max_batch=8,
        max_wait_s=0.0)


def _turns(small_corpus):
    convs = np.asarray(small_corpus.conversations, np.float32)
    return [(f"c{c}", convs[c, t]) for t in range(T) for c in range(C)]


def _serve(eng, turns, tel=None):
    eng.set_telemetry(tel)
    futs = [eng.submit(c, q) for c, q in turns]
    eng.drain()
    return [f.result() for f in futs]


def _served(small_corpus, ivf_index, strategy="toploc+", **kw):
    eng = _engine(ivf_index, strategy, **kw)
    tel = Telemetry()
    _serve(eng, _turns(small_corpus), tel)
    return eng, tel


def test_off_by_default_records_nothing(small_corpus, ivf_index):
    eng = _engine(ivf_index)
    assert eng.telemetry is None and eng.batcher.telemetry is None
    assert telemetry.span(None, "wave.launch", 3) is telemetry._OFF
    tel = Telemetry()
    turns = _turns(small_corpus)
    _serve(eng, turns[:8], tel)
    n_spans = len(tel.spans())
    assert n_spans
    _serve(eng, turns[8:], None)
    assert len(tel.spans()) == n_spans
    assert all(r.wave >= 0 for r in eng.records)


def _by_turn(records):
    fields = ("centroid_dists", "list_dists", "graph_dists", "refreshed",
              "i0", "code_dists", "cache_hit")
    return {(r.conv_id, r.turn): tuple(getattr(r, f) for f in fields)
            for r in records}


def _route(ivf_index, turns, tel):
    with ReplicatedSearchEngine(_cfg("toploc+"), replicas=1,
                                ivf_index=ivf_index, n_slots=8, max_batch=8,
                                max_wait_s=1e-4) as router:
        router.set_telemetry(tel)
        router.start()
        # a conversation's next turn is sent once its last one answered
        out = []
        for t in range(T):
            futs = [router.submit(c, q) for c, q in turns[t * C:(t + 1) * C]]
            out += [f.result(timeout=60) for f in futs]
        return out, router.records


@pytest.mark.parametrize("path", ["engine", "router"])
def test_on_and_off_serve_the_same(small_corpus, ivf_index, path):
    turns = _turns(small_corpus)
    runs = []
    for tel in (None, Telemetry()):
        if path == "engine":
            eng = _engine(ivf_index)
            res = _serve(eng, turns, tel)
            recs = eng.records
        else:
            res, recs = _route(ivf_index, turns, tel)
        runs.append((res, _by_turn(recs), tel))
    (res0, recs0, _), (res1, recs1, tel) = runs
    for (v0, i0), (v1, i1) in zip(res0, res1):
        np.testing.assert_array_equal(i0, i1)
        np.testing.assert_array_equal(v0, v1)
    assert recs0 == recs1 and len(recs0) == C * T
    names = {s.name for s in tel.spans()}
    assert {"wave.launch", "wave.fetch", "pump.drain"} <= names
    if path == "router":
        assert "pump.sync" in names
        assert {s.thread for s in tel.spans()} == {"replica-pump-0"}


def test_spans_nest_under_their_parents(small_corpus, ivf_index):
    _, tel = _served(small_corpus, ivf_index)
    spans = tel.spans()
    by_id = {s.id: s for s in spans}
    assert len(by_id) == len(spans)
    for s in spans:
        parent = by_id[s.parent].name if s.parent >= 0 else None
        assert parent in PARENTS[s.name], (s.name, parent)
        assert s.start_ns <= s.end_ns
    assert set(PARENTS) - {"pump.sync"} <= {s.name for s in spans}


def test_a_waves_children_fall_inside_its_launch(small_corpus, ivf_index):
    eng, tel = _served(small_corpus, ivf_index)
    spans = tel.spans()
    launches = {s.id: s for s in spans if s.name == "wave.launch"}
    assert sorted(s.wave for s in launches.values()) == list(
        range(len(launches)))
    assert len(launches) > len(eng.batcher.batch_sizes)   # split drains
    children = [s for s in spans if s.parent in launches]
    assert {s.name for s in children} == {
        "wave.assemble", "store.acquire", "wave.step"}
    for s in children:
        w = launches[s.parent]
        assert s.wave == w.wave
        assert w.start_ns <= s.start_ns <= s.end_ns <= w.end_ns


def test_every_record_names_a_recorded_wave(small_corpus, ivf_index):
    eng, tel = _served(small_corpus, ivf_index)
    waves = {s.wave for s in tel.spans() if s.name == "wave.launch"}
    assert {r.wave for r in eng.records} == waves
    fetched = [s.wave for s in tel.spans() if s.name == "wave.fetch"]
    assert sorted(fetched) == sorted(waves)
    assert len(eng.records) == C * T


@pytest.mark.parametrize("strategy,cache", [("toploc", 0.0),
                                            ("toploc+", 0.0),
                                            ("toploc", 0.5)])
def test_gate_open_is_any_refresh_over_the_bucket(small_corpus, ivf_index,
                                                  strategy, cache,
                                                  monkeypatch):
    # the step's own flags over the padded bucket, one a wave in wave
    # order, from before the result cache's fuse (the step runs inside
    # the wave program, so a callback hands them out); a hit zeroes its
    # record's flag but is never a first turn, so a wave's records
    # still give the gate the device computed
    flags = []
    step = toploc.step_batch

    def kept(*args, **kw):
        out = step(*args, **kw)
        jax.debug.callback(lambda f: flags.append(np.asarray(f)),
                           out[3].refreshed, ordered=True)
        return out
    monkeypatch.setattr(toploc, "step_batch", kept)
    eng, _ = _served(small_corpus, ivf_index, strategy,
                     cache_threshold=cache)
    gate = {w: bool(f.any()) for w, f in enumerate(flags)}
    assert len(flags) == len({r.wave for r in eng.records})
    assert all(eng.batcher.bucket(len(f)) == len(f) for f in flags)
    want = {}
    for r in eng.records:
        want[r.wave] = want.get(r.wave, False) or bool(r.refreshed)
    assert gate == want
    if strategy == "toploc":
        # first turns open the gate, follow-ups alone never do
        assert set(gate.values()) == {False, True}
    if cache:
        assert eng.cache_stats()["hits"] > 0


def test_overflow_is_counted_not_raised(monkeypatch):
    monkeypatch.setattr(telemetry, "CAPACITY", 2)
    tel = Telemetry()
    for w in range(4):
        with tel.span("wave.launch", w):
            pass
    assert [s.wave for s in tel.spans()] == [0, 1]
    assert tel.dropped == 2


def test_a_span_ends_when_its_block_raises():
    tel = Telemetry()
    with pytest.raises(ValueError):
        with tel.span("wave.launch", 0):
            with tel.span("wave.step", 0):
                raise ValueError("step failed")
    step, launch = tel.spans()
    assert (step.name, launch.name) == ("wave.step", "wave.launch")
    assert step.parent == launch.id and launch.parent == -1
    with tel.span("pump.drain"):
        pass
    assert tel.spans()[-1].parent == -1
