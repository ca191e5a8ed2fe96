"""The serving loop's spans against the device trace (chipbench/spans.py)
and the run that keeps them (chipbench/spanrun.py)."""
import pathlib
import sys
import time
from types import SimpleNamespace as NS

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))
from chipbench import harness, registry, spanrun, spans, tracing  # noqa: E402
from chipbench.harness import Observations  # noqa: E402
from repro.serving.telemetry import Span  # noqa: E402

MS = 1_000_000


def span(i, name, wave, start_ms, end_ms, parent=-1):
    return Span(i, name, wave, parent, "pump", int(start_ms * MS),
                int(end_ms * MS))


def obs(records=(), trace=None):
    return Observations({"p": 16384, "d": 768}, list(records),
                        [0] * len(records), [], [], 0.0, {}, trace)


def test_the_clock_offset_is_recovered_from_a_shifted_trace():
    shift = 5_123_456_789
    ours = [span(w, "wave.launch", w, 40 * w, 40 * w + 3) for w in range(20)]
    jitter = [0, 300, -200, 100, 0, -100, 200, 0, 50, -50] * 2
    # the trace holds waves 5-16; wave 9's annotation is lost
    notes = [("wave.launch", s.wave, s.start_ns + shift + jitter[s.wave],
              s.end_ns + shift) for s in ours if 5 <= s.wave <= 16
             and s.wave != 9]
    notes.append(("pump.drain", -1, notes[0][2] - 7 * MS,
                  notes[0][2] - 1 * MS))
    got = spans.clock(ours, notes)
    assert got["offset_ns"] == pytest.approx(shift, abs=100)
    assert got["matched"] == 11
    assert got["in_window"] == 12 and got["matched_in_window"] == 11
    assert got["range_ns"] == 500 and 0 <= got["iqr_ns"] <= 500


def test_nothing_to_match_gives_no_clock():
    ours = [span(0, "wave.launch", 0, 0, 1)]
    assert spans.clock(ours, []) is None
    assert spans.clock(ours, [("wave.launch", 7, 0.0, 1.0)]) is None


def test_idle_gaps_go_to_the_innermost_span():
    off = 1000 * MS
    # in-memory clock: a launch (10-20 ms) holding a gather (12-15 ms),
    # a retire (22-30 ms); trace clock = in-memory + 1000 ms
    ours = [span(1, "store.gather", 0, 12, 15, parent=0),
            span(0, "wave.launch", 0, 10, 20),
            span(2, "batch.retire", -1, 22, 30)]
    t = lambda ms: off + ms * MS
    ops = [("a", t(0), 13 * MS),                 # busy 0-13
           ("b", t(14), 3 * MS),                 # gap 13-14: gather
           ("c", t(18), 1 * MS),                 # gap 17-18: the launch
           ("d", t(19) + 1000, 1 * MS),          # 1 us gap: short
           ("e", t(24), 1 * MS),                 # gap 20-24, mid 22: retire
           ("f", t(40), 1 * MS)]                 # gap 25-40, mid 32.5: none
    got = spans.idle_table(ops, ours, off)
    by = got["by_span"]
    assert by["store.gather"] == pytest.approx(0.001)
    assert by["wave.launch"] == pytest.approx(0.001)
    assert by[spans.SHORT] == pytest.approx(1e-6)
    assert by["batch.retire"] == pytest.approx(0.004 - 1e-6)
    assert by[spans.OUTSIDE] == pytest.approx(0.015)
    assert got["idle_s"] == pytest.approx(sum(by.values()))
    assert got["launch_s"] == pytest.approx(0.002)


def test_stops_are_long_leaf_spans():
    ours = [span(1, "store.gather", 0, 0, 120, parent=0),
            span(0, "wave.launch", 0, 0, 150),
            span(2, "pump.drain", -1, 200, 299),
            span(3, "wave.fetch", 1, 300, 400, parent=4),
            span(4, "batch.retire", -1, 300, 402)]
    assert [s.name for s in spans.stops(ours)] == ["store.gather",
                                                   "wave.fetch"]


def rec(wave, refreshed):
    return NS(wave=wave, refreshed=refreshed)


def test_the_readers_read_what_a_run_kept():
    o = obs([rec(0, True), rec(0, False), rec(1, False), rec(2, False),
             rec(2, True), rec(3, True)])
    o.spans = [span(0, "wave.launch", 0, 0, 2),
               span(1, "wave.launch", 1, 10, 14),
               span(2, "wave.launch", 2, 20, 23),
               span(3, "wave.fetch", 0, 30, 50, parent=5),
               span(4, "wave.fetch", 1, 60, 70, parent=6),
               span(5, "batch.retire", -1, 30, 51),
               span(6, "batch.retire", -1, 60, 71),
               span(7, "pump.drain", -1, 100, 250)]
    o.idle = {"idle_s": 0.4, "by_span": {}, "launch_s": 0.1}
    read = lambda name: getattr(spans, name)(o)
    assert read("launch_host_ms") == pytest.approx(3.0)
    assert read("fetch_wait_ms") == pytest.approx(15.0)
    assert read("pump_stop_ms") == pytest.approx(150.0)
    assert read("gate_open_share") == pytest.approx(75.0)
    assert read("idle_launch_share") == pytest.approx(25.0)


def test_the_gate_metric_file_reads_the_records():
    o = obs([rec(0, True), rec(1, False), rec(1, False)])
    assert registry.reader("gate_open_share")(o) == pytest.approx(50.0)


def test_each_replica_numbers_its_own_waves():
    # wave 0 of replica 0 opened its gate, wave 0 of replica 1 did not:
    # two waves, one open, not one wave
    o = obs([rec(0, True), rec(0, False), rec(1, False), rec(1, False)])
    o.replica = [0, 1, 0, 1]
    assert registry.reader("gate_open_share")(o) == pytest.approx(25.0)
    o.replica = [0] * 4
    assert registry.reader("gate_open_share")(o) == pytest.approx(50.0)


@pytest.mark.parametrize("name", spanrun.READERS)
def test_nothing_to_read_gives_nothing(name):
    # the harness's own observations, of a program whose records carry
    # no wave
    o = obs([NS(refreshed=True, conv_id="c", turn=0)],
            trace={"modules": {}})
    assert getattr(spans, name)(o) is None
    assert getattr(spans, name)(obs()) is None


def test_spans_and_annotations_share_a_clock(small_corpus, ivf_index,
                                              tmp_path):
    """A real profiler trace on the CPU: every wave's annotation is
    found and matched, and the two clocks agree to well under a
    millisecond."""
    from repro.serving import (BatchedConversationalSearchEngine,
                               ServingConfig, Telemetry)
    eng = BatchedConversationalSearchEngine(
        ServingConfig(backend="ivf", strategy="toploc+", nprobe=4, h=16,
                      k=10), ivf_index=ivf_index, n_slots=8, max_batch=4,
        max_wait_s=0.0)
    convs = np.asarray(small_corpus.conversations, np.float32)
    serve = lambda: [eng.submit(f"c{c}", convs[c, t]) for t in range(3)
                     for c in range(4)] and eng.drain()
    serve()                                    # compiles
    tel = Telemetry()
    eng.set_telemetry(tel)
    with tracing.capture(str(tmp_path)):
        serve()
    notes = spans.annotations(tracing.xplane_path(str(tmp_path)),
                              ["wave.launch", "wave.fetch"])
    launches = [s for s in tel.spans() if s.name == "wave.launch"]
    assert sorted(w for n, w, _, _ in notes if n == "wave.launch") == \
        sorted(s.wave for s in launches)
    got = spans.clock(tel.spans(), notes)
    assert got["matched"] == len(launches) > 0
    assert got["matched_in_window"] == got["in_window"] == len(launches)
    assert got["range_ns"] < 1_000_000


def test_a_watched_run_keeps_its_spans():
    """The harness at a tiny size on the CPU, untraced, watched: the
    window's spans and the records of its waves are kept."""
    tiny = dict(n_docs=1 << 12, n_topics=32, p=32, h=16, nprobe=4,
                n_slots=64, max_batch=8, kmeans_iters=3, check_turns=16)
    cfg = registry.config("cast-ivf-f32")
    cfg.update(tiny)
    traffic = {"rate_turns_per_s": 120, "turns_min": 2, "turns_max": 6,
               "live_population": 24, "shift_prob": 0.1}
    drive = harness.drive
    with spanrun.watch() as seen:
        assert harness.drive is not drive
        out = harness.run_cell({"name": "tiny"}, cfg, traffic, seed=2 ** 33,
                               seconds=1.0, trace=False, end_to_end=[],
                               per_layer=[], out_dir="unused",
                               proc_start=time.perf_counter())
    assert harness.drive is drive and "tel" in seen
    assert out["failed"] == 0
    got = spanrun.report(seen)
    assert got["window"]["turns_per_s"] > 0
    s = got["spans"]
    assert s["launch_host_ms"] > 0 and s["fetch_wait_ms"] >= 0
    assert s["pump_stop_ms"] >= 0
    assert 0 < s["gate_open_share"] <= 100
    assert s["idle_launch_share"] is None and got["clock"] is None
    threads = {sp.thread for sp in seen["tel"].spans()}
    assert threads == {"replica-pump-0"}


def test_a_run_that_kept_no_telemetry_fails(monkeypatch, capsys):
    """Should the harness stop calling ``harness.drive``, the run keeps
    no spans; ``spanrun`` then exits 1 rather than print nothing."""
    from chipbench import run
    monkeypatch.setattr(run, "PROC_START", run.PROC_START)
    monkeypatch.setattr(run, "main", lambda argv=None: 0)
    assert spanrun.main([]) == 1
    assert "kept no telemetry" in capsys.readouterr().err
    monkeypatch.setattr(run, "main", lambda argv=None: 3)
    assert spanrun.main([]) == 3
