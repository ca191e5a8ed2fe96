"""The serving loop's spans against the device trace.

With ``repro.serving.telemetry`` on, every span of the serving loop is
kept twice: in memory on ``time.perf_counter_ns``, and in the profiler's
host plane as a ``TraceAnnotation`` (the bare span name, its ``wave`` as
a stat) on the trace's clock.  This module

* maps the one clock onto the other (``clock``): each in-memory
  ``wave.launch`` is matched to the annotation of the same name and
  wave, and the offset is the median of (trace start - in-memory start);
* puts each idle gap of the device down to the innermost span that
  covers its midpoint, or to ``outside program spans`` (``idle_table``);
* reads the serving loop's per-layer numbers (the readers below).

The readers take an ``Observations`` and read what it carries beside
the harness's own fields: ``records`` (``TurnRecord.wave``), and where a
run kept them, ``spans`` (the window's ``telemetry.Span`` list) and
``idle`` (``idle_table`` of the traced part).  Each returns None where
there is nothing to read.

No reader here takes the device time of the refresh gate's centroid
scan: on the TPU the trace's operations carry only their HLO text and
device times as stats (``ProfileData`` shows no name stack), so no
operation can be put under a program scope.
"""
from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from chipbench import tracing

#: a leaf span at least this long is a stop of the serving loop
#: (``harness.StallWatch.STALL_S``)
STOP_NS = 100_000_000
OUTSIDE = "outside program spans"
SHORT = "gaps under 2 us"

Note = Tuple[str, int, float, float]     # name, wave, start ns, end ns


# -- reading the trace ---------------------------------------------------


def _stat(event, name):
    for k, v in event.stats:
        if k == name:
            return v
    return None


def annotations(path: str, names: Sequence[str]) -> List[Note]:
    """The host plane's annotations named in ``names`` that carry a
    ``wave`` stat, as (name, wave, start_ns, end_ns)."""
    from jax.profiler import ProfileData
    want = set(names)
    out: List[Note] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in want:
                    w = _stat(e, "wave")
                    if w is not None:
                        out.append((e.name, int(w), float(e.start_ns),
                                    float(e.start_ns + e.duration_ns)))
    return out


# -- the two clocks ------------------------------------------------------


def clock(spans: Sequence, notes: Sequence[Note],
          name: str = "wave.launch") -> Optional[Dict]:
    """Offset (ns) from ``perf_counter_ns`` to the trace's clock, from
    the spans called ``name`` matched to their annotations by wave.

    Returns None where nothing matched; else ``offset_ns``, the spread
    of the differences (``iqr_ns``, ``range_ns``), ``matched`` and the
    share of the in-memory spans inside the traced part that matched
    (``in_window``, ``matched_in_window``)."""
    trace_at = {(n, w): s for n, w, s, _ in notes if n == name}
    if not trace_at:
        return None
    ours = [s for s in spans if s.name == name]
    diffs = [trace_at[(s.name, s.wave)] - s.start_ns for s in ours
             if (s.name, s.wave) in trace_at]
    if not diffs:
        return None
    offset = statistics.median(diffs)
    lo = min(s for _, _, s, _ in notes)
    hi = max(e for _, _, _, e in notes)
    inside = [s for s in ours
              if lo <= s.start_ns + offset and s.end_ns + offset <= hi]
    matched = sum((s.name, s.wave) in trace_at for s in inside)
    q = (statistics.quantiles(diffs, n=4) if len(diffs) > 1
         else [diffs[0]] * 3)
    return {"offset_ns": offset, "iqr_ns": q[2] - q[0],
            "range_ns": max(diffs) - min(diffs), "matched": len(diffs),
            "in_window": len(inside), "matched_in_window": matched}


# -- idle gaps -----------------------------------------------------------


def idle_table(ops: Sequence[tracing.Event], spans: Sequence,
               offset_ns: float,
               short_ns: float = tracing.SHORT_GAP_NS) -> Dict:
    """The device's idle gaps between its operations (``ops``, trace
    clock), each of ``short_ns`` or more put down to the innermost span
    covering its midpoint (the shortest one; spans of one thread nest).

    Returns ``idle_s`` (every gap), ``by_span`` (seconds per span name,
    ``OUTSIDE`` and ``SHORT``) and ``launch_s`` (gaps whose midpoint
    lies inside a ``wave.launch``)."""
    busy = tracing.union([(s, s + d) for _, s, d in ops])
    if len(busy) < 2:
        return {"idle_s": 0.0, "by_span": {}, "launch_s": 0.0}
    lo, hi = busy[0][0] - offset_ns, busy[-1][1] - offset_ns
    near = [s for s in spans if s.end_ns >= lo and s.start_ns <= hi]
    start = np.array([s.start_ns for s in near], float) + offset_ns
    end = np.array([s.end_ns for s in near], float) + offset_ns
    launch = np.array([s.name == "wave.launch" for s in near], bool)
    by: Dict[str, float] = {}
    idle = launch_s = 0.0
    for (_, e0), (s1, _) in zip(busy, busy[1:]):
        gap = (s1 - e0) * 1e-9
        idle += gap
        if s1 - e0 < short_ns:
            label = SHORT
        else:
            mid = (e0 + s1) / 2
            cover = (start <= mid) & (end >= mid)
            if cover.any():
                i = int(np.argmin(np.where(cover, end - start, np.inf)))
                label = near[i].name
                if (cover & launch).any():
                    launch_s += gap
            else:
                label = OUTSIDE
        by[label] = by.get(label, 0.0) + gap
    return {"idle_s": idle, "by_span": by, "launch_s": launch_s}


# -- what the spans say --------------------------------------------------


def stops(spans: Sequence, min_ns: float = STOP_NS) -> List:
    """Leaf spans (no span names them as parent) lasting ``min_ns`` or
    more, in time order: the serving loop stopped inside them."""
    parents = {s.parent for s in spans}
    return sorted((s for s in spans if s.id not in parents
                   and s.end_ns - s.start_ns >= min_ns),
                  key=lambda s: s.start_ns)


def durations_ms(spans: Sequence, name: str) -> List[float]:
    return [(s.end_ns - s.start_ns) * 1e-6 for s in spans if s.name == name]


def table(spans: Sequence) -> List[Tuple[str, int, float, float, float]]:
    """(name, count, median ms, p95 ms, total s) per span name."""
    out = []
    for name in sorted({s.name for s in spans}):
        d = np.array(durations_ms(spans, name))
        out.append((name, len(d), float(np.median(d)),
                    float(np.percentile(d, 95)), float(d.sum() * 1e-3)))
    return out


# -- readers ---------------------------------------------------------------


def _median_span(obs, name: str) -> Optional[float]:
    d = durations_ms(getattr(obs, "spans", None) or [], name)
    return float(np.median(d)) if d else None


def launch_host_ms(obs) -> Optional[float]:
    """Median over the window's waves of ``wave.launch``, ms."""
    return _median_span(obs, "wave.launch")


def fetch_wait_ms(obs) -> Optional[float]:
    """Median over the window's waves of ``wave.fetch``: the pump
    blocked on the device's results, ms."""
    return _median_span(obs, "wave.fetch")


def pump_stop_ms(obs) -> Optional[float]:
    """Sum over the window of the leaf spans lasting ``STOP_NS`` or
    more, ms."""
    spans = getattr(obs, "spans", None)
    if not spans:
        return None
    return float(sum(s.end_ns - s.start_ns for s in stops(spans)) * 1e-6)


def gate_open_share(obs) -> Optional[float]:
    """Waves whose refresh gate opened over the window's waves, %: a
    wave's gate is open where any of its turns refreshed (its pad rows
    never do; a result-cache hit zeroes its row's flag, but a hit is
    never a first turn).  Each replica numbers its own waves, so a wave
    is its replica (``obs.replica``) and its id."""
    gates: Dict[Tuple[int, int], bool] = {}
    for k, r in zip(obs.replica, obs.records):
        w = getattr(r, "wave", -1)
        if w >= 0:
            gates[k, w] = gates.get((k, w), False) or bool(r.refreshed)
    return 100.0 * float(np.mean(list(gates.values()))) if gates else None


def idle_launch_share(obs) -> Optional[float]:
    """Share of the traced part's device idle time in gaps inside a
    ``wave.launch``: the device waiting on the host's launch, %."""
    idle = getattr(obs, "idle", None)
    if not idle or idle["idle_s"] <= 0:
        return None
    return 100.0 * idle["launch_s"] / idle["idle_s"]
