"""Device trace: capture, and the reduction from trace to numbers.

A traced run records the JAX profiler for a few seconds in the middle of
the window (host TraceMe events, no Python function tracer, so the host
runs nearly as it does untraced).  ``events`` turns the ``.xplane.pb``
into plain lists of ``(name, start_ns, duration_ns)`` per line, and
``reduce`` does the rest on those lists alone, so the tests check it on
a small recorded trace.

Numbers taken from a trace:

* busy seconds: the union of the intervals in which an XLA operation ran
  on the device; the idle share is one minus busy over the traced window;
* per-program device time: the executions of each jitted module (the
  ``XLA Modules`` line), by module name without its id suffix;
* per-kernel device time: the operations whose name contains a given
  kernel name (a Pallas call keeps its kernel's name);
* the breakdown: the device operations that took most time, and the idle
  gaps by the host event that covered them.

A cell on several chips has a device plane per chip: each is reduced on
its own, against the one host plane, and the first chip's numbers stand
at the top level as a one-chip cell's do.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import threading
from typing import Dict, Iterator, List, Optional, Tuple

Event = Tuple[str, float, float]            # name, start ns, duration ns

OPS_LINE = "XLA Ops"
OP_NAME_CHARS = 160
MODULES_LINE = "XLA Modules"


@contextlib.contextmanager
def capture(log_dir: str) -> Iterator[None]:
    """Profile the enclosed block into ``log_dir``."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class Background:
    """A capture of ``seconds`` on a thread of its own, so that the
    sender's schedule is not held up while the profiler starts or
    writes."""

    def __init__(self, log_dir: str, seconds: float):
        self.log_dir = log_dir
        self.seconds = seconds
        self.error: Optional[BaseException] = None
        self.opened = 0.0              # host clock once the capture runs
        self._t = threading.Thread(target=self._run, name="trace",
                                   daemon=True)

    def _run(self) -> None:
        import time
        try:
            with capture(self.log_dir):
                self.opened = time.perf_counter()
                time.sleep(self.seconds)
        except BaseException as e:   # noqa: BLE001  re-raised by join()
            self.error = e

    def start(self) -> None:
        self._t.start()

    def join(self) -> None:
        self._t.join()
        if self.error is not None:
            raise self.error


def xplane_path(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


DEVICE_PLANE = "/device:TPU:"


def _lines(plane) -> Dict[str, List[Event]]:
    out: Dict[str, List[Event]] = {}
    for line in plane.lines:
        out.setdefault(line.name, []).extend(
            (e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events)
    return out


def _device_id(name: str) -> int:
    digits = re.match(r"\d+", name[len(DEVICE_PLANE):])
    return int(digits.group()) if digits else 0


def events(path: str, chips: int = 1) -> Dict:
    """``{"chips": [{line: events}, ...], "host": {line: events}}``: the
    first ``chips`` TPUs' planes, by device id, and the host's plane."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices = [p for p in pd.planes if p.name.startswith(DEVICE_PLANE)]
    host = [p for p in pd.planes if p.name == "/host:CPU"]
    devices.sort(key=lambda p: _device_id(p.name))
    planes = [_lines(p) for p in devices[:max(chips, 1)]]
    return {"chips": planes or [{}],
            "host": _lines(host[0]) if host else {}}


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def module_name(name: str) -> str:
    """``jit_step_batch(1234)`` -> ``jit_step_batch``."""
    return re.sub(r"\(\d+\)$", "", name)


class _HostCover:
    """Names the shortest host event spanning a time."""

    def __init__(self, host: Dict[str, List[Event]]):
        import numpy as np
        evs = [e for line in host.values() for e in line if e[2] > 0]
        self.names = [e[0] for e in evs]
        self.start = np.array([e[1] for e in evs], float)
        self.dur = np.array([e[2] for e in evs], float)

    def __call__(self, t: float) -> str:
        import numpy as np
        if not self.names:
            return "no host event"
        cover = (self.start <= t) & (self.start + self.dur >= t)
        if not cover.any():
            return "no host event"
        i = int(np.argmin(np.where(cover, self.dur, np.inf)))
        return self.names[i]


#: idle gaps shorter than this are the device's own spacing between
#: operations of one program, not something the host did
SHORT_GAP_NS = 2_000.0


def span(ev: Dict) -> Tuple[float, float]:
    """(first start, last end) in ns over every event of the device's
    and the host's planes: the window the profiler recorded."""
    evs = [e for kind in ("device", "host") for line in ev[kind].values()
           for e in line]
    return (min(s for _, s, _ in evs), max(s + d for _, s, d in evs))


def reduce(ev: Dict, kernels: Tuple[str, ...] = ()) -> Dict:
    """Numbers of one traced window: the first chip's, and under
    ``"chips"`` one entry per chip, each its plane's numbers."""
    host = ev.get("host", {})
    chips = [_reduce_chip({"device": d, "host": host}, kernels)
             for d in ev["chips"]]
    return dict(chips[0], chips=chips)


def _reduce_chip(ev: Dict[str, Dict[str, List[Event]]],
                 kernels: Tuple[str, ...]) -> Dict:
    """Numbers of one chip's plane, against the host's."""
    dev = ev["device"]
    ops = dev.get(OPS_LINE) or []
    if not ops:
        raise ValueError(f"the trace has no device events on {OPS_LINE!r}")
    t0, t1 = span(ev)
    window_s = (t1 - t0) * 1e-9
    busy = union([(s, s + d) for _, s, d in ops])
    busy_s = sum(e - s for s, e in busy) * 1e-9

    modules: Dict[str, List[float]] = {}
    for name, _, d in dev.get(MODULES_LINE, []):
        modules.setdefault(module_name(name), []).append(d * 1e-9)

    by_op: Dict[str, float] = {}
    for name, _, d in ops:
        # an operation's HLO text, cut to its name, shape and operands
        name = name[:OP_NAME_CHARS]
        by_op[name] = by_op.get(name, 0.0) + d * 1e-9
    kernel_s = {k: sum(v for n, v in by_op.items() if k in n)
                for k in kernels}

    gaps: Dict[str, float] = {}
    cover = _HostCover(ev.get("host", {}))
    for (_, e0), (s1, _) in zip(busy, busy[1:]):
        label = (cover((e0 + s1) / 2) if s1 - e0 >= SHORT_GAP_NS
                 else "gaps under 2 us")
        gaps[label] = gaps.get(label, 0.0) + (s1 - e0) * 1e-9
    top = lambda d: [[n, v] for n, v in sorted(d.items(),
                                              key=lambda x: -x[1])[:10]]
    return {"busy_s": busy_s, "window_s": window_s, "start_ns": t0,
            "modules": modules,
            "kernel_s": kernel_s,
            "breakdown": {"device_ops": top(by_op), "idle_gaps": top(gaps)}}
