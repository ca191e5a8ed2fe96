#!/usr/bin/env python3
"""Run one cell as ``run.py`` does, with the serving loop's telemetry on.

    python3 chipbench/spanrun.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The engine's ``repro.serving.telemetry`` is turned on at the window's
opening and off at its close; everything else is ``run.py``'s run,
whose result line is printed as it is.  Then standard error gets the
spans by name, the stops (``spans.stops``), and with ``--trace 1`` the
clock mapping and the idle gaps by span; the last line of standard output is one JSON
object: the window's end-to-end numbers (``window``), the serving
loop's readings (``spans``: the readers of ``chipbench/spans.py``) and
the clock mapping (``clock``).

``harness.run_cell`` keeps no spans itself: this wraps its ``drive``,
``window.summary`` and ``tracing.events`` to see the window, its
numbers and its trace.  Should the harness stop calling them through
those modules, the run keeps no telemetry and this exits 1 after
``run.py``'s line.  It goes once the harness keeps spans itself.
"""
from __future__ import annotations

import time

PROC_START = time.perf_counter()

import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402
from typing import Dict, Iterator  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE),
                                                     "src")]


#: the readers of ``chipbench/spans.py`` whose numbers are printed
READERS = ["launch_host_ms", "fetch_wait_ms", "pump_stop_ms",
           "gate_open_share", "idle_launch_share"]


def log(msg: str) -> None:
    print(f"[spanrun] {msg}", file=sys.stderr, flush=True)


@contextlib.contextmanager
def watch() -> Iterator[Dict]:
    """Wrap the harness so that its next run keeps the window's
    telemetry, records, end-to-end numbers and trace in the dict
    yielded; the wrappers come off at the exit."""
    from chipbench import harness, tracing, window
    from repro.serving.telemetry import Telemetry
    seen: Dict = {}
    drive, summary, events = harness.drive, window.summary, tracing.events

    def traced_drive(router, sched, queries, seconds, on_open):
        tel = Telemetry()

        def opened(t0):
            router.set_telemetry(tel)
            on_open(t0)
        try:
            return drive(router, sched, queries, seconds, opened)
        finally:
            router.set_telemetry(None)
            seen.update(tel=tel, records=router.engines[0].records)

    def kept_summary(*a, **kw):
        seen["window"] = summary(*a, **kw)
        return seen["window"]

    def kept_events(path):
        seen["path"] = path
        seen["ev"] = events(path)
        return seen["ev"]

    harness.drive, window.summary = traced_drive, kept_summary
    tracing.events = kept_events
    try:
        yield seen
    finally:
        harness.drive, window.summary = drive, summary
        tracing.events = events


def report(seen: Dict) -> Dict:
    """Log the tables of one watched run; returns the result object."""
    from chipbench import spans, tracing
    tel = seen["tel"]
    got = tel.spans()
    log(f"{len(got)} spans, {tel.dropped} dropped")
    for name, n, med, p95, tot in spans.table(got):
        log(f"span {name:14s} n={n:6d} median {med:9.3f} ms  p95 "
            f"{p95:9.3f} ms  total {tot:8.3f} s")
    t_open = min((s.start_ns for s in got), default=0)
    for s in spans.stops(got):
        log(f"stop: {s.name} wave {s.wave} "
            f"{(s.end_ns - s.start_ns) * 1e-6:.3f} ms at "
            f"{(s.start_ns - t_open) * 1e-9:.3f}s")
    waves = {s.wave for s in got if s.name == "wave.launch"}
    records = [r for r in seen["records"] if r.wave in waves]
    obs = SimpleNamespace(spans=got, records=records, idle=None)
    mapped = None
    if "ev" in seen:
        ev = seen["ev"]
        notes = spans.annotations(seen["path"], ["wave.launch"])
        mapped = spans.clock(got, notes)
        log(f"clock: {mapped}")
        if mapped is not None:
            obs.idle = spans.idle_table(ev["device"][tracing.OPS_LINE], got,
                                        mapped["offset_ns"])
            for name, v in sorted(obs.idle["by_span"].items(),
                                  key=lambda x: -x[1]):
                log(f"idle {name:22s} {v * 1e3:9.3f} ms "
                    f"({100 * v / obs.idle['idle_s']:.2f}%)")
    return {"window": seen.get("window"),
            "spans": {n: getattr(spans, n)(obs) for n in READERS},
            "clock": mapped}


def main(argv=None) -> int:
    from chipbench import run
    run.PROC_START = PROC_START
    with watch() as seen:
        rc = run.main(argv)
    if rc != 0:
        return rc
    if "tel" not in seen:
        log("the run kept no telemetry: the harness no longer calls "
            "harness.drive")
        return 1
    print(json.dumps(report(seen)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
