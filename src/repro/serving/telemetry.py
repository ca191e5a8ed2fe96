"""Spans of the serving loop, kept in memory.

A ``Telemetry`` records what the batched engine's serving loop does, by
phase and by wave:

  * ``span(name, wave)`` — a context manager recording one
    ``Span(id, name, wave, parent, thread, start_ns, end_ns)`` on
    ``time.perf_counter_ns``.  ``parent`` is the id of the span open on
    the same thread when this one opened (-1 at the top).  Each span
    also opens ``jax.profiler.TraceAnnotation(name, wave=wave)``, so a
    profiler trace taken meanwhile holds the same spans, under the bare
    name with ``wave`` as a stat, on the profiler's own clock.

Storage is bounded: past ``CAPACITY`` spans a span is dropped and
``dropped`` counts it; nothing raises.  There is no writer or
exporter: a reader takes ``spans()``.

Off is the default.  Engines hold ``telemetry = None`` and their call
sites go through the module-level ``span(tel, name, wave)``, which then
returns one shared ``nullcontext``: no allocation, no annotation.

Spans named by the serving path (``wave`` is -1 where a span belongs
to no one wave):

  ``pump.drain``      waiting for requests or the batcher's deadline
  ``wave.launch``     host time to enqueue one wave, with children
    ``wave.assemble``   stacking the queries and putting them on the device
    ``store.acquire``   slot lookup and LRU evictions' scatters
    ``store.gather``    the slab's rows for the wave
    ``wave.step``       dispatch of the jitted step
    ``store.scatter``   the donating write-back of the wave's sessions
  ``batch.retire``    retiring one launch, with children
    ``wave.fetch``      blocking reads of a wave's results
    ``wave.records``    the wave's ``TurnRecord``s
    ``batch.resolve``   the launch's futures and their callbacks
  ``pump.sync``       an idle tick retiring what is in flight

A wave's batch-wide refresh gate needs no counter of its own: its
``TurnRecord``s carry the wave id and each row's ``refreshed`` flag.
"""
from __future__ import annotations

import contextlib
import itertools
import threading
import time
from typing import List, NamedTuple, Optional

import jax

from repro.concurrency import guarded_by


class Span(NamedTuple):
    id: int
    name: str
    wave: int
    parent: int         # id of the enclosing span on the thread, or -1
    thread: str
    start_ns: int       # time.perf_counter_ns()
    end_ns: int


#: spans one ``Telemetry`` keeps: about a quarter of an hour of serving
#: at 25 waves a second and 12 spans a wave; the rest count in ``dropped``
CAPACITY = 1 << 18

#: the shared context of every call site while telemetry is off
_OFF = contextlib.nullcontext()


def span(tel: Optional["Telemetry"], name: str, wave: int = -1):
    """``tel.span(name, wave)``, or the shared no-op context for None."""
    return _OFF if tel is None else tel.span(name, wave)


class _Open:
    """One span while it is open (``Telemetry.span``)."""

    __slots__ = ("_tel", "_name", "_wave", "_id", "_parent", "_t0",
                 "_note")

    def __init__(self, tel: "Telemetry", name: str, wave: int):
        self._tel, self._name, self._wave = tel, name, wave

    def __enter__(self) -> "_Open":
        stack = self._tel._stack()
        self._parent = stack[-1] if stack else -1
        self._id = next(self._tel._ids)
        stack.append(self._id)
        self._note = jax.profiler.TraceAnnotation(self._name,
                                                  wave=self._wave)
        self._note.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter_ns()
        self._note.__exit__(*exc)
        self._tel._stack().pop()
        self._tel._add_span(Span(
            self._id, self._name, self._wave, self._parent,
            threading.current_thread().name, self._t0, t1))
        return False


@guarded_by("_lock", "_spans", "_dropped")
class Telemetry:
    """Bounded in-memory spans (module docstring)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._local = threading.local()
        self._ids = itertools.count()
        self._spans: List[Span] = []
        self._dropped = 0

    def _stack(self) -> List[int]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def span(self, name: str, wave: int = -1) -> _Open:
        return _Open(self, name, wave)

    def _add_span(self, s: Span) -> None:
        with self._lock:
            if len(self._spans) < CAPACITY:
                self._spans.append(s)
            else:
                self._dropped += 1

    def spans(self) -> List[Span]:
        """The closed spans so far, in the order they closed."""
        with self._lock:
            return list(self._spans)

    @property
    def dropped(self) -> int:
        """Spans not kept, past ``CAPACITY``."""
        with self._lock:
            return self._dropped
