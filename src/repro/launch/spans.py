"""The program's spans: named host intervals, kept in a bounded in-memory
ring and written into the profiler's trace while one runs.

    with spans.span("engine.admit") as sp:      # sp.t0, sp.attrs
        ...
        sp.attrs["rows"] = 3
    spans.mark("engine.queue", t_submit, t_round, rid=7)
    spans.between(t0, t1)     # the records inside [t0, t1], or None

A span enters ``jax.profiler.TraceAnnotation(name)``, so it lands on the
profiler's host plane on the same clock as the device's operations, and
appends a :class:`Span` to the ring when it ends.  Times are
``time.perf_counter`` seconds.  ``parent`` is the name of the span that
encloses it on the same thread.  The ring is always on and holds the
newest :data:`CAPACITY` records; ``between`` answers ``None`` once the
ring has dropped a record that might lie in the interval asked for, so a
reader never takes a partial window for a whole one.

Span data is diagnostics only: it never enters a snapshot, the journal
or an artifact.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

#: Records the ring keeps: some minutes of a busy serving loop.
CAPACITY = 65_536

#: Every span name the program records (a trace reducer names the
#: device's idle gaps by them).
NAMES = (
    "engine.queue",      # submit (or requeue) -> the round that admits it
    "engine.admit",      # one admission round
    "engine.match",      #   its slot loop: prefix match, reservation, pages
    "engine.tables",     #   each page-table upload to the device
    "engine.prefill",    #   first device dispatch -> next tokens on the host
                         #   (a MoE stack's also: picks_here, expert_rows)
    "engine.step",       # one decode step
    "engine.grow",       #   page growth before the decode dispatch
    "engine.decode",     #   decode dispatch -> next tokens on the host
                         #   (picks_here, expert_rows as for prefill)
    "engine.finish",     #   one finished slot
    "engine.snapshot",   # the engine state pulled to the host
)


class Span(NamedTuple):
    name: str
    t0: float
    t1: float
    parent: Optional[str]
    rid: Optional[int]
    attrs: dict


class Ring:
    """The newest ``capacity`` spans, and how far back they still reach."""

    def __init__(self, capacity: int):
        self._spans: deque = deque(maxlen=capacity)
        self._lost_t1 = float("-inf")        # latest end among dropped
        self._lock = threading.Lock()

    def add(self, rec: Span) -> None:
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                self._lost_t1 = max(self._lost_t1, self._spans[0].t1)
            self._spans.append(rec)

    def between(self, t0: float, t1: float) -> Optional[List[Span]]:
        """The spans inside ``[t0, t1]`` in the order they ended, or None
        if a dropped span may have been among them."""
        with self._lock:
            if self._lost_t1 >= t0:
                return None
            return [s for s in self._spans if t0 <= s.t0 and s.t1 <= t1]


_ring = Ring(CAPACITY)
_local = threading.local()


def _stack() -> list:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


class _Open:
    __slots__ = ("name", "rid", "attrs", "t0", "_parent", "_ann")

    def __init__(self, name: str, rid: Optional[int], attrs: dict):
        self.name, self.rid, self.attrs = name, rid, attrs

    def __enter__(self) -> "_Open":
        stack = _stack()
        self._parent = stack[-1] if stack else None
        stack.append(self.name)
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        _stack().pop()
        _ring.add(Span(self.name, self.t0, t1, self._parent, self.rid,
                       self.attrs))


def span(name: str, rid: Optional[int] = None, **attrs) -> _Open:
    """A context manager that records one span; it yields itself, whose
    ``t0`` is the start and whose ``attrs`` may still be filled in."""
    return _Open(name, rid, attrs)


def mark(name: str, t0: float, t1: float, rid: Optional[int] = None,
         **attrs) -> None:
    """Record a span whose start was taken earlier (a queue wait)."""
    stack = _stack()
    _ring.add(Span(name, t0, t1, stack[-1] if stack else None, rid, attrs))


def between(t0: float, t1: float) -> Optional[List[Span]]:
    """The recorded spans inside ``[t0, t1]``, or None if the ring no
    longer reaches back to ``t0``."""
    return _ring.between(t0, t1)
