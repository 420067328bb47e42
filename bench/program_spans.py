"""The measured window's spans from the program's own recorder
(``repro.launch.spans``), for the per-layer metrics that read them.

The window holds what the other metrics count:

* rounds: ``engine.admit`` spans that admitted (``rows > 0``) and began
  at or after the window's open, as ``admit_ms`` counts them, each with
  the ``engine.prefill`` spans inside it;
* steps: ``engine.step`` spans that began at or after the open, as
  ``decode_step_ms`` counts them, each with the ``engine.decode`` spans
  inside it;
* queue: the ``engine.queue`` spans of the requests due in the window,
  as ``ttft_p50_ms`` counts them.

It ends with the last of the driver's ``run.steps``.  The driver keeps
no time of its window's open, and the first round may come before the
first step; the open is given back by any due request with a first
token, whose TTFT is that token's time less the open and its due
offset.

``window`` is None where the program records no spans (one without
``repro.launch.spans``), or where its ring no longer reaches back to the
open: a reader never takes part of the window for the whole.
"""
from __future__ import annotations

import bisect
from dataclasses import dataclass


@dataclass
class Window:
    rounds: list      # (engine.admit span, [its engine.prefill spans])
    steps: list       # (engine.step span, [its engine.decode spans])
    queue: list       # engine.queue spans of the window's requests


def _due(run) -> list:
    return [s for s in run.state.get("seen", ())
            if 0 <= s.req.due < run.seconds]


def window_open(run):
    """The window's open on ``time.perf_counter``'s clock, or None."""
    for s, ttft in zip(_due(run), run.state.get("ttft", ())):
        if s.t_first is not None:
            return s.t_first - ttft - s.req.due
    return None


def _with_kids(parents: list, kids: list) -> list:
    """Each parent span with the spans of ``kids`` that lie inside it."""
    kids = sorted(kids, key=lambda s: s.t0)
    starts = [k.t0 for k in kids]
    out = []
    for p in sorted(parents, key=lambda s: s.t0):
        lo = bisect.bisect_left(starts, p.t0)
        hi = bisect.bisect_right(starts, p.t1)
        out.append((p, [k for k in kids[lo:hi] if k.t1 <= p.t1]))
    return out


def window(run):
    """The window's spans (a :class:`Window`), or None."""
    try:
        from repro.launch import spans
    except ImportError:
        return None
    t_open = window_open(run)
    if t_open is None or not run.steps:
        return None
    recs = spans.between(t_open, run.steps[-1][1])
    if recs is None:
        return None
    by = {}
    for s in recs:
        by.setdefault(s.name, []).append(s)
    due = {s.req.rid for s in _due(run)}
    return Window(
        rounds=_with_kids([s for s in by.get("engine.admit", ())
                           if s.attrs.get("rows")],
                          by.get("engine.prefill", [])),
        steps=_with_kids(by.get("engine.step", []),
                         by.get("engine.decode", [])),
        queue=[s for s in by.get("engine.queue", ()) if s.rid in due])


def dur(spans) -> float:
    """Seconds the spans cover, summed."""
    return sum(s.t1 - s.t0 for s in spans)
