"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

* busy: the union of the intervals in which an operation ran on a
  device, inside the traced window (the harness's ``window`` span);
* op time by stable name: device self time (an operation's duration
  less that of the operations nested in it) summed per operation, the
  name's numeric suffixes dropped (``fusion.12`` -> ``fusion``);
* idle gaps: each stretch of the window in which no device operation
  ran, named by the innermost harness host span it falls in (``admit``,
  ``step``, ``generator``, ...), or ``other``.

Only ``jax.profiler.ProfileData`` is used.  Device planes are those
named ``/device:...`` that have an ``XLA Ops`` line, which holds the
operations; a plane without one (the runtime's ``Megascale Trace``) is
no chip and is not counted.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

_SUFFIX = re.compile(r"(\.\d+)+$")


def stable_name(name: str) -> str:
    """The operation's name without its numeric suffixes; an event named
    by its HLO text (``%fusion.12 = bf16[...] fusion(...)``) is named by
    the instruction it defines."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


def _self_times(events: list) -> list:
    """(name, self seconds) of each event on one line: its duration less
    that of the events nested inside it (a ``while`` holds its body's
    operations), so that the sum over a line is its busy time."""
    out, stack = [], []                       # stack: [name, end, self_ns]
    for n, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            top = stack.pop()
            out.append((top[0], top[2] * 1e-9))
        if stack:
            stack[-1][2] -= min(b, stack[-1][1]) - a
        stack.append([n, b, b - a])
    out.extend((n, s * 1e-9) for n, _, s in stack)
    return out


@dataclass
class Reduced:
    window_s: float
    busy_s: float
    n_devices: int
    op_s: dict = field(default_factory=dict)          # stable name -> s
    idle_by_span: dict = field(default_factory=dict)  # span name -> s

    def kernel_s(self, pattern: str) -> float:
        """Device seconds of the operations whose name holds ``pattern``."""
        return sum(s for n, s in self.op_s.items() if pattern in n)

    def top_ops(self, n: int = 10) -> list:
        return sorted(([k, v] for k, v in self.op_s.items()),
                      key=lambda kv: -kv[1])[:n]

    def top_gaps(self, n: int = 10) -> list:
        return sorted(([k, v] for k, v in self.idle_by_span.items()),
                      key=lambda kv: -kv[1])[:n]


def find_xplane(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def _union(intervals: list) -> list:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def reduce(path: str, span_names, window_span: str = "window") -> Reduced:
    """Reduce one trace.  ``span_names`` are the harness's host spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans, per_device = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops = [ln for ln in plane.lines if ln.name == "XLA Ops"]
            if not ops:               # not a chip (e.g. a runtime's plane)
                continue
            per_device[plane.name] = [
                (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                for ln in ops for ev in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name in span_names or ev.name == window_span:
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    wins = [s for s in spans if s[0] == window_span]
    if not wins:
        raise ValueError(f"no {window_span!r} span in {path}")
    w0, w1 = wins[0][1], wins[0][2]
    inner = [s for s in spans if s[0] != window_span and s[2] > w0
             and s[1] < w1]

    busy_total, op_s, idle = 0.0, {}, {}
    for dev_events in per_device.values():
        clipped = [(n, max(a, w0), min(b, w1)) for n, a, b in dev_events
                   if b > w0 and a < w1]
        for n, s in _self_times(clipped):
            key = stable_name(n)
            op_s[key] = op_s.get(key, 0.0) + s
        busy = _union([(a, b) for _, a, b in clipped])
        busy_total += sum(b - a for a, b in busy) * 1e-9
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                name = _span_at(inner, (a + b) / 2)
                idle[name] = idle.get(name, 0.0) + (b - a) * 1e-9
    n = max(len(per_device), 1)
    return Reduced(window_s=(w1 - w0) * 1e-9, busy_s=busy_total / n,
                   n_devices=len(per_device),
                   op_s={k: v / n for k, v in op_s.items()},
                   idle_by_span={k: v / n for k, v in idle.items()})


def _span_at(spans: list, t: float) -> str:
    """The innermost (shortest) span covering time ``t``."""
    best, best_len = "other", None
    for name, a, b in spans:
        if a <= t <= b and (best_len is None or b - a < best_len):
            best, best_len = name, b - a
    return best
