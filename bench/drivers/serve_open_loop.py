"""Driver kind ``serve_open_loop``: the program's ``ServingEngine`` under
open-loop traffic from ``bench/traffic_gen.py``.

One host thread runs the engine the way a server does: submit every
request that has come due, run an admission round when a slot is free
and the queue is not empty, then one decode step.  Arrivals begin the
mix's ``preroll_s`` before the window opens, so the window measures the
engine as full as the rate keeps it; only what falls in the window is
counted.  A request is timed from the moment it was due, so the wait a
slow step imposes on later arrivals counts.  Its first token exists
when ``admit`` returns (the prefill's argmax is on the host then), and
every later token when the ``step`` that made it returns.

Set-up warms every prefill bucket the mix can reach (page multiples up
to ``prompt_cap``) and the decode step, through the engine itself, so
nothing compiles in the window.  After the window the engine is freed
and a seeded sample of the finished requests, the longest among them,
is scored against the plain float32 reference.
"""
from __future__ import annotations

import time

import numpy as np

from bench import flops, serving, traffic_gen

# the host spans idle gaps on the device are named by
SPANS = ("admit", "step", "generator")


class Served:
    """What the window saw of one request."""

    __slots__ = ("req", "t_submit", "t_first", "times")

    def __init__(self, req):
        self.req = req
        self.t_submit = None
        self.t_first = None
        self.times = []


def setup(run) -> None:
    mix, cfgj = run.cell.traffic, run.cell.config
    cfg, params, engine = serving.build_engine(run)
    reqs = traffic_gen.build(mix, run.seed, cfgj["vocab_size"], run.seconds)
    run.state = {"cfg": cfg, "params": params, "engine": engine,
                 "reqs": reqs}


def window(run) -> None:
    from repro.launch.engine import Request

    st = run.state
    engine, reqs = st["engine"], st["reqs"]
    ps = run.cell.traffic["page_size"]
    seen = [Served(r) for r in reqs]
    by_id = {s.req.rid: s for s in seen}
    steps, admits = [], []           # in the window: (t0, t1, detail)
    slots_used, pages_used = [], []  # at each decode step of the window
    spans = run.spans
    nxt = 0
    t_open = time.perf_counter() + run.cell.traffic.get("preroll_s", 0)
    t_end = t_open + run.seconds
    base = queued_at_open = None
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        if base is None and now >= t_open:
            # arrivals have run for ``preroll_s``: the window opens here
            base, queued_at_open = _counters(engine), len(engine.queue)
            run.open_window(t_open)
        run.tick(now)
        while nxt < len(seen) and t_open + seen[nxt].req.due <= now:
            s = seen[nxt]
            engine.submit(Request(req=s.req.rid, tokens=s.req.tokens,
                                  gen_len=s.req.gen_len))
            s.t_submit = now
            nxt += 1
        if engine.queue and engine.free_slot_count():
            mark = len(engine.journal)
            with spans.span("admit"):
                t0 = time.perf_counter()
                got = engine.admit()
                t1 = time.perf_counter()
            if got and t0 >= t_open:
                # the round's prefill width: its longest uncached tail,
                # rounded up to a page
                tail = max(len(by_id[e["req"]].req.tokens) - e["cached"]
                           for e in engine.journal[mark:]
                           if e["ev"] == "admit")
                admits.append((t0, t1, (len(got), -(-tail // ps) * ps)))
            for rid in got:
                by_id[rid].t_first = t1
                by_id[rid].times.append(t1)
        active = [(rec.request.req, int(engine.pos[b]))
                  for b, rec in enumerate(engine.slots) if rec is not None]
        if active:
            with spans.span("step"):
                t0 = time.perf_counter()
                engine.step()
                t1 = time.perf_counter()
            if t0 >= t_open:
                steps.append((t0, t1, [p for _, p in active]))
                slots_used.append(len(active))
                pages_used.append(engine.pool.in_use)
            for rid, _ in active:
                by_id[rid].times.append(t1)
        elif nxt < len(seen):
            with spans.span("generator"):
                wait = t_open + seen[nxt].req.due - time.perf_counter()
                if wait > 0:
                    time.sleep(min(wait, t_end - time.perf_counter(), 0.05))
    t_close = time.perf_counter()
    run.close_window(t_close)

    due = [s for s in seen if 0 <= s.req.due < run.seconds]
    ttft = [(s.t_first if s.t_first is not None else t_close)
            - (t_open + s.req.due) for s in due]
    itl = [b - a for s in seen for a, b in zip(s.times, s.times[1:])
           if a >= t_open]
    late = [s.t_submit - (t_open + s.req.due) for s in due
            if s.t_submit is not None]
    counters = {k: v - base[k] for k, v in _counters(engine).items()}
    cfgj = run.cell.config
    step_s = sum(t1 - t0 for t0, t1, _ in steps)
    decode_flops = sum(flops.forward_flops(cfgj, p + 1)
                       for _, _, pos in steps for p in pos)
    counters.update(
        admit_rounds=len(admits), admit_s=sum(t1 - t0 for t0, t1, _ in admits),
        steps=len(steps), step_s=step_s, decode_flops=decode_flops,
        window_s=t_close - t_open, queued_at_open=queued_at_open,
        queued_at_close=len(engine.queue),
        mean_active_slots=float(np.mean(slots_used)) if slots_used else 0.0,
        mean_pages_in_use=float(np.mean(pages_used)) if pages_used else 0.0)
    run.counters.update(counters)
    run.log(f"window {t_close - t_open:.3f} s after a pre-roll of "
            f"{run.cell.traffic.get('preroll_s', 0)} s: {len(due)} requests "
            f"due, {sum(s.t_first is not None for s in due)} with a first "
            f"token, {len(engine.responses)} finished since the pre-roll "
            f"began; {len(steps)} decode steps, {len(admits)} admission "
            f"rounds")
    run.log(f"samples: ttft {len(ttft)}, itl {len(itl)}")
    if ttft:
        run.log("ttft p50/p95/max ms: " + "/".join(
            f"{1e3 * v:.3f}" for v in (np.percentile(ttft, 50),
                                       np.percentile(ttft, 95), max(ttft))))
    if itl:
        run.log("itl p50/p95/p99 ms: " + "/".join(
            f"{1e3 * np.percentile(itl, q):.3f}" for q in (50, 95, 99)))
    if admits:
        t0, t1, (rows, width) = max(admits, key=lambda a: a[1] - a[0])
        run.log(f"longest admission round: {1e3 * (t1 - t0):.3f} ms, "
                f"{rows} admitted, prefill width {width}")
    if steps:
        run.log(f"longest decode step: "
                f"{1e3 * max(t1 - t0 for t0, t1, _ in steps):.3f} ms")
    if late:
        run.log(f"generator lateness: median {1e3 * np.median(late):.3f} ms, "
                f"p95 {1e3 * np.percentile(late, 95):.3f} ms, "
                f"max {1e3 * max(late):.3f} ms")
    run.log("engine counters: " + ", ".join(
        f"{k}={v}" for k, v in counters.items()))
    run.attempted = len(due)
    run.failed = 0
    run.e2e.update(
        ttft_p50_ms=1e3 * float(np.percentile(ttft, 50)) if ttft else None,
        itl_p99_ms=1e3 * float(np.percentile(itl, 99)) if itl else None)
    # the steps inside the traced window, for the kernel's roofline
    run.steps = steps
    st.update(seen=seen, ttft=ttft)


def _counters(engine) -> dict:
    return {k: getattr(engine, k) for k in (
        "decode_steps", "generated", "prefill_tokens", "cached_tokens",
        "prefix_hits", "prefix_misses", "cow_copies", "stalled_admissions",
        "evictions")}


def finished(run) -> list:
    """(request, served tokens) of every traffic request that finished."""
    st = run.state
    return [(s.req, st["engine"].responses[s.req.rid]) for s in st["seen"]
            if s.req.rid in st["engine"].responses]


def release(run) -> None:
    """Free the program's state; the weights stay for the reference."""
    serving.release(run, finished(run))


def compare(run, control: bool = False) -> dict:
    return serving.compare(run, control)


def checks(run, control: bool = False, got: dict = None) -> list:
    """Each reading the cell's limits file names, beside its limit; with
    ``control``, the fp8 control's readings stand in for the program's."""
    return serving.checks(run, control, got)
