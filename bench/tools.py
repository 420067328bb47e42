"""Measurements that set a cell's numbers, each in one process on the chip.

    python3 bench/tools.py sweep --workload <cell> --rates 1,2,3 --seconds 20
        the cell's traffic at each offered rate: tails, and tokens
        completed per second against tokens offered (to find the knee);
    python3 bench/tools.py control --workload <cell> --seeds 1,2,3 --seconds 20
        per seed, a short window at the cell's own load, then the
        served-token gaps of the program and of the fp8 control (the
        lower and upper readings a limit is set from), and whether each,
        held to the cell's limits, comes out correct;
    python3 bench/tools.py record-trace --workload <cell> --seconds 3 --out DIR
        a run whose short trace is kept in DIR (``bench/testdata``).

Where the benchmark's own runs print one result, these print one JSON
line per rate or seed.  The benchmark runs never call them.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _build(args, seed):
    from bench import harness
    ns = argparse.Namespace(workload=args.workload, seed=seed,
                            seconds=args.seconds, trace=0)
    return harness.build(ROOT, ns)


def sweep(args) -> None:
    import numpy as np
    for rate in (float(r) for r in args.rates.split(",")):
        run, driver, _ = _build(args, args.seed)
        run.cell.traffic["rate_per_s"] = rate
        driver.setup(run)
        driver.window(run)
        c = run.counters
        offered = sum(r.gen_len for r in run.state["reqs"]
                      if 0 <= r.due < run.seconds) / run.seconds
        due = [s for s in run.state["seen"] if 0 <= s.req.due < run.seconds]
        ttft = run.state["ttft"]
        print(json.dumps({
            "rate_per_s": rate, "attempted": run.attempted,
            "ttft_p50_ms": run.e2e["ttft_p50_ms"],
            "itl_p99_ms": run.e2e["itl_p99_ms"],
            "first_tokens": sum(s.t_first is not None for s in due),
            "tokens_per_s": c["generated"] / c["window_s"],
            "offered_tokens_per_s": offered,
            "queued_at_open": c["queued_at_open"],
            "queued_at_close": c["queued_at_close"],
            "mean_active_slots": c["mean_active_slots"],
            "mean_pages_in_use": c["mean_pages_in_use"],
            "decode_step_ms": 1e3 * c["step_s"] / max(c["steps"], 1),
            "admit_ms": 1e3 * c["admit_s"] / max(c["admit_rounds"], 1),
            "busy_share": (c["step_s"] + c["admit_s"]) / c["window_s"],
            "ttft_p95_ms": 1e3 * float(np.percentile(ttft, 95)),
            "counters": c}), flush=True)
        del run, driver
        gc.collect()


def control(args) -> None:
    from bench import harness
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        run, driver, _ = _build(args, seed)
        driver.setup(run)
        driver.window(run)
        driver.release(run)
        got = driver.compare(run, control=True)
        ok = harness.is_correct(driver.checks(run, False, got))
        ok_control = harness.is_correct(driver.checks(run, True, got))
        print(json.dumps({"seed": seed, **got, "correct": ok,
                          "control_correct": ok_control, "ttft_p50_ms":
                          run.e2e["ttft_p50_ms"], "seconds":
                          time.perf_counter() - t0}), flush=True)
        del run, driver
        gc.collect()


def record_trace(args) -> None:
    from bench import harness, trace
    run, driver, _ = _build(args, args.seed)
    run.tracer = harness.Tracer(run, args.trace_s, driver.SPANS)
    driver.setup(run)
    driver.window(run)
    path = trace.find_xplane(run.tracer.dir)
    os.makedirs(args.out, exist_ok=True)
    dst = os.path.join(args.out, "serve.xplane.pb")
    shutil.copy(path, dst)
    red = trace.reduce(dst, driver.SPANS)
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(dst).planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
        print("plane", plane.name, lines[:12], file=sys.stderr)
    steps = [pos for t0, t1, pos in run.steps if run.traced(t0, t1)]
    print(json.dumps({"busy_s": red.busy_s, "window_s": red.window_s,
                      "top_ops": red.top_ops(20), "gaps": red.top_gaps(),
                      "traced_steps": len(steps),
                      "kernel_s": red.kernel_s("paged_decode"),
                      "bytes": os.path.getsize(dst)}), flush=True)
    shutil.rmtree(run.tracer.dir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("what", choices=("sweep", "control", "record-trace"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--rates", default="1,2,3")
    ap.add_argument("--trace-s", type=float, default=0.3)
    ap.add_argument("--out", default="bench/testdata")
    args = ap.parse_args(argv)
    {"sweep": sweep, "control": control,
     "record-trace": record_trace}[args.what](args)
    return 0


if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    raise SystemExit(main())
