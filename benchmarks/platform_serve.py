"""Platform-vs-direct serving overhead for the REAL payload (Fig-2 analog
for the serve kind).

The same ``JobSpec(kind="serve", serve.real_compute=True)`` workload runs
twice:

* **direct** — ``RealServePayload.build()`` + ``ServingEngine`` drained
  in-process: model build, prefill/decode compiles, continuous batching.
* **platform** — submitted to ``DLaaSPlatform``: the identical engine runs
  inside a server pod under the full dependability machinery (gang
  admission, claim journal + periodic engine snapshots on the job volume,
  COS response shipping, Guardian monitoring, metering).

Overhead = extra wall-clock the platform machinery adds around identical
JAX work (each side pays exactly one model build + compile).  The run also
asserts the two response sets are byte-identical — the platform must never
change what gets served, only make it dependable.

    PYTHONPATH=src python -m benchmarks.platform_serve [--smoke] [--no-write]

``--smoke`` (CI) uses tiny shapes and never rewrites the checked-in
``BENCH_platform_serve.json``.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

OUT = Path(__file__).resolve().parents[1] / "BENCH_platform_serve.json"


def _spec(smoke: bool):
    from repro.core.jobspec import JobSpec, ServeSpec
    sv = ServeSpec(batch=2, prompt_len=16, gen=6, requests=4,
                   reduced=True, real_compute=True, snapshot_every=2) \
        if smoke else \
        ServeSpec(batch=4, prompt_len=32, gen=16, requests=12,
                  reduced=True, real_compute=True, snapshot_every=4)
    return JobSpec(name="bench-platform-serve", kind="serve",
                   framework="qwen3-0.6b", serve=sv)


def run_direct(spec):
    from repro.launch.engine import RealServePayload
    t0 = time.perf_counter()
    engine, requests = RealServePayload(spec).build()
    for r in requests:
        engine.submit(r)
    engine.run()
    dt = time.perf_counter() - t0
    return dt, engine.responses, {
        "decode_steps": engine.decode_steps,
        "generated": engine.generated,
        "high_water_pages": engine.pool.high_water,
    }


def run_platform(spec):
    from repro.core.platform import DLaaSPlatform
    t0 = time.perf_counter()
    p = DLaaSPlatform(seed=11)
    p.run(10)
    h = p.submit(spec)
    p.run(5)
    if not h.acked:
        raise RuntimeError(f"platform rejected the job: {h.rejected}")
    state = p.run_until_terminal(h.job_id, timeout=3600)
    dt = time.perf_counter() - t0
    if state != "COMPLETED":
        raise RuntimeError(f"platform serve job ended {state}")
    responses = {}
    for r in range(spec.serve.requests):
        raw = p.objectstore.get(f"cos/{h.job_id}/responses/{r}")
        responses[r] = json.loads(raw.decode())["tokens"]
    return dt, responses, {"virtual_s": round(p.sim.now, 1),
                           "restarts": p.client.get(h.job_id)["restarts"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes (CI platform-serve gate)")
    ap.add_argument("--no-write", action="store_true",
                    help="print only; don't rewrite BENCH_platform_serve.json")
    args = ap.parse_args(argv)

    spec = _spec(args.smoke)
    sv = spec.serve
    print(f"workload: {spec.framework} reduced, slots={sv.batch} "
          f"prompt<={sv.prompt_len} gen<={sv.gen} requests={sv.requests} "
          f"snapshot_every={sv.snapshot_every}")

    # warm-up both paths once so first-touch costs (compile caches, import
    # side effects) bias neither measured run; smoke only gates on the
    # byte-equality check, so it skips the warm-up entirely
    if not args.smoke:
        run_direct(spec)
        run_platform(spec)
    direct_s, direct_resp, engine_stats = run_direct(spec)
    platform_s, platform_resp, plat_stats = run_platform(spec)

    if platform_resp != direct_resp:
        print("FAIL: platform responses diverge from the direct engine run")
        return 1
    overhead_pct = 100.0 * (platform_s - direct_s) / direct_s
    tokens = sum(len(t) for t in direct_resp.values())
    print(f"direct:   {direct_s:6.1f} s wall "
          f"({tokens/direct_s:.0f} tok/s, "
          f"{engine_stats['decode_steps']} decode steps)")
    print(f"platform: {platform_s:6.1f} s wall "
          f"(virtual {plat_stats['virtual_s']} s, "
          f"restarts {plat_stats['restarts']})")
    print(f"overhead: {overhead_pct:+.1f}% (incl. per-pod model build, "
          f"snapshots every {sv.snapshot_every} steps, COS shipping)")
    print("responses: byte-identical across platform and direct runs")

    if not args.no_write and not args.smoke:   # smoke never rewrites the
        OUT.write_text(json.dumps(             # checked-in trajectory file
            {"workload": {"framework": spec.framework,
                          "batch": sv.batch, "prompt_len": sv.prompt_len,
                          "gen": sv.gen, "requests": sv.requests,
                          "snapshot_every": sv.snapshot_every},
             "direct_s": round(direct_s, 2),
             "platform_s": round(platform_s, 2),
             "overhead_pct": round(overhead_pct, 1),
             "tokens": tokens,
             "engine": engine_stats,
             "platform": plat_stats,
             "responses_match": True}, indent=1) + "\n")
        print(f"wrote {OUT}")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
