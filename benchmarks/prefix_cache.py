"""Prefix-cache benchmark lane: hash-addressed shared prefix pages + CoW.

Two sections, emitted together to ``BENCH_prefix_cache.json``:

* **modeled** — per-step decode attention bytes and admitted capacity for
  production decode cells under batch-wide prefix sharing, swept over the
  share ratio (``RunConfig.prefix_share_frac``).  Shared prefix pages are
  physically resident ONCE, so the ``kernel_unique`` pricing path of
  ``launch.specs.decode_attn_bytes`` scales bytes/step down toward
  ``1/B`` of the kernel walk as the share ratio grows, and the same page
  budget admits proportionally more concurrent sequences.
* **measured** — the real ``ServingEngine`` on the current backend (CPU
  in CI) at a reduced shape, swept over share ratio × the same batch:
  prompt tokens actually prefilled vs served from cache, unique resident
  prefix pages (N sequences, ONE physical copy), peak concurrency vs the
  ``prefix_cache=False`` baseline, and byte-identical responses between
  the shared batch and solo runs of each request through a fresh engine
  (sharing is an alias, never an answer change — greedy decode must not
  notice).  The no-cache baseline's responses are reported but not gated:
  it prefills via the flash path, a different float-association family
  than the cache engine's chunked paged walk (~2e-3 logit noise, which
  can flip an argmax without either result being wrong).

    PYTHONPATH=src python -m benchmarks.prefix_cache [--smoke] [--no-write]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
from pathlib import Path

OUT = Path(__file__).resolve().parents[1] / "BENCH_prefix_cache.json"

MODELED_ARCHS = ("qwen3-0.6b", "gemma2-9b", "mistral-large-123b")
MODELED_SHAPE = "decode_32k"
SHARES = (0.0, 0.5, 0.9)


def modeled_rows():
    from repro.configs import SHAPES, RunConfig, get_config
    from repro.launch.specs import (
        decode_attn_bytes, decode_page_budget, unique_decode_pages)
    from repro.models.model import num_pages

    rows = []
    for arch in MODELED_ARCHS:
        cfg = dataclasses.replace(get_config(arch), cache_layout="paged")
        sh = SHAPES[MODELED_SHAPE]
        B = sh.global_batch
        r = num_pages(sh.seq_len, cfg.page_size)   # resident pages/seq
        for share in SHARES:
            run = RunConfig(prefix_share_frac=share)
            kern = decode_attn_bytes(cfg, sh, run, "kernel")
            uniq = decode_attn_bytes(cfg, sh, run, "kernel_unique")
            budget = decode_page_budget(cfg, sh, run)
            shared_pages = min(int(r * share), r)
            # the page budget holds `cap` concurrent sequences: the shared
            # span is resident once, each private remainder per sequence
            cap = (budget - shared_pages) // max(r - shared_pages, 1) \
                if shared_pages else budget // r
            rows.append({
                "arch": arch, "shape": MODELED_SHAPE, "share": share,
                "batch": B, "pages_per_seq": r,
                "unique_pages": unique_decode_pages(B, r, run),
                "bytes_kernel": kern, "bytes_kernel_unique": uniq,
                "reduction_bytes": round(kern / uniq, 3),
                "admitted_capacity": int(cap),
                "capacity_gain": round(cap / max(budget // r, 1), 3),
            })
    return rows


def _drive(engine):
    """engine.run() while tracking peak concurrency."""
    peak = 0
    while not engine.idle:
        engine.admit()
        peak = max(peak, sum(s is not None for s in engine.slots))
        if all(s is None for s in engine.slots):
            if not engine.queue:
                break
            continue
        engine.step()
    return peak


def measured_rows(smoke: bool):
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core.jobspec import ServeSpec
    from repro.launch.engine import ServingEngine, synthesize_requests
    from repro.models.layers import Ctx
    from repro.models.params import init_params

    # prompt 80 @ page 8: 90% share = 72 tokens = exactly 9 full pages.
    # budget 40 serializes the no-sharing baseline (8 x 11 worst-case
    # pages) but admits the whole dedup batch (11 + 7 x 2 reserved).
    N, P, G, budget = 8, 80, 8, 40
    cfg = dataclasses.replace(get_config("qwen3-0.6b").reduced(),
                              cache_layout="paged")
    ctx = Ctx(dtype=jnp.float32)
    params = init_params(cfg, jax.random.key(0))
    shares = (0.0, 0.9) if smoke else SHARES

    steps = []
    for share in shares:
        sv = ServeSpec(batch=N, prompt_len=P, gen=G, requests=N,
                       page_budget=budget, reduced=True,
                       shared_prefix_frac=share)
        eng = ServingEngine(cfg, ctx, params, sv)
        reqs = synthesize_requests(cfg, sv, seed=0, ragged=eng.ragged)
        for r in reqs:
            eng.submit(r)
        # capture residency right after the batch is fully admitted
        eng.admit()
        eng.admit()
        prefix_pages = eng.resident_prefix_pages()
        unique_pages = eng.unique_resident_pages()
        peak = max(_drive(eng), sum(s is not None for s in eng.slots))

        base = ServingEngine(cfg, ctx, params,
                             dataclasses.replace(sv, prefix_cache=False))
        for r in synthesize_requests(cfg, sv, seed=0, ragged=base.ragged):
            base.submit(r)
        base_peak = _drive(base)

        # golden gate: each request solo through a fresh cache engine must
        # reproduce its batch response token-for-token — page aliasing and
        # CoW are invisible to the answers (smoke spot-checks 3 requests)
        probe = range(N) if not smoke else (0, 1, N - 1)
        solo_ok = True
        for i in probe:
            se = ServingEngine(cfg, ctx, params, sv)
            se.submit(reqs[i])
            se.run()
            solo_ok = solo_ok and se.responses[i] == eng.responses[i]

        steps.append({
            "share": share, "requests": N, "prompt_len": P,
            "page_size": eng.ps, "page_budget": budget,
            "prefill_tokens": eng.prefill_tokens,
            "cached_tokens": eng.cached_tokens,
            "prefill_tokens_baseline": base.prefill_tokens,
            "resident_prefix_pages": prefix_pages,
            "unique_resident_pages": unique_pages,
            "prefix_hits": eng.prefix_hits,
            "cow_copies": eng.cow_copies,
            "peak_concurrency": peak,
            "peak_concurrency_baseline": base_peak,
            "responses_match_solo": solo_ok,
            "responses_match_baseline": eng.responses == base.responses,
        })
    return {"arch": cfg.name, "backend": jax.default_backend(),
            "steps": steps}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="share endpoints only (CI regression gate)")
    ap.add_argument("--no-write", action="store_true",
                    help="print only; don't rewrite BENCH_prefix_cache.json")
    args = ap.parse_args(argv)

    modeled = modeled_rows()
    print("arch,shape,share,GB_kernel,GB_unique,reduction,capacity,gain")
    for r in modeled:
        print(f"{r['arch']},{r['shape']},{r['share']},"
              f"{r['bytes_kernel']/1e9:.2f},"
              f"{r['bytes_kernel_unique']/1e9:.2f},"
              f"{r['reduction_bytes']:.1f}x,{r['admitted_capacity']},"
              f"{r['capacity_gain']:.1f}x")

    measured = measured_rows(args.smoke)
    print(f"\nmeasured (arch={measured['arch']}, "
          f"backend={measured['backend']}):")
    for s in measured["steps"]:
        print(f"  share={s['share']:<4} prefill {s['prefill_tokens']:4d} "
              f"(baseline {s['prefill_tokens_baseline']}) "
              f"cached {s['cached_tokens']:4d}  prefix pages "
              f"{s['resident_prefix_pages']}  concurrency "
              f"{s['peak_concurrency']} vs {s['peak_concurrency_baseline']}"
              f"  solo_match={s['responses_match_solo']}")

    failures = []
    for s in measured["steps"]:
        if not s["responses_match_solo"]:
            failures.append(f"share {s['share']}: batch responses diverged "
                            "from solo runs (aliasing changed an answer)")
    hi = [s for s in measured["steps"] if s["share"] == 0.9]
    for s in hi:
        N, P, ps = s["requests"], s["prompt_len"], s["page_size"]
        C = int(P * 0.9)
        # exactly ONE prefill over the shared span: leader pays P, each
        # follower only its private tail
        want = P + (N - 1) * (P - C)
        if s["prefill_tokens"] != want:
            failures.append(f"90% share: {s['prefill_tokens']} prefill "
                            f"tokens, want {want} (one shared-span prefill)")
        if s["resident_prefix_pages"] != -(-C // ps):
            failures.append(f"90% share: {s['resident_prefix_pages']} "
                            f"resident prefix pages, want {-(-C // ps)} "
                            "(one physical copy, not N)")
        if s["peak_concurrency"] < 2 * s["peak_concurrency_baseline"]:
            failures.append("90% share: <2x measured admitted capacity")
    hi_m = [r for r in modeled if r["share"] == 0.9]
    if any(r["reduction_bytes"] < 2.0 and r["capacity_gain"] < 2.0
           for r in hi_m):
        failures.append("<2x modeled bytes/step AND capacity at 90% share")

    for f in failures:
        print(f"FAIL: {f}")
    if failures:
        return 1

    if not args.no_write and not args.smoke:   # smoke never rewrites the
        OUT.write_text(json.dumps(             # checked-in trajectory file
            {"modeled": modeled, "measured": measured}, indent=1) + "\n")
        print(f"wrote {OUT}")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
