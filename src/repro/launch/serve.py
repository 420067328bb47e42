"""Serving CLI: a thin parse-to-spec layer over the shared executor.

Flags build a ``JobSpec(kind="serve")``; ``repro.launch.executor`` runs it
(lockstep or continuous batching; see ``executor.run_lockstep`` /
``run_continuous``).  The same spec can instead be submitted to the
platform for gang-scheduled, quota'd, metered serving.

Two cache layouts (``--layout``):

* ``dense`` — the fallback: one (B, K, S_max, hd) buffer per layer,
  lockstep batch (every sequence at the same position).
* ``paged`` — vLLM-style: global-attention layers share a physical page
  pool with per-sequence page tables (``models/model.py``).  Lockstep mode
  uses identity page tables over a worst-case pool; ``--continuous`` runs
  real continuous batching — per-request admission when the page allocator
  has room, per-sequence decode positions, pages freed on completion.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \\
        --batch 4 --prompt-len 64 --gen 32
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \\
        --layout paged --continuous --requests 8
"""
from __future__ import annotations

import argparse

from repro.core.jobspec import JobSpec, ServeSpec
from repro.launch.executor import (  # noqa: F401  (PagePool: compat re-export)
    PagePool, execute, run_continuous, run_lockstep)


def parse_spec(argv=None) -> JobSpec:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--mesh", default="host", choices=["host", "prod", "multipod"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--layout", default=None, choices=["dense", "paged"],
                    help="KV-cache layout (default: the config's)")
    ap.add_argument("--page-size", type=int, default=0,
                    help="tokens per KV page (0 = config default)")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching over the paged cache")
    ap.add_argument("--requests", type=int, default=8,
                    help="request-queue length for --continuous")
    ap.add_argument("--page-budget", type=int, default=0,
                    help="physical pages in the pool (0 = worst case); "
                         "smaller budgets throttle admission")
    ap.add_argument("--overcommit", type=float, default=1.0,
                    help="optimistic admission factor: reserve worst-case "
                         "pages up to overcommit × budget; page exhaustion "
                         "evicts the youngest sequence back to the queue "
                         "(1.0 = conservative, never evicts)")
    ap.add_argument("--use-pallas", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="Pallas kernels for decode (default: on a TPU "
                         "backend only; interpret mode off-TPU)")
    ap.add_argument("--ragged-prefill", dest="ragged_prefill",
                    action="store_const", const=True, default=None,
                    help="force ragged prefill, each prompt padded to a "
                         "page multiple (default: every decoder-only arch)")
    ap.add_argument("--no-ragged-prefill", dest="ragged_prefill",
                    action="store_const", const=False,
                    help="force exact-length prefill (one compile per "
                         "prompt length)")
    ap.add_argument("--no-prefix-cache", dest="prefix_cache",
                    action="store_false", default=True,
                    help="disable hash-addressed prefix caching / "
                         "copy-on-write page sharing")
    ap.add_argument("--shared-prefix", type=float, default=0.0,
                    help="synthetic workload: fraction of prompt-len every "
                         "request shares as a common leading prefix")
    args = ap.parse_args(argv)

    return JobSpec(
        name=f"serve-{args.arch}",
        kind="serve",
        framework=args.arch,
        seed=args.seed,
        serve=ServeSpec(
            batch=args.batch,
            prompt_len=args.prompt_len,
            gen=args.gen,
            mesh=args.mesh,
            reduced=args.reduced,
            cache_layout=args.layout,
            page_size=args.page_size,
            continuous=args.continuous,
            requests=args.requests,
            page_budget=args.page_budget,
            overcommit=args.overcommit,
            use_pallas=args.use_pallas,
            ragged_prefill=args.ragged_prefill,
            prefix_cache=args.prefix_cache,
            shared_prefix_frac=args.shared_prefix,
        ))


def main(argv=None) -> int:
    return execute(parse_spec(argv))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
