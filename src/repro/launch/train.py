"""Training CLI: a thin parse-to-spec layer over the shared executor.

Flags build a ``JobSpec(kind="train")``; ``repro.launch.executor`` runs it.
The same spec can be submitted to the platform instead
(``DLaaSPlatform.submit``) to run under the full dependability machinery.

CPU-runnable with --reduced (the same code path the production mesh uses;
on a real TPU slice drop --reduced and pass --mesh prod/multipod).

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
        --reduced --steps 100 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse

from repro.core.jobspec import JobSpec, TrainSpec
from repro.launch.executor import execute


def parse_spec(argv=None) -> JobSpec:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-overhead-100m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="none", choices=["none", "dots", "full"])
    ap.add_argument("--mesh", default="host", choices=["host", "prod", "multipod"])
    ap.add_argument("--use-pallas", action="store_true",
                    help="refused until a Pallas kernel has a backward pass")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    return JobSpec(
        name=f"train-{args.arch}",
        kind="train",
        framework=args.arch,
        seed=args.seed,
        train=TrainSpec(
            total_steps=args.steps,
            global_batch=args.batch,
            seq_len=args.seq,
            learning_rate=args.lr,
            num_microbatches=args.microbatches,
            remat_policy=args.remat,
            mesh=args.mesh,
            use_pallas=args.use_pallas,
            reduced=args.reduced,
            log_every=args.log_every,
        ))


def main(argv=None) -> int:
    return execute(parse_spec(argv))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
