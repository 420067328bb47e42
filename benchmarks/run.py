"""Benchmark driver: one section per paper table/figure + the roofline.

    PYTHONPATH=src python -m benchmarks.run [--quick]
"""
from __future__ import annotations

import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="skip the slow real-compute benchmarks")
    args = ap.parse_args()

    print("=" * 72)
    print("# Fig 4 — per-component crash recovery (virtual seconds)")
    print("=" * 72)
    from benchmarks import recovery_fig4
    recovery_fig4.main()

    if not args.quick:
        print()
        print("=" * 72)
        print("# Fig 2 — platform overhead vs bare loop (real JAX steps)")
        print("=" * 72)
        from benchmarks import overhead_fig2
        overhead_fig2.main()

        print()
        print("=" * 72)
        print("# Fig 3 — dependability fully-armed vs minimal")
        print("=" * 72)
        from benchmarks import dependability_fig3
        dependability_fig3.main([])

    print()
    print("=" * 72)
    print("# Chaos lane — self-healing Guardian: classify + safe repair "
          "per failure class")
    print("=" * 72)
    from benchmarks import dependability_fig3 as fig3
    failures_chaos = fig3.main(
        ["--chaos", "--smoke"] if args.quick else ["--chaos"])

    print()
    print("=" * 72)
    print("# Serve decode — paged flash-decode vs reference walk "
          "(bytes/step + tok/s)")
    print("=" * 72)
    from benchmarks import serve_decode
    failures = serve_decode.main(["--smoke"] if args.quick else [])

    print()
    print("=" * 72)
    print("# Platform serve — real-engine payload under the platform vs "
          "direct (wall s)")
    print("=" * 72)
    from benchmarks import platform_serve
    failures = platform_serve.main(
        ["--smoke"] if args.quick else []) or failures

    print()
    print("=" * 72)
    print("# Prefix cache — shared-prefix dedup + CoW "
          "(prefill tokens, resident pages, capacity)")
    print("=" * 72)
    from benchmarks import prefix_cache
    failures = prefix_cache.main(
        ["--smoke"] if args.quick else ["--no-write"]) or failures

    print()
    print("=" * 72)
    print("# Roofline — per (arch × shape), single-pod 16x16 "
          "(from dry-run artifacts)")
    print("=" * 72)
    from benchmarks import roofline
    roofline.main()

    if failures or failures_chaos:
        sys.exit(1)                  # propagate lane FAILs to CI


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
