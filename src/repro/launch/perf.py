import os
# a CPU-only compile tool: 512 fake host devices, never the chip
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""§Perf hillclimb harness: measure one cell under modified knobs.

    PYTHONPATH=src python -m repro.launch.perf --arch X --shape Y \
        [--override resid_seq=model] [--override seq=model] \
        [--microbatches N] [--constrain-scan-weights] [--tag note]

Prints the three roofline terms + temp memory, and appends a JSON line to
artifacts/perf_log.jsonl so every hypothesis→measure iteration is recorded.
"""
import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ART = Path(__file__).resolve().parents[3] / "artifacts"

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9


def measure(arch, shape, overrides, mb=None, csw=False, multi_pod=False):
    from repro.configs import get_config, get_run_config
    from repro.dist.sharding import DEFAULT_RULES
    from repro.launch.dryrun import build_lowered, cost_dict, parse_collectives
    from repro.launch.analysis import _variant_cfg, _extrapolate

    run = get_run_config(arch, shape)
    if mb is not None:
        run = dataclasses.replace(run, num_microbatches=mb)
    if overrides:
        run = dataclasses.replace(
            run, sharding_overrides=tuple((k, tuple(v.split("+")) if v else ())
                                          for k, v in overrides.items()))

    def _build(cfg_override=None, run_override=None, unroll=False):
        rules = DEFAULT_RULES
        r = run_override or run
        if r.sharding_overrides:
            rules = rules.override(**{k: v for k, v in r.sharding_overrides})
        return build_lowered(
            arch, shape, multi_pod, rules=rules, cfg_override=cfg_override,
            run_override=r, scan_unroll=unroll,
            constrain_scan_weights=csw)

    # memory from the FULL config compile
    t0 = time.perf_counter()
    lowered, meta = _build()
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    temp = int(getattr(mem, "temp_size_in_bytes", 0))
    arg = int(getattr(mem, "argument_size_in_bytes", 0))

    # roofline terms from unrolled g=2/3 variants
    cfg = get_config(arch)
    G = (cfg.num_layers - cfg.first_k_dense) // len(cfg.block_pattern)
    run1 = dataclasses.replace(run, num_microbatches=1)
    cs = {}
    for g in (2, 3):
        lw, _ = _build(cfg_override=_variant_cfg(cfg, g), run_override=run1,
                       unroll=True)
        c = lw.compile()
        cost = cost_dict(c)
        cs[g] = {"flops": float(cost.get("flops", 0)),
                 "bytes": float(cost.get("bytes accessed", 0)),
                 "transcendentals": float(cost.get("transcendentals", 0)),
                 "collectives": parse_collectives(c.as_text())}
    ex = _extrapolate(cs[2], cs[3], G)
    wire = sum(v["wire_bytes"] for v in ex["collectives"].values())
    rec = {
        "arch": arch, "shape": shape, "overrides": overrides, "mb": mb,
        "constrain_scan_weights": csw,
        "analytic": meta.get("analytic"),
        "temp_GB": round(temp / 1e9, 2), "args_GB": round(arg / 1e9, 2),
        "t_compute_s": round(ex["flops"] / PEAK_FLOPS, 4),
        "t_memory_s": round(ex["bytes"] / HBM_BW, 4),
        "t_collective_s": round(wire / ICI_BW, 4),
        "collectives_GB": {k: round(v["wire_bytes"] / 1e9, 2)
                           for k, v in ex["collectives"].items()},
        "flops_dev": ex["flops"],
        "seconds": round(time.perf_counter() - t0, 1),
    }
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--override", action="append", default=[],
                    help="logical=mesh1+mesh2 (empty rhs = replicate)")
    ap.add_argument("--microbatches", type=int)
    ap.add_argument("--constrain-scan-weights", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--tag", default="")
    args = ap.parse_args(argv)

    overrides = {}
    for o in args.override:
        k, _, v = o.partition("=")
        overrides[k] = v

    rec = measure(args.arch, args.shape, overrides, args.microbatches,
                  args.constrain_scan_weights, args.multi_pod)
    rec["tag"] = args.tag
    print(json.dumps(rec, indent=2))
    with open(ART / "perf_log.jsonl", "a") as f:
        f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
