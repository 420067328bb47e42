import os
# a CPU-only compile tool: 512 fake host devices, never the chip
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Roofline cost extraction with scan-trip-count correction.

XLA's HloCostAnalysis gives a while-loop body constant weight regardless of
trip count (verified experimentally — see EXPERIMENTS.md §Dry-run), so raw
cost_analysis() of the scanned layer stack is wrong.  Fix: compile the same
cell with the layer scan UNROLLED at n_groups = 2 and 3 (microbatches pinned
to 1 so the grad-accum loop disappears; that moves FLOPs between loops but
not their total) and fit linearly:

    cost(G) = cost(2) + (cost(3) - cost(2)) · (G - 2)

Verified linear to <2% (the g=1 point is excluded: XLA simplifies
single-layer programs more aggressively).  This captures everything in the
body — remat recompute, per-layer collectives, attention block skipping —
at exact HLO fidelity.  Collective bytes extrapolate per op kind the same
way.  Known residual: the RWKV intra-chunk scan stays rolled (its einsums
are <1% of layer FLOPs; noted in EXPERIMENTS.md).

Writes artifacts/analysis/<arch>__<shape>__16x16.json (single-pod: the
roofline table mesh) and, with --multi-pod, the 2x16x16 variant.
"""
import argparse
import dataclasses
import json
import subprocess
import sys
import time
from pathlib import Path

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "analysis"

_COLL_KEYS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
              "collective-permute")


def _variant_cfg(cfg, g: int):
    """Same family, n_groups = g (prefix/tail preserved)."""
    P = len(cfg.block_pattern)
    body = cfg.num_layers - cfg.first_k_dense
    tail = body % P
    n_layers = cfg.first_k_dense + g * P + tail
    kw = {"num_layers": n_layers}
    if cfg.is_encoder_decoder:
        full_groups = body // P
        kw["num_encoder_layers"] = max(
            1, cfg.num_encoder_layers * g // full_groups)
    return dataclasses.replace(cfg, **kw)


def _measure(arch, shape_name, multi_pod, cfg, run):
    from repro.launch.dryrun import build_lowered, parse_collectives
    lowered, meta = build_lowered(arch, shape_name, multi_pod,
                                  cfg_override=cfg, run_override=run,
                                  scan_unroll=True)
    if lowered is None:
        return None
    compiled = lowered.compile()
    from repro.launch.dryrun import cost_dict
    cost = cost_dict(compiled)
    rec = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "transcendentals": float(cost.get("transcendentals", 0.0)),
        "collectives": parse_collectives(compiled.as_text()),
    }
    return rec


def _extrapolate(c2, c3, G: int):
    """cost(G) from the (g=2, g=3) unrolled fit points."""
    out = {}
    for k in ("flops", "bytes", "transcendentals"):
        slope = c3[k] - c2[k]
        out[k] = c2[k] + slope * (G - 2)
    colls = {}
    keys = set(c2["collectives"]) | set(c3["collectives"])
    for op in keys:
        a = c2["collectives"].get(op, {"count": 0, "bytes": 0, "wire_bytes": 0})
        b = c3["collectives"].get(op, {"count": 0, "bytes": 0, "wire_bytes": 0})
        colls[op] = {
            key: a[key] + (b[key] - a[key]) * (G - 2)
            for key in ("count", "bytes", "wire_bytes")}
    out["collectives"] = colls
    return out


def run_analysis(arch: str, shape_name: str, multi_pod: bool) -> dict:
    from repro.configs import SHAPES, get_config, get_run_config, shape_applicable
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    meta = {"arch": arch, "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16"}
    if not ok:
        return {"ok": True, "skipped": why, **meta}

    run = get_run_config(arch, shape_name)
    run1 = dataclasses.replace(run, num_microbatches=1)
    P = len(cfg.block_pattern)
    G = (cfg.num_layers - cfg.first_k_dense) // P

    t0 = time.perf_counter()
    c2 = _measure(arch, shape_name, multi_pod, _variant_cfg(cfg, 2), run1)
    c3 = _measure(arch, shape_name, multi_pod, _variant_cfg(cfg, 3), run1)
    full = _extrapolate(c2, c3, G)
    return {
        "ok": True, **meta,
        "n_groups": G,
        "seconds": round(time.perf_counter() - t0, 1),
        "g2": c2, "g3": c3,
        "extrapolated": full,
    }


def cell_path(arch, shape_name, multi_pod) -> Path:
    mesh = "2x16x16" if multi_pod else "16x16"
    return ARTIFACTS / f"{arch}__{shape_name}__{mesh}.json"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args(argv)
    ARTIFACTS.mkdir(parents=True, exist_ok=True)

    if args.all:
        from repro.launch.dryrun import all_cells
        fails = 0
        for arch, shape_name in all_cells():
            out = cell_path(arch, shape_name, args.multi_pod)
            if out.exists() and not args.force:
                continue
            cmd = [sys.executable, "-m", "repro.launch.analysis",
                   "--arch", arch, "--shape", shape_name]
            if args.multi_pod:
                cmd.append("--multi-pod")
            print(f"[analysis] {arch} × {shape_name} ...", flush=True)
            if subprocess.run(cmd, timeout=3600).returncode:
                fails += 1
        return 1 if fails else 0

    assert args.arch and args.shape
    out = cell_path(args.arch, args.shape, args.multi_pod)
    if out.exists() and not args.force:
        print(f"[analysis] cached: {out}")
        return 0
    try:
        rec = run_analysis(args.arch, args.shape, args.multi_pod)
    except Exception as e:
        import traceback
        rec = {"ok": False, "arch": args.arch, "shape": args.shape,
               "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-3000:]}
    out.write_text(json.dumps(rec, indent=2))
    print(json.dumps({k: rec.get(k) for k in ("ok", "arch", "shape",
                                              "skipped", "error")}))
    return 0 if rec.get("ok") else 1


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
