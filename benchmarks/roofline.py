"""Roofline table (required §Roofline): three terms per (arch × shape),
single-pod 16×16 mesh, from the dry-run + analysis artifacts.

    compute    = HLO_FLOPs(device)      / 197 TFLOP/s   (bf16, TPU v5e)
    memory     = HLO_bytes(device)      / 819 GB/s      (HBM)
    collective = wire_bytes(device)     / 50 GB/s       (ICI per link)

FLOPs/bytes come from artifacts/analysis (unrolled-variant extrapolation —
XLA's cost model gives while bodies constant weight, see launch/analysis.py);
collective wire bytes use ring-algorithm accounting per op.  MODEL_FLOPS =
6·N·D (dense) or 6·N_active·D (MoE) counts non-embedding params.
"""
from __future__ import annotations

import json
from pathlib import Path

ART = Path(__file__).resolve().parents[1] / "artifacts"

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9
CHIPS = 256


def load_cell(arch, shape, mesh="16x16"):
    a = ART / "analysis" / f"{arch}__{shape}__{mesh}.json"
    d = ART / "dryrun" / f"{arch}__{shape}__{mesh}.json"
    rec = {}
    if a.exists():
        rec["analysis"] = json.loads(a.read_text())
    if d.exists():
        rec["dryrun"] = json.loads(d.read_text())
    return rec


def model_flops_per_device(dryrun: dict) -> float:
    """6·N(active)·tokens / chips; decode processes 1 token per sequence."""
    n = dryrun.get("n_params_active") or dryrun.get("n_params")
    kind = dryrun["kind"]
    B, S = dryrun["global_batch"], dryrun["seq_len"]
    tokens = B if kind == "decode" else B * S
    mult = 6 if kind == "train" else 2
    return mult * n * tokens / dryrun.get("n_devices", CHIPS)


def roofline_row(arch: str, shape: str) -> dict | None:
    rec = load_cell(arch, shape)
    dr = rec.get("dryrun", {})
    an = rec.get("analysis", {})
    if "dryrun" not in rec or "analysis" not in rec:
        # fresh checkout or half-run sweep: the cell's dry-run/analysis
        # pass hasn't produced both artifacts yet — not a failure
        return {"arch": arch, "shape": shape, "missing": True}
    if dr.get("skipped") or an.get("skipped"):
        return {"arch": arch, "shape": shape, "skipped": dr.get("skipped") or
                an.get("skipped")}
    if not dr.get("ok") or not an.get("ok"):
        return {"arch": arch, "shape": shape, "error": True}
    ex = an["extrapolated"]
    wire = sum(v["wire_bytes"] for v in ex["collectives"].values())
    t_comp = ex["flops"] / PEAK_FLOPS
    t_mem = ex["bytes"] / HBM_BW
    t_coll = wire / ICI_BW
    row = {}
    if dr.get("kind") == "decode" and _kernel_applies(arch):
        # Paged-decode pricing: the dry-run HLO walks the cache at the
        # dense/table-bounded rate, but the serving hot path is the paged
        # flash-decode kernel, which touches only *resident* pages.
        # Re-price the memory term by swapping the dense-view attention
        # bytes for the kernel's resident-page bytes (per device).  MLA
        # archs keep the HLO pricing — their latent cache has no paged
        # decode walk yet (ROADMAP).
        row.update(_paged_decode_pricing(arch, shape, ex["bytes"]))
        t_mem = row["t_memory_paged_s"]
    terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops_per_device(dr)
    step_time = max(terms.values())            # no-overlap upper bound
    mfu = mf / PEAK_FLOPS / step_time if step_time else 0.0
    row.update({
        "arch": arch, "shape": shape,
        "t_compute_s": t_comp, "t_memory_s": t_mem, "t_collective_s": t_coll,
        "dominant": dominant,
        "model_flops_dev": mf,
        "hlo_flops_dev": ex["flops"],
        "useful_ratio": mf / ex["flops"] if ex["flops"] else 0.0,
        "roofline_mfu": mfu,
        "temp_bytes_dev": dr.get("memory", {}).get("temp_size_in_bytes"),
    })
    return row


def _kernel_applies(arch: str) -> bool:
    """Paged flash-decode prices GQA page pools; MLA (latent cache) and
    attention-free stacks keep the raw HLO memory term."""
    from repro.configs import get_config
    cfg = get_config(arch)
    return cfg.uses_attention and not cfg.use_mla


def _paged_decode_pricing(arch: str, shape: str, hlo_bytes_dev: float) -> dict:
    """Kernel-vs-dense decode bandwidth for one cell: per-device attention
    bytes under the dense-view walk and the paged kernel (resident pages,
    serving occupancy from the cell's RunConfig), plus the re-priced
    memory term and the kernel's arithmetic intensity."""
    import dataclasses as _dc

    from repro.configs import SHAPES, get_config, get_run_config
    from repro.launch.specs import (
        decode_arithmetic_intensity, decode_attn_bytes)

    cfg = _dc.replace(get_config(arch), cache_layout="paged")
    sh = SHAPES[shape]
    run = get_run_config(arch, shape)
    dense_dev = decode_attn_bytes(cfg, sh, run, "dense") / CHIPS
    # dedup-aware: prefix pages shared across the batch (the serving
    # engine's prefix cache) are physically read once per step.  Equal to
    # the plain kernel walk at RunConfig.prefix_share_frac = 0, so cells
    # without a share assumption price exactly as before.
    kern_dev = decode_attn_bytes(cfg, sh, run, "kernel_unique") / CHIPS
    adj = max(hlo_bytes_dev - dense_dev + kern_dev, kern_dev)
    return {
        "attn_bytes_dense_dev": dense_dev,
        "attn_bytes_kernel_dev": kern_dev,
        "t_memory_paged_s": adj / HBM_BW,
        "kernel_ai_flops_per_byte": decode_arithmetic_intensity(
            cfg, sh, run, "kernel_unique"),
    }


def all_rows():
    from repro.configs import SHAPES, list_configs
    rows = []
    for arch in list_configs():
        if arch == "paper-overhead-100m":
            continue
        for shape in SHAPES:
            r = roofline_row(arch, shape)
            if r is not None:
                rows.append(r)
    return rows


def main():
    rows = all_rows()
    missing = sum(1 for r in rows if r.get("missing"))
    if missing == len(rows):
        print(f"(no dry-run artifacts for any of the {missing} cells — run "
              "`python -m repro.launch.dryrun --all` and "
              "`python -m repro.launch.analysis` to populate artifacts/)")
        return
    print("arch,shape,t_compute_ms,t_memory_ms,t_collective_ms,dominant,"
          "useful_flops_ratio,roofline_mfu,temp_GB,kernel_ai")
    for r in rows:
        if r.get("missing"):
            print(f"{r['arch']},{r['shape']},MISSING,,,,")
            continue
        if r.get("skipped"):
            print(f"{r['arch']},{r['shape']},SKIP,,,,{r['skipped'][:40]}...")
            continue
        if r.get("error"):
            print(f"{r['arch']},{r['shape']},ERROR,,,,")
            continue
        ai = r.get("kernel_ai_flops_per_byte")
        print(f"{r['arch']},{r['shape']},"
              f"{r['t_compute_s']*1e3:.1f},{r['t_memory_s']*1e3:.1f},"
              f"{r['t_collective_s']*1e3:.1f},{r['dominant']},"
              f"{r['useful_ratio']:.3f},{r['roofline_mfu']:.3f},"
              f"{(r['temp_bytes_dev'] or 0)/1e9:.1f},"
              f"{'' if ai is None else f'{ai:.2f}'}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
