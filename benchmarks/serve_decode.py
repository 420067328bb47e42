"""Serving-decode benchmark lane: paged-reference walk vs flash-decode.

Four sections, emitted together to ``BENCH_serve_decode.json``:

* **modeled** — per-step attention bytes-touched for production decode
  cells under the walks priced by ``launch.specs.decode_attn_bytes``
  (dense buffer / paged gather reference / paged kernel — and, for MLA,
  the hypothetical head-expanded cache), swept over pool occupancy.  The
  reference gathers the table-bounded dense view, so its bytes are flat
  in occupancy; the kernel touches only resident pages, so its bytes
  scale down linearly — the ratio is the modeled bandwidth win (4x at
  25% occupancy, the ISSUE acceptance number).  For deepseek-v2 the
  latent walk must also price ≥4x below the dense-expanded equivalent.
* **measured** — real wall-clock per decode step at a small op-level
  shape on the current backend (CPU in CI): the jitted reference gather
  vs the jitted O(pages) ``lax.scan`` walk, over the same occupancy
  sweep, plus a one-step interpret-mode run of the Pallas kernel checked
  against the reference (kernels are *validated* here; kernel speed is a
  TPU property the modeled section stands in for).
* **mla_measured** — the same sweep for the MLA latent walk: scan
  ms/step, latent vs hypothetical dense-expanded bytes/step, and the
  latent Pallas kernel validated (interpret) against the scan.
* **grouped_measured** — the head-tiled grouped kernel at G=8 (beyond
  the old ``G <= 4`` auto-cap) validated against the ungrouped grid and
  the scan walk, with scan timing for scale.

    PYTHONPATH=src python -m benchmarks.serve_decode [--smoke] [--no-write]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from pathlib import Path

OUT = Path(__file__).resolve().parents[1] / "BENCH_serve_decode.json"

MODELED_ARCHS = ("qwen3-0.6b", "gemma2-9b", "mistral-large-123b",
                 "deepseek-v2-236b")
MODELED_SHAPE = "decode_32k"
OCCUPANCIES = (1.0, 0.5, 0.25, 0.125)


def modeled_rows():
    from repro.configs import SHAPES, RunConfig, get_config
    from repro.launch.specs import (
        decode_arithmetic_intensity, decode_attn_bytes)

    rows = []
    for arch in MODELED_ARCHS:
        cfg = dataclasses.replace(get_config(arch), cache_layout="paged")
        sh = SHAPES[MODELED_SHAPE]
        for occ in OCCUPANCIES:
            run = RunConfig(page_occupancy=occ)
            dense = decode_attn_bytes(cfg, sh, run, "dense")
            ref = decode_attn_bytes(cfg, sh, run, "reference")
            kern = decode_attn_bytes(cfg, sh, run, "kernel")
            row = {
                "arch": arch, "shape": MODELED_SHAPE, "occupancy": occ,
                "bytes_dense": dense, "bytes_reference": ref,
                "bytes_kernel": kern,
                "reduction_ref_over_kernel": round(ref / kern, 3),
                "kernel_ai_flops_per_byte": round(
                    decode_arithmetic_intensity(cfg, sh, run, "kernel"), 3),
                "reference_ai_flops_per_byte": round(
                    decode_arithmetic_intensity(cfg, sh, run, "reference"), 3),
            }
            if cfg.use_mla:
                # the MLA lane's headline: what a head-expanded cache
                # would read vs the latent pages the kernel walks
                expanded = decode_attn_bytes(cfg, sh, run, "dense_expanded")
                row["bytes_dense_expanded"] = expanded
                row["reduction_expanded_over_kernel"] = round(
                    expanded / kern, 3)
            rows.append(row)
    return rows


def _time_it(fn, *args, iters: int) -> float:
    import jax
    jax.block_until_ready(fn(*args))           # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def measured_rows(smoke: bool):
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ops import paged_decode_bhd
    from repro.kernels.paged_attention import paged_decode_jnp
    from repro.models.attention import decode_attention_paged

    if smoke:
        B, H, K, hd, ps, pps, iters = 2, 4, 2, 16, 8, 8, 3
    else:
        B, H, K, hd, ps, pps, iters = 8, 16, 4, 64, 16, 64, 20
    P = B * pps                                  # worst-case pool
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, 1, H, hd)), jnp.float32)
    # a one-layer stack of the token-major pool, read at layer 0
    kp = jnp.asarray(rng.normal(size=(1, P, ps, K * hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(1, P, ps, K * hd)), jnp.float32)
    layer = jnp.int32(0)
    scale = hd ** -0.5

    ref = jax.jit(functools.partial(decode_attention_paged, layer=layer,
                                    scale=scale))
    scan = jax.jit(functools.partial(
        lambda q, k, v, t, p, scale: paged_decode_jnp(
            q.reshape(B, K, H // K, hd), k, v, t, p, layer,
            scale=scale).reshape(B, 1, H, hd), scale=scale))

    shape_meta = {"B": B, "H": H, "K": K, "hd": hd, "page_size": ps,
                  "pages_per_seq": pps, "pool_pages": P, "iters": iters,
                  "backend": jax.default_backend()}
    steps = []
    kernel_err = 0.0
    for occ in OCCUPANCIES:
        live = max(int(pps * occ), 1)
        table = np.full((B, pps), -1, np.int32)
        for b in range(B):
            table[b, :live] = rng.permutation(P)[:live]
        table_j = jnp.asarray(table)
        pos = jnp.full((B,), live * ps - 1, jnp.int32)   # last live slot
        t_ref = _time_it(ref, q, kp, vp, table_j, pos, iters=iters)
        t_scan = _time_it(scan, q, kp, vp, table_j, pos, iters=iters)
        # one interpret-mode kernel step, checked against the reference
        out_k = paged_decode_bhd(q, kp, vp, table_j, pos, layer, scale=scale)
        out_r = ref(q, kp, vp, table_j, pos)
        kernel_err = max(kernel_err, float(jnp.abs(out_k - out_r).max()))
        token_bytes = 2 * K * hd * 4                     # K+V, fp32
        steps.append({
            "occupancy": occ, "live_pages": live,
            "ref_ms_per_step": round(t_ref * 1e3, 3),
            "scan_ms_per_step": round(t_scan * 1e3, 3),
            "tokens_per_s_ref": round(B / t_ref, 1),
            "tokens_per_s_scan": round(B / t_scan, 1),
            "bytes_touched_ref": B * pps * ps * token_bytes,
            "bytes_touched_scan": B * live * ps * token_bytes,
        })
    return {"shape": shape_meta, "steps": steps,
            "kernel_interpret_max_abs_err": kernel_err}


def mla_measured_rows(smoke: bool):
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.paged_attention import (
        mla_paged_decode_attention, mla_paged_decode_jnp)

    if smoke:
        B, H, lora, rd, ps, pps, iters = 2, 4, 16, 8, 8, 8, 3
    else:
        B, H, lora, rd, ps, pps, iters = 8, 16, 64, 32, 16, 64, 20
    # the hypothetical head-expanded cache the latent layout replaces:
    # per-head nope+rope keys and values of the same latent capacity
    expanded_tok_bytes = H * (lora + rd + lora) * 4
    latent_tok_bytes = (lora + rd) * 4
    P = B * pps
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(B, H, lora + rd)), jnp.float32)
    ckv = jnp.asarray(rng.normal(size=(1, P, ps, lora)), jnp.float32)
    krope = jnp.asarray(rng.normal(size=(1, P, ps, rd)), jnp.float32)
    layer = jnp.int32(0)
    scale = (lora + rd) ** -0.5

    scan = jax.jit(functools.partial(mla_paged_decode_jnp, layer=layer,
                                     scale=scale))
    shape_meta = {"B": B, "H": H, "kv_lora_rank": lora, "rope_dim": rd,
                  "page_size": ps, "pages_per_seq": pps, "pool_pages": P,
                  "iters": iters, "backend": jax.default_backend()}
    steps = []
    kernel_err = 0.0
    for occ in OCCUPANCIES:
        live = max(int(pps * occ), 1)
        table = np.full((B, pps), -1, np.int32)
        for b in range(B):
            table[b, :live] = rng.permutation(P)[:live]
        table_j = jnp.asarray(table)
        pos = jnp.full((B,), live * ps - 1, jnp.int32)
        t_scan = _time_it(scan, q, ckv, krope, table_j, pos, iters=iters)
        out_k = mla_paged_decode_attention(q, ckv, krope, table_j, pos,
                                           layer, scale=scale, interpret=True)
        out_s = scan(q, ckv, krope, table_j, pos)
        kernel_err = max(kernel_err, float(jnp.abs(out_k - out_s).max()))
        steps.append({
            "occupancy": occ, "live_pages": live,
            "scan_ms_per_step": round(t_scan * 1e3, 3),
            "tokens_per_s_scan": round(B / t_scan, 1),
            "bytes_latent": B * live * ps * latent_tok_bytes,
            "bytes_dense_expanded": B * pps * ps * expanded_tok_bytes,
        })
    return {"shape": shape_meta, "steps": steps,
            "kernel_interpret_max_abs_err": kernel_err}


def grouped_measured_rows(smoke: bool):
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.paged_attention import (
        group_tile, paged_decode_attention, paged_decode_jnp)

    G = 8                                        # beyond the old auto-cap
    if smoke:
        B, K, hd, ps, pps, iters = 2, 2, 16, 8, 8, 3
    else:
        B, K, hd, ps, pps, iters = 8, 4, 64, 16, 64, 20
    P = B * pps
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(B, K, G, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(1, P, ps, K * hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(1, P, ps, K * hd)), jnp.float32)
    layer = jnp.int32(0)
    scale = hd ** -0.5

    live = max(pps // 2, 1)
    table = np.full((B, pps), -1, np.int32)
    for b in range(B):
        table[b, :live] = rng.permutation(P)[:live]
    table_j = jnp.asarray(table)
    pos = jnp.full((B,), live * ps - 1, jnp.int32)

    scan = jax.jit(functools.partial(paged_decode_jnp, layer=layer,
                                     scale=scale))
    t_scan = _time_it(scan, q, kp, vp, table_j, pos, iters=iters)
    grp = paged_decode_attention(q, kp, vp, table_j, pos, layer, scale=scale,
                                 interpret=True, grouped=True)
    ung = paged_decode_attention(q, kp, vp, table_j, pos, layer, scale=scale,
                                 interpret=True, grouped=False)
    out_s = scan(q, kp, vp, table_j, pos)
    return {
        "shape": {"B": B, "K": K, "G": G, "hd": hd, "page_size": ps,
                  "pages_per_seq": pps, "head_tile": group_tile(K, G, hd),
                  "iters": iters, "backend": jax.default_backend()},
        "scan_ms_per_step": round(t_scan * 1e3, 3),
        "grouped_vs_ungrouped_max_abs_err": float(
            jnp.abs(grp - ung).max()),
        "grouped_vs_scan_max_abs_err": float(jnp.abs(grp - out_s).max()),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes (CI kernel-regression gate)")
    ap.add_argument("--no-write", action="store_true",
                    help="print only; don't rewrite BENCH_serve_decode.json")
    args = ap.parse_args(argv)

    modeled = modeled_rows()
    print("arch,shape,occupancy,GB_reference,GB_kernel,reduction,kernel_AI")
    for r in modeled:
        print(f"{r['arch']},{r['shape']},{r['occupancy']},"
              f"{r['bytes_reference']/1e9:.2f},{r['bytes_kernel']/1e9:.2f},"
              f"{r['reduction_ref_over_kernel']:.1f}x,"
              f"{r['kernel_ai_flops_per_byte']:.2f}")

    measured = measured_rows(args.smoke)
    err = measured["kernel_interpret_max_abs_err"]
    print(f"\nmeasured (backend={measured['shape']['backend']}, "
          f"pool={measured['shape']['pool_pages']} pages):")
    for s in measured["steps"]:
        print(f"  occ={s['occupancy']:<6} ref {s['ref_ms_per_step']:7.2f} ms"
              f"  scan {s['scan_ms_per_step']:7.2f} ms"
              f"  ({s['tokens_per_s_scan']:.0f} tok/s scan, "
              f"bytes {s['bytes_touched_ref']/1e6:.1f} -> "
              f"{s['bytes_touched_scan']/1e6:.1f} MB)")
    print(f"kernel (interpret) vs reference max abs err: {err:.2e}")
    if not (err < 1e-4):
        print("FAIL: kernel drifted from the reference walk")
        return 1

    quarter = [r for r in modeled if r["occupancy"] == 0.25]
    if any(r["reduction_ref_over_kernel"] < 4.0 for r in quarter):
        print("FAIL: <4x modeled reduction at 25% occupancy")
        return 1
    mla_modeled = [r for r in modeled if "reduction_expanded_over_kernel"
                   in r]
    if any(r["reduction_expanded_over_kernel"] < 4.0 for r in mla_modeled):
        print("FAIL: MLA latent walk <4x below the dense-expanded cache")
        return 1

    mla = mla_measured_rows(args.smoke)
    mla_err = mla["kernel_interpret_max_abs_err"]
    print(f"\nmla_measured (backend={mla['shape']['backend']}, "
          f"pool={mla['shape']['pool_pages']} pages):")
    for s in mla["steps"]:
        print(f"  occ={s['occupancy']:<6} scan {s['scan_ms_per_step']:7.2f}"
              f" ms  (latent {s['bytes_latent']/1e6:.2f} MB vs expanded "
              f"{s['bytes_dense_expanded']/1e6:.2f} MB)")
    print(f"mla kernel (interpret) vs scan max abs err: {mla_err:.2e}")
    if not (mla_err < 1e-4):
        print("FAIL: MLA kernel drifted from the latent scan walk")
        return 1

    grouped = grouped_measured_rows(args.smoke)
    gsh = grouped["shape"]
    print(f"\ngrouped_measured G={gsh['G']} K={gsh['K']} "
          f"(head_tile={gsh['head_tile']}): "
          f"scan {grouped['scan_ms_per_step']:.2f} ms, "
          f"grouped-vs-ungrouped err "
          f"{grouped['grouped_vs_ungrouped_max_abs_err']:.2e}, "
          f"grouped-vs-scan err "
          f"{grouped['grouped_vs_scan_max_abs_err']:.2e}")
    if not (grouped["grouped_vs_ungrouped_max_abs_err"] < 1e-4
            and grouped["grouped_vs_scan_max_abs_err"] < 1e-4):
        print("FAIL: grouped G=8 kernel drifted past the old auto-cap")
        return 1

    if not args.no_write and not args.smoke:   # smoke never rewrites the
        OUT.write_text(json.dumps(             # checked-in trajectory file
            {"modeled": modeled, "measured": measured,
             "mla_measured": mla, "grouped_measured": grouped},
            indent=1) + "\n")
        print(f"wrote {OUT}")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
