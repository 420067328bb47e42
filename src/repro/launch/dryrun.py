import os
# a CPU-only compile tool: 512 fake host devices, never the chip
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: XLA SPMD must partition every step function over the production
meshes, the per-device memory must fit the 16 GB HBM of a TPU v5e, and the
compiled HLO yields the FLOP/byte/collective terms for §Roofline.

Each cell writes ``artifacts/dryrun/<arch>__<shape>__<mesh>.json`` and is
skipped when that file exists (the sweep is resumable; use --force to
recompute).  The CLI is a parse-to-spec layer: flags become a
``JobSpec(kind="dryrun")`` that the shared executor runs, one subprocess
per cell for isolation (the hidden ``--cell-worker`` entry).
"""
import argparse
import json
import re
import sys
import time
import traceback
from pathlib import Path

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

# TPU v5e constants (per chip)
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s per link

_DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "c64": 8, "f32": 4, "s32": 4,
                "u32": 4, "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
                "s8": 1, "u8": 1, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def cost_dict(compiled) -> dict:
    """``Compiled.cost_analysis()``, empty when XLA reports nothing."""
    return compiled.cost_analysis() or {}


def parse_collectives(hlo_text: str) -> dict:
    """Sum output bytes of every collective op in the optimized HLO.

    Returns {op_kind: {"count": n, "bytes": total_output_bytes,
                       "wire_bytes": est. bytes moved per device}}.
    """
    shape_re = re.compile(r"(\w+)\[([0-9,]*)\]")
    group_re = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")
    group_expl_re = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
    out: dict = {k: {"count": 0, "bytes": 0, "wire_bytes": 0.0}
                 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = re.search(r"=\s*(.*?)\s+(all-reduce|all-gather|reduce-scatter|"
                      r"all-to-all|collective-permute)(-start)?\(", line)
        if not m:
            continue
        shapes_part, op = m.group(1), m.group(2)
        nbytes = 0
        for dt, dims in shape_re.findall(shapes_part):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        g = group_re.search(line)
        if g:
            gsize = int(g.group(2))
        else:
            g2 = group_expl_re.search(line)
            gsize = len(g2.group(1).split(",")) if g2 else 2
        # ring-algorithm wire bytes per participating device
        if op == "all-reduce":
            wire = 2 * nbytes * (gsize - 1) / max(gsize, 1)
        elif op == "all-gather":
            wire = nbytes * (gsize - 1) / max(gsize, 1)
        elif op == "reduce-scatter":
            wire = nbytes * (gsize - 1)          # nbytes is the shard output
        elif op == "all-to-all":
            wire = nbytes * (gsize - 1) / max(gsize, 1)
        else:                                     # collective-permute
            wire = nbytes
        out[op]["count"] += 1
        out[op]["bytes"] += nbytes
        out[op]["wire_bytes"] += wire
    return {k: v for k, v in out.items() if v["count"]}


def build_lowered(arch: str, shape_name: str, multi_pod: bool,
                  rules=None, cfg_override=None, run_override=None,
                  scan_unroll: bool = False,
                  constrain_scan_weights: bool = False):
    """Lower the right step function for one cell.  Heavy imports are local
    so `--all` subprocess dispatch stays cheap."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import SHAPES, get_config, get_run_config, shape_applicable
    from repro.dist.sharding import DEFAULT_RULES
    from repro.launch.mesh import make_production_mesh
    from repro.launch import specs as S
    from repro.models.layers import Ctx
    from repro.train.steps import (
        make_decode_step, make_prefill_step, make_train_step)

    cfg = cfg_override if cfg_override is not None else get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return None, {"skipped": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    run = run_override if run_override is not None \
        else get_run_config(arch, shape_name)
    if rules is None:
        rules = DEFAULT_RULES
        if run.sharding_overrides:
            rules = rules.override(
                **{k: v for k, v in run.sharding_overrides})
    ctx = Ctx(mesh=mesh, rules=rules, dtype=jnp.bfloat16,
              scan_unroll=scan_unroll,
              constrain_scan_weights=constrain_scan_weights)
    kind = shape.kind

    bs = S.batch_specs(cfg, shape, kind)
    bsh = S.batch_shardings(bs, mesh, rules)
    rep = NamedSharding(mesh, P())

    if kind == "train":
        step = make_train_step(cfg, ctx, run)
        ssp = S.state_specs(cfg, run)
        ssh = S.state_shardings(cfg, mesh, rules, run)
        fn = jax.jit(step, in_shardings=(ssh, bsh),
                     out_shardings=(ssh, None), donate_argnums=(0,))
        lowered = fn.lower(ssp, bs)
    elif kind == "prefill":
        step = make_prefill_step(cfg, ctx)
        psp = S.param_specs(cfg, serve=True)
        psh = S.param_shardings(cfg, mesh, rules)
        csp = S.cache_specs(cfg, shape, run)
        csh = S.cache_shardings(cfg, shape, mesh, rules, run)
        fn = jax.jit(step, in_shardings=(psh, bsh, csh),
                     out_shardings=(None, csh), donate_argnums=(2,))
        lowered = fn.lower(psp, bs, csp)
    else:  # decode
        step = make_decode_step(cfg, ctx)
        psp = S.param_specs(cfg, serve=True)
        psh = S.param_shardings(cfg, mesh, rules)
        csp = S.cache_specs(cfg, shape, run)
        csh = S.cache_shardings(cfg, shape, mesh, rules, run)
        fn = jax.jit(step, in_shardings=(psh, bsh, csh, rep),
                     out_shardings=(None, csh), donate_argnums=(2,))
        lowered = fn.lower(psp, bs, csp,
                           jax.ShapeDtypeStruct((), jnp.int32))

    meta = {"arch": arch, "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "kind": kind, "n_devices": mesh.devices.size,
            "seq_len": shape.seq_len, "global_batch": shape.global_batch,
            "num_microbatches": run.num_microbatches,
            "remat_policy": run.remat_policy,
            # pre-compile placement estimate from the sharding trees —
            # cross-check against compiled argument_size_in_bytes
            "analytic": S.placement_report(cfg, shape, run, mesh, rules)}
    return lowered, meta


def run_cell(arch: str, shape_name: str, multi_pod: bool) -> dict:
    from repro.configs import get_config
    from repro.models.model import count_params

    t0 = time.perf_counter()
    lowered, meta = build_lowered(arch, shape_name, multi_pod)
    if lowered is None:
        return {"ok": True, **meta}
    t_lower = time.perf_counter() - t0

    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0 - t_lower

    mem = compiled.memory_analysis()
    mem_rec = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes",
                 "alias_size_in_bytes"):
        if hasattr(mem, attr):
            mem_rec[attr] = int(getattr(mem, attr))

    cost = cost_dict(compiled)
    cost_rec = {k: float(v) for k, v in cost.items()
                if isinstance(v, (int, float)) and k in
                ("flops", "bytes accessed", "transcendentals",
                 "utilization operand 0 {}", "bytes accessed output {}")}
    colls = parse_collectives(compiled.as_text())

    cfg = get_config(arch)
    meta.update(
        ok=True,
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        memory=mem_rec,
        cost=cost_rec,
        collectives=colls,
        n_params=count_params(cfg),
        n_params_active=count_params(cfg, active_only=True),
    )
    return meta


def cell_path(arch: str, shape_name: str, multi_pod: bool) -> Path:
    mesh = "2x16x16" if multi_pod else "16x16"
    return ARTIFACTS / f"{arch}__{shape_name}__{mesh}.json"


def all_cells():
    from repro.configs import SHAPES, get_config, list_configs, shape_applicable
    for arch in list_configs():
        if arch == "paper-overhead-100m":
            continue
        for shape_name in SHAPES:
            yield arch, shape_name


def _run_cell_worker(args) -> int:
    """In-process single-cell execution (the subprocess entry the shared
    executor dispatches to; isolation keeps each cell's XLA fresh)."""
    assert args.arch and args.shape, "--arch and --shape required"
    out = cell_path(args.arch, args.shape, args.multi_pod)
    if out.exists() and not args.force:
        print(f"[dryrun] cached: {out}")
        return 0
    try:
        rec = run_cell(args.arch, args.shape, args.multi_pod)
    except Exception as e:
        rec = {"ok": False, "arch": args.arch, "shape": args.shape,
               "mesh": "2x16x16" if args.multi_pod else "16x16",
               "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
        out.write_text(json.dumps(rec, indent=2))
        print(json.dumps({k: rec[k] for k in ("ok", "arch", "shape", "error")},
                         indent=2))
        return 1
    out.write_text(json.dumps(rec, indent=2))
    brief = {k: rec.get(k) for k in
             ("ok", "arch", "shape", "mesh", "compile_s", "memory", "skipped")}
    print(json.dumps(brief, indent=2))
    return 0


def parse_spec(argv=None):
    """Parse CLI flags into a ``JobSpec(kind="dryrun")`` (plus the raw
    args, for the hidden --cell-worker plumbing)."""
    from repro.core.jobspec import DryRunSpec, JobSpec, Resources, SweepCell

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every cell (both meshes) in subprocesses")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--timeout", type=int, default=3600)
    ap.add_argument("--cell-worker", action="store_true",
                    help=argparse.SUPPRESS)     # executor's subprocess entry
    args = ap.parse_args(argv)

    if args.all:
        cells, sweep_all = (), True
    else:
        assert args.arch and args.shape, "--arch and --shape required"
        cells = (SweepCell(args.arch, args.shape, args.multi_pod),)
        sweep_all = False
    spec = JobSpec(
        name="dryrun-all" if args.all else f"dryrun-{args.arch}",
        kind="dryrun",
        framework=args.arch or "paper-overhead-100m",
        resources=Resources(replicas=1, gpus_per_replica=0),
        dryrun=DryRunSpec(cells=cells, sweep_all=sweep_all,
                          force=args.force, timeout_s=args.timeout))
    return spec, args


def main(argv=None) -> int:
    spec, args = parse_spec(argv)
    if args.cell_worker:
        ARTIFACTS.mkdir(parents=True, exist_ok=True)
        return _run_cell_worker(args)
    from repro.launch.executor import execute
    return execute(spec)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
