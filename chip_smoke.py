"""Drive the system's main paths once on a TPU, at full width.

    python3 chip_smoke.py                # one chip: serve, train, platform
    python3 chip_smoke.py --four-chips   # four chips: sharded train and the
                                         # int8 all-reduce, nothing else

One chip, in one process (the only one that touches the chip):

1. serve: qwen3-0.6b at its published widths, bf16, continuous batching
   over the paged cache with prefix caching on, random weights from
   ``--seed``.  Every request must complete; the compiled decode step
   must hold the Pallas kernel (``tpu_custom_call``); one decode step of
   the kernel must agree with the ``lax.scan`` walk on the live cache.
2. train: paper-overhead-100m at its config widths through the training
   CLI's path; the loss must be finite at every step.
3. platform: the serve job of phase 1 submitted to ``DLaaSPlatform``
   with ``real_compute``; it must reach COMPLETED without a restart and
   ship responses byte-equal to phase 1's.

Exits non-zero, printing no result, where JAX finds no TPU or any phase
fails.  The last line of a passing run is one JSON object naming the
device.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# phase 1/3 traffic: 8 slots, 16 requests, prompts of 256-512 tokens,
# 16-32 generated tokens each
SERVE = dict(batch=8, prompt_len=512, gen=32, requests=16)
# without remat the step needs 16.75 GB of the chip's 15.75 GB of HBM;
# saving only the matmul outputs brings its temporaries to 5.6 GB
TRAIN = dict(total_steps=5, global_batch=8, seq_len=1024, remat_policy="dots")
# kernel vs scan, bf16 output: two bf16 ulps at the output's magnitude
KERNEL_TOL = 2.0 ** -6
# four chips: |loss(2x2 mesh) - loss(one chip)| per step, bf16 compute
LOSS_TOL = 2e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def memory_line() -> str:
    import jax
    parts = []
    for d in jax.devices():
        st = d.memory_stats() or {}
        parts.append(f"dev{d.id} in_use={st.get('bytes_in_use', 0)/2**30:.3f}"
                     f" peak={st.get('peak_bytes_in_use', 0)/2**30:.3f} GiB")
    return "; ".join(parts)


def serve_spec(arch: str = "qwen3-0.6b", reduced: bool = False,
               seed: int = 0, **traffic):
    from repro.core.jobspec import JobSpec, ServeSpec
    sv = ServeSpec(**{**SERVE, **traffic}, reduced=reduced, real_compute=True)
    # a crash in the platform's pod must fail the job, not restart it
    return JobSpec(name="chip-smoke-serve", kind="serve", framework=arch,
                   seed=seed, max_restarts=0, serve=sv)


def train_spec(arch: str = "paper-overhead-100m", reduced: bool = False,
               seed: int = 0, **shape):
    from repro.core.jobspec import JobSpec, TrainSpec
    tr = TrainSpec(**{**TRAIN, **shape}, reduced=reduced, log_every=1)
    return JobSpec(name="chip-smoke-train", kind="train", framework=arch,
                   seed=seed, train=tr)


def _layer0_pools(cache):
    """Layer 0's (k_pages, v_pages) of a paged cache tree."""
    import jax
    found = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        key = getattr(path[-1], "key", None)
        if key in ("k_pages", "v_pages") and key not in found:
            stacked = any(getattr(p, "key", None) == "groups" for p in path)
            found[key] = leaf[0] if stacked else leaf
    return found["k_pages"], found["v_pages"]


def decode_kernel_error(engine, seed: int = 0) -> float:
    """Max abs difference, over active slots, between the Pallas paged
    decode kernel and the ``lax.scan`` walk on the engine's live cache."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ops import paged_decode_bhd
    from repro.kernels.paged_attention import paged_decode_jnp

    cfg = engine.cfg
    B, H, K, hd = engine.B, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    kp, vp = _layer0_pools(engine.cache)
    # the newest position each slot has written (the cache holds < pos)
    pos = jnp.asarray(np.where(engine.pos > 0, engine.pos - 1, -1),
                      jnp.int32)
    pt = jnp.asarray(engine.host_table, jnp.int32)
    q = jax.random.normal(jax.random.key(seed), (B, 1, H, hd), kp.dtype)
    ker = paged_decode_bhd(q, kp, vp, pt, pos, scale=hd ** -0.5)
    ref = paged_decode_jnp(q.reshape(B, K, H // K, hd), kp, vp, pt, pos,
                           scale=hd ** -0.5).reshape(B, 1, H, hd)
    act = np.asarray(pos) >= 0
    if not act.any():
        raise RuntimeError("no active slot to compare the kernel on")
    ker = np.asarray(ker, np.float32)[act]
    ref = np.asarray(ref, np.float32)[act]
    err = float(np.abs(ker - ref).max())
    scale = max(1.0, float(np.abs(ref).max()))
    log(f"[serve] kernel vs scan on the live cache: max|err|={err:.3e} "
        f"(max|ref|={float(np.abs(ref).max()):.3e}, "
        f"tol={KERNEL_TOL * scale:.3e}, {int(act.sum())} active slots)")
    if not err <= KERNEL_TOL * scale:
        raise RuntimeError("decode kernel disagrees with the scan walk")
    return err


def decode_hlo(engine) -> str:
    """Optimized HLO of the engine's decode step at its current shapes."""
    import jax.numpy as jnp
    return engine.decode.lower(
        engine.params, {"tokens": jnp.asarray(engine.toks)}, engine.cache,
        jnp.asarray(engine.pos, jnp.int32)).compile().as_text()


def run_serve(spec) -> dict:
    """The direct engine path (``RealServePayload`` → ``ServingEngine``),
    timed; returns its responses and what was checked."""
    import statistics

    import jax

    from repro.launch.engine import RealServePayload

    sv = spec.serve
    t0 = time.perf_counter()
    engine, requests = RealServePayload(spec).build()
    jax.block_until_ready(engine.params)
    cfg = engine.cfg
    log(f"[serve] {cfg.name}: layers={cfg.num_layers} d_model={cfg.d_model} "
        f"heads={cfg.num_heads}/{cfg.num_kv_heads} head_dim={cfg.head_dim} "
        f"vocab={cfg.vocab_size} dtype={engine.ctx.dtype.__name__} "
        f"decode={'pallas' if engine.ctx.use_pallas else 'jnp-scan'} "
        f"prefix_cache={engine.prefix_cache} page_size={engine.ps} "
        f"pool={engine.pool.n_pages} pages")
    log(f"[serve] build (params + cache) {time.perf_counter() - t0:.2f} s")
    for r in requests:
        engine.submit(r)

    # the first admission round and decode step compile
    t0 = time.perf_counter()
    engine.admit()
    log(f"[serve] first admission round (prefill, incl. compile) "
        f"{time.perf_counter() - t0:.2f} s")
    t0 = time.perf_counter()
    engine.step()
    log(f"[serve] first decode step (incl. compile) "
        f"{time.perf_counter() - t0:.2f} s")
    err = decode_kernel_error(engine, spec.seed)
    has_kernel = "tpu_custom_call" in decode_hlo(engine)
    log(f"[serve] compiled decode step holds tpu_custom_call: {has_kernel}")

    step_s, admit_s = [], []

    def timed(fn, sink):
        def call():
            t = time.perf_counter()
            out = fn()
            sink.append(time.perf_counter() - t)
            return out
        return call

    engine.step = timed(engine.step, step_s)
    engine.admit = timed(engine.admit, admit_s)
    t0 = time.perf_counter()
    try:
        engine.run()
        jax.block_until_ready(engine.cache)
    finally:
        # the wrappers hold the engine: drop them so its parameters and
        # cache leave the chip with it, before the next phase
        del engine.step, engine.admit
    wall = time.perf_counter() - t0
    n_done = len(engine.responses)
    if step_s:
        q = sorted(step_s)
        log(f"[serve] later decode steps: n={len(q)} "
            f"median={1e3 * statistics.median(q):.2f} ms "
            f"p90={1e3 * q[int(0.9 * (len(q) - 1))]:.2f} ms "
            f"max={1e3 * q[-1]:.2f} ms; admission rounds n={len(admit_s)} "
            f"total={sum(admit_s):.2f} s")
    log(f"[serve] completed {n_done}/{sv.requests} requests, "
        f"{engine.generated} tokens in {engine.decode_steps} decode steps; "
        f"prefill {engine.prefill_tokens} tokens, cached "
        f"{engine.cached_tokens}; rest of the run {wall:.2f} s")
    if n_done != sv.requests:
        raise RuntimeError(f"only {n_done}/{sv.requests} requests completed")
    return {"responses": dict(engine.responses), "kernel_err": err,
            "has_kernel": has_kernel, "use_pallas": engine.ctx.use_pallas}


def run_train(spec) -> None:
    """The training CLI's path (``launch.executor.execute``); it returns
    non-zero when a step's loss is not finite."""
    from repro.launch.executor import execute
    rc = execute(spec)
    if rc != 0:
        raise RuntimeError(f"training returned {rc}")


def run_platform(spec, direct: dict) -> None:
    """The same serve job through ``DLaaSPlatform.submit``: COMPLETED, no
    restart, responses byte-equal to the direct engine's."""
    from benchmarks.platform_serve import run_platform as submit_and_wait
    wall, responses, stats = submit_and_wait(spec)
    log(f"[platform] COMPLETED in {wall:.2f} s wall "
        f"(virtual {stats['virtual_s']} s), restarts {stats['restarts']}")
    if stats["restarts"]:
        raise RuntimeError(f"the serve pod restarted {stats['restarts']}x")
    same = responses == direct
    log(f"[platform] {len(responses)} responses byte-equal to the direct "
        f"engine: {same}")
    if not same:
        raise RuntimeError("platform responses differ from the direct engine")


def run_four_chips(cfg, *, batch: int, seq: int, steps: int = 3,
                   seed: int = 0) -> None:
    """``make_train_step`` under a 2x2 ("data", "model") mesh against the
    same global batch on one chip, then the int8 all-reduce against the
    dense psum over four devices."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.configs import RunConfig
    from repro.data.pipeline import SyntheticLMData
    from repro.dist.compression import (
        CompressionConfig, allreduce_int8_bound, sharded_allreduce_int8)
    from repro.dist.mesh import make_device_mesh, make_host_mesh
    from repro.dist.sharding import DEFAULT_RULES
    from repro.launch.specs import batch_shardings, state_shardings
    from repro.models.layers import Ctx
    from repro.train.steps import init_train_state, make_train_step

    log(f"[four-chips] jax.device_count()={jax.device_count()}")
    run = RunConfig(learning_rate=1e-3, warmup_steps=1, total_steps=steps,
                    remat_policy=TRAIN["remat_policy"])
    data = SyntheticLMData(cfg.vocab_size, seq, batch, seed)
    state0 = init_train_state(cfg, jax.random.key(seed), run)

    bspec = jax.eval_shape(lambda: data.batch_at(0))

    def losses_on(mesh):
        # the state enters on the shardings the step returns it on, so
        # the step compiles once
        step = make_train_step(cfg, Ctx(mesh=mesh, dtype=jnp.bfloat16), run)
        ssh = state_shardings(cfg, mesh, DEFAULT_RULES, run)
        fn = jax.jit(step, out_shardings=(ssh, None), in_shardings=(
            ssh, batch_shardings(bspec, mesh, DEFAULT_RULES)))
        state = jax.device_put(state0, ssh)
        out = []
        for i in range(steps):
            t = time.perf_counter()
            state, m = fn(state, data.batch_at(i))
            out.append(float(m["loss"]))
            log(f"[four-chips]   {mesh.devices.shape} step {i} loss "
                f"{out[-1]:.6f} ({time.perf_counter() - t:.2f} s)")
        log(f"[four-chips] memory after {mesh.devices.shape}: "
            f"{memory_line()}")
        return out

    one = losses_on(make_host_mesh())
    four = losses_on(make_host_mesh(data=2, model=2))
    diff = [abs(a - b) for a, b in zip(one, four)]
    ok = all(math.isfinite(x) for x in one + four) and max(diff) <= LOSS_TOL
    log(f"[four-chips] 2x2 vs one chip: max|dloss|={max(diff):.3e} "
        f"(tol {LOSS_TOL:g}) per step {['%.3e' % d for d in diff]}")
    if not ok:
        raise RuntimeError("sharded training disagrees with one chip")

    # int8 all-reduce: per-device magnitudes 100x apart, so the shared
    # (pmax) scale is load-bearing
    ndev = 4
    rng = np.random.default_rng(seed + 11)
    mags = np.array([0.03, 0.5, 1.0, 3.0])[:, None, None]
    x = (rng.normal(size=(ndev, 1024, 768)) * mags).astype(np.float32)
    ccfg = CompressionConfig(chunk_size=256)
    dmesh = make_device_mesh(data=ndev)
    got = np.asarray(sharded_allreduce_int8(jnp.asarray(x), dmesh,
                                            axis="data", cfg=ccfg))
    dense = np.asarray(jax.shard_map(
        lambda r: jax.lax.psum(r[0], "data"), mesh=dmesh,
        in_specs=P("data"), out_specs=P())(jnp.asarray(x)))
    bound = allreduce_int8_bound(x, ccfg)
    err = np.abs(got - dense)
    # fp32 slack for the dense psum's own rounding
    slack = 1e-6 * (1.0 + np.abs(dense))
    log(f"[four-chips] int8 all-reduce vs dense psum: max|err|="
        f"{err.max():.3e}, max err/bound={float((err / bound).max()):.3f}, "
        f"rel={err.max() / np.abs(dense).max():.3e}")
    if not (err <= bound + slack).all():
        raise RuntimeError("int8 all-reduce outside its documented bound")
    log(f"[four-chips] memory: {memory_line()}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded-train and int8 "
                         "all-reduce phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: the repository's src/repro is not beside "
              f"{Path(__file__).name}", file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} TPU devices, found {len(devices)}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}")

    failed = []

    def phase(name, fn, *a, **kw):
        log(f"== {name}")
        t = time.perf_counter()
        try:
            out = fn(*a, **kw)
        except Exception:  # noqa: BLE001 - report every failing phase
            traceback.print_exc()
            failed.append(name)
            log(f"== {name}: FAILED after {time.perf_counter() - t:.2f} s")
            return None
        log(f"== {name}: ok in {time.perf_counter() - t:.2f} s; "
            f"memory {memory_line()}")
        return out

    if args.four_chips:
        from repro.configs import get_config
        phase("four-chips", run_four_chips, get_config("paper-overhead-100m"),
              batch=8, seq=1024, seed=args.seed)
    else:
        spec = serve_spec(seed=args.seed)
        direct = phase("serve", run_serve, spec)
        if direct is not None:
            if not direct["use_pallas"] or not direct["has_kernel"]:
                log("[serve] the decode step did not run the Pallas kernel")
                failed.append("serve-kernel")
        phase("train", run_train, train_spec(seed=args.seed))
        if direct is None:
            failed.append("platform (no direct responses)")
        else:
            phase("platform", run_platform, spec, direct["responses"])

    if failed:
        log(f"chip_smoke: FAILED phases {failed}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
