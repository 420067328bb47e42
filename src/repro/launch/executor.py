"""One executor for every job kind — local execution of a ``JobSpec``.

The three launch CLIs (``train``, ``serve``, ``dryrun``) are thin
parse-to-spec layers over this module: each builds a validated
``JobSpec`` and hands it to :func:`execute`, which dispatches on
``spec.kind``.  The exact same spec can instead be submitted to the
platform (``DLaaSPlatform.submit``) where the Guardian runs it under the
full dependability machinery — one resource model, two run paths.

Continuous-batching serving lives in :class:`repro.launch.engine.
ServingEngine` (resumable admit/step/finish/snapshot/restore state
machine); :func:`run_continuous` here is the thin CLI driver over it.
``PagePool`` moved to ``repro.launch.engine`` and is re-exported here (and
from ``repro.launch.serve``) for compatibility.
"""
from __future__ import annotations

import dataclasses
import math
import subprocess
import sys
import time

from repro.core.jobspec import (
    FrameworkRegistry, JobSpec, ServeSpec, resolve_cells)
from repro.launch.engine import (  # noqa: F401  (PagePool: compat re-export)
    PagePool, ServingEngine, synthesize_requests)


def execute(spec: JobSpec) -> int:
    """Validate and run a JobSpec locally; returns a process exit code."""
    err = spec.validate(FrameworkRegistry.default())
    if err:
        raise SystemExit(f"invalid JobSpec: {err}")
    if spec.kind == "train":
        return _run_train(spec)
    if spec.kind == "serve":
        return _run_serve(spec)
    return _run_dryrun(spec)


def _make_mesh(name: str):
    from repro.launch.mesh import make_host_mesh, make_production_mesh
    return {"host": make_host_mesh,
            "prod": make_production_mesh,
            "multipod": lambda: make_production_mesh(multi_pod=True)}[name]()


# ---------------------------------------------------------------------------
# kind = train
# ---------------------------------------------------------------------------
def _run_train(spec: JobSpec) -> int:
    import jax
    import jax.numpy as jnp

    from repro.configs import RunConfig, get_config
    from repro.data.pipeline import SyntheticLMData
    from repro.launch.specs import state_shardings
    from repro.models.layers import Ctx
    from repro.train.steps import init_train_state, make_train_step

    t = spec.train
    cfg = get_config(spec.framework)
    if t.reduced:
        cfg = cfg.reduced()
    mesh = _make_mesh(t.mesh)
    ctx = Ctx(mesh=mesh, dtype=jnp.float32 if t.reduced else jnp.bfloat16,
              use_pallas=t.use_pallas)
    run = RunConfig(num_microbatches=t.num_microbatches,
                    remat_policy=t.remat_policy,
                    learning_rate=t.learning_rate,
                    warmup_steps=max(t.total_steps // 20, 1),
                    total_steps=t.total_steps)
    # on the mesh from the start: the step returns its state there, and a
    # first input placed otherwise would compile the step twice
    state = jax.device_put(init_train_state(cfg, jax.random.key(spec.seed),
                                            run),
                           state_shardings(cfg, mesh, run=run))
    data = SyntheticLMData(cfg.vocab_size, t.seq_len, t.global_batch,
                           spec.seed)
    step = jax.jit(make_train_step(cfg, ctx, run), donate_argnums=(0,))

    n_params = sum(x.size for x in jax.tree.leaves(state["params"]))
    print(f"[train] arch={cfg.name} params={n_params/1e6:.1f}M "
          f"mesh={mesh.devices.shape} devices={mesh.devices.size} "
          f"backend={jax.default_backend()}")
    losses = []
    step_s = []
    for i in range(t.total_steps):
        t0 = time.perf_counter()
        state, m = step(state, data.batch_at(i))
        losses.append(float(m["loss"]))          # waits for the step
        step_s.append(time.perf_counter() - t0)
        if i % t.log_every == 0 or i == t.total_steps - 1:
            print(f"  step {i:5d}  loss {losses[-1]:.4f}  "
                  f"gnorm {float(m['grad_norm']):.3f}  "
                  f"lr {float(m['lr']):.2e}  {step_s[-1]*1e3:.1f} ms")
    tok = t.global_batch * t.seq_len
    steady = step_s[1:]
    print(f"[train] first step (trace + compile) {step_s[0]:.2f} s"
          + (f"; steady {1e3*sum(steady)/len(steady):.1f} ms/step "
             f"({tok*len(steady)/sum(steady):.0f} tok/s)" if steady else ""))
    bad = [i for i, x in enumerate(losses) if not math.isfinite(x)]
    if bad:
        print(f"[train] FAIL: non-finite loss at step(s) {bad}")
        return 1
    return 0


# ---------------------------------------------------------------------------
# kind = serve
# ---------------------------------------------------------------------------
def run_lockstep(cfg, ctx, params, sv: ServeSpec) -> int:
    """Batched prefill + lockstep greedy decode (dense or paged layout)."""
    import jax
    import jax.numpy as jnp

    from repro.models.model import init_cache
    from repro.train.steps import make_serve_steps

    B, P, G = sv.batch, sv.prompt_len, sv.gen
    max_len = P + G
    prompts = jax.random.randint(jax.random.key(1), (B, P), 0, cfg.vocab_size)
    batch = {"tokens": prompts}
    src_len = 0
    if cfg.is_encoder_decoder:
        src_len = max(P // 4, 16)
        batch["src_embeds"] = 0.02 * jax.random.normal(
            jax.random.key(2), (B, src_len, cfg.d_model))

    prefill, decode = make_serve_steps(cfg, ctx)
    cache = init_cache(cfg, B, max_len, src_len=src_len)

    t0 = time.perf_counter()
    logits, cache = prefill(params, batch, cache)[:2]
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
    t_prefill = time.perf_counter() - t0

    out = [tok]
    t0 = time.perf_counter()
    for t in range(P, P + G - 1):
        logits, cache = decode(params, {"tokens": tok}, cache,
                               jnp.int32(t))[:2]
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
        out.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.perf_counter() - t0

    gen = jnp.concatenate(out, axis=1)
    print(f"[serve] arch={cfg.name} layout={cfg.cache_layout} "
          f"batch={B} prompt={P} gen={G}")
    print(f"  prefill: {t_prefill*1e3:.1f} ms "
          f"({B*P/t_prefill:.0f} tok/s incl. compile)")
    print(f"  decode:  {t_decode*1e3:.1f} ms "
          f"({B*(G-1)/max(t_decode,1e-9):.0f} tok/s incl. compile)")
    print(f"  sample continuations: {gen[:2, :10].tolist()}")
    return 0


def run_continuous(cfg, ctx, params, sv: ServeSpec, seed: int = 0) -> int:
    """Continuous batching over the paged cache: the CLI driver over
    :class:`repro.launch.engine.ServingEngine`.  Synthesizes the request
    workload, drains the engine, prints the summary — all batching,
    admission (conservative or optimistic via ``sv.overcommit``),
    eviction/requeue and paging semantics live in the engine."""
    import jax

    try:
        engine = ServingEngine(cfg, ctx, params, sv)
    except ValueError as e:          # CLI contract: bad flags exit nonzero
        raise SystemExit(str(e)) from e
    n_req = sv.requests
    t0 = time.perf_counter()
    for request in synthesize_requests(cfg, sv, seed, engine.ragged):
        engine.submit(request)
    engine.run()

    jax.block_until_ready(engine.cache)
    dt = time.perf_counter() - t0
    print(f"[serve/continuous] arch={cfg.name} requests={n_req} "
          f"slots={engine.B} prompt<= {sv.prompt_len} gen<= {sv.gen} "
          f"page_size={engine.ps} "
          f"prefill={'ragged' if engine.ragged else 'per-slot'} "
          f"decode={'pallas' if ctx.use_pallas else 'jnp-scan'}")
    print(f"  pool: {engine.pool.n_pages} pages, high-water "
          f"{engine.pool.high_water}, admission stalls "
          f"{engine.stalled_admissions}, evictions {engine.evictions} "
          f"(overcommit {engine.overcommit:g})")
    if engine.prefix_cache:
        total = engine.prefill_tokens + engine.cached_tokens
        print(f"  prefix cache: {engine.prefix_hits} hits / "
              f"{engine.prefix_misses} misses, {engine.cached_tokens}/"
              f"{total} prompt tokens served from cache, "
              f"{engine.cow_copies} CoW copies")
    print(f"  completed {len(engine.responses)}/{n_req} in "
          f"{engine.decode_steps} decode steps, "
          f"{dt*1e3:.1f} ms ({engine.generated/max(dt,1e-9):.0f} tok/s "
          f"incl. compile)")
    assert len(engine.responses) == n_req, (len(engine.responses), n_req)
    return 0


def serve_ctx(sv: ServeSpec):
    """The forward-pass context a serve job runs under.  Kernels follow
    the backend (Pallas on TPU, the scan walk elsewhere) unless the spec
    says otherwise."""
    import jax.numpy as jnp

    from repro.kernels.ops import on_tpu
    from repro.models.layers import Ctx
    return Ctx(mesh=_make_mesh(sv.mesh),
               dtype=jnp.float32 if sv.reduced else jnp.bfloat16,
               use_pallas=on_tpu() if sv.use_pallas is None
               else sv.use_pallas)


def _run_serve(spec: JobSpec) -> int:
    import jax

    from repro.configs import get_config
    from repro.models.params import init_params

    sv = spec.serve
    cfg = get_config(spec.framework)
    if sv.reduced:
        cfg = cfg.reduced()
    overrides = {}
    if sv.cache_layout:
        overrides["cache_layout"] = sv.cache_layout
    if sv.continuous and "cache_layout" not in overrides:
        overrides["cache_layout"] = "paged"
    if sv.page_size:
        overrides["page_size"] = sv.page_size
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)

    ctx = serve_ctx(sv)
    params = init_params(cfg, jax.random.key(spec.seed))

    if sv.continuous:
        return run_continuous(cfg, ctx, params, sv, seed=spec.seed)
    return run_lockstep(cfg, ctx, params, sv)


# ---------------------------------------------------------------------------
# kind = dryrun
# ---------------------------------------------------------------------------
def _run_dryrun(spec: JobSpec) -> int:
    """Run the sweep cells, one subprocess each (isolation: every cell gets
    a fresh XLA with the 512 fake-host-device flag).  Cached cells are
    skipped unless the spec says ``force`` — the sweep is resumable."""
    from repro.launch import dryrun as dr_mod

    dr = spec.dryrun
    dr_mod.ARTIFACTS.mkdir(parents=True, exist_ok=True)
    failures = 0
    for cell in resolve_cells(dr):
        out = dr_mod.cell_path(cell.arch, cell.shape, cell.multi_pod)
        if out.exists() and not dr.force:
            print(f"[dryrun] cached: {out}")
            continue
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--cell-worker",
               "--arch", cell.arch, "--shape", cell.shape]
        if cell.multi_pod:
            cmd.append("--multi-pod")
        if dr.force:
            cmd.append("--force")
        print(f"[dryrun] {cell.arch} × {cell.shape} × {cell.mesh_name} ...",
              flush=True)
        r = subprocess.run(cmd, timeout=dr.timeout_s)
        if r.returncode:
            failures += 1
    return 1 if failures else 0
