"""Fig. 4 analog: per-component crash-recovery time.

The paper kills each component with kubectl and reports seconds to recover
(API 3-5, LCM 4-6, Guardian 1-2, Helper 3-4, Learner 10-20).  We do the
same against the virtual-time platform: kill the pod, measure virtual
seconds until the replacement is RUNNING.  Additionally we report the REAL
wall-clock cost of the learner's state restore (checkpoint download/load +
re-jit), which the paper attributes the learner's longer recovery to.

Output rows: component,recover_s_min,recover_s_max,paper_range
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import RunConfig, get_config
from repro.core import DLaaSPlatform
from repro.core.checkpoint import CheckpointManager
from repro.core.jobspec import JobSpec, Resources, TrainSpec
from repro.core.objectstore import ObjectStore
from repro.data.pipeline import SyntheticLMData
from repro.models.layers import Ctx
from repro.train.steps import init_train_state, make_train_step

PAPER = {"api": "3-5s", "lcm": "4-6s", "guardian": "1-2s",
         "helper": "3-4s", "learner": "10-20s"}


def measure_component(component: str, trials: int = 5):
    times = []
    for t in range(trials):
        p = DLaaSPlatform(seed=100 + t)
        p.run(10)
        h = p.submit(JobSpec(
            name="r",
            resources=Resources(replicas=2, gpus_per_replica=1),
            max_restarts=50,
            train=TrainSpec(total_steps=10_000, step_time_s=0.5,
                            checkpoint_interval_s=20)))
        p.run(40)           # fully deployed and training
        pod = {"api": "api-0", "lcm": "lcm-0",
               "guardian": f"guardian-{h.job_id}",
               "helper": f"helper-{h.job_id}",
               "learner": f"learner-{h.job_id}-0"}[component]
        t0 = p.sim.now
        assert p.kill_pod(pod), pod
        p.run(60)
        rt = p.recovery_time(pod, t0)
        if rt is not None:
            times.append(rt)
    return times


def learner_restore_wallclock():
    """Real work on restart: checkpoint load + re-jit + first step."""
    cfg = get_config("paper-overhead-100m").reduced()
    run_cfg = RunConfig(learning_rate=1e-3, warmup_steps=5, total_steps=100)
    data = SyntheticLMData(cfg.vocab_size, 64, 8, seed=0)
    state = init_train_state(cfg, jax.random.key(0), run_cfg)
    step = jax.jit(make_train_step(cfg, Ctx(dtype=jnp.float32), run_cfg))
    for i in range(5):
        state, m = step(state, data.batch_at(i))
    store = ObjectStore()
    ck = CheckpointManager(store, "restore-bench")
    ck.save(5, jax.tree.map(np.asarray, state))

    t0 = time.perf_counter()
    _, restored = ck.load()
    state2 = jax.tree.map(lambda c, n: jnp.asarray(n).astype(c.dtype),
                          state, restored)
    step2 = jax.jit(make_train_step(cfg, Ctx(dtype=jnp.float32), run_cfg))
    state2, m = step2(state2, data.batch_at(5))
    jax.block_until_ready(m["loss"])
    return time.perf_counter() - t0


def run():
    rows = []
    for comp in ("api", "lcm", "guardian", "helper", "learner"):
        ts = measure_component(comp)
        rows.append((comp, min(ts), max(ts), PAPER[comp]))
    return rows


def main():
    print("component,recover_s_min,recover_s_max,paper_range")
    for comp, lo, hi, paper in run():
        print(f"{comp},{lo:.1f},{hi:.1f},{paper}")
    print(f"learner_restore_wallclock_s,"
          f"{learner_restore_wallclock():.2f},,real CPU (load+rejit+step)")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
