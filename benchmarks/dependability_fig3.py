"""Fig. 3 analog: the dependability/efficiency trade-off — plus the
self-healing chaos lane.

**Overhead section** (default).  The paper's Fig. 3 compares DLaaS on
commodity hardware against a bare DGX-1 (≈3–14% slower) and argues the gap
buys dependability.  Our analog measures the cost of ARMING the
dependability features on the same hardware: a minimally-instrumented loop
vs a fully-armed one (synchronous quorum status every step + frequent real
checkpoints to the object store with sha256 integrity).  The fully-armed
config bounds lost work at one checkpoint interval; the measured % slowdown
is the price.

Output rows: config,steps_s,overhead_pct_vs_minimal,ckpt_bytes

**Chaos lane** (``--chaos``).  Scripted ``FaultPlan`` injection against the
virtual-time platform, one scenario per failure class the self-healing
Guardian knows how to classify and repair:

    scenario        injected fault            expected classification/repair
    oom             learner OOM gate          OOM → reduce_memory
    ckpt_corrupt    corrupt newest gen +      CKPT_CORRUPT → checkpoint_fallback
                    chief kill
    flaky_pod       one-shot pod kill         FLAKY_POD → restart_in_place
    poisoned_node   poison the learners'      POISONED_NODE →
                    node (gray failure)       reschedule_exclude_node
    straggler       4× slow incarnation       STRAGGLER → restart_in_place
    unknown         wedge with an exit        UNKNOWN → plain restart,
                    detail nobody knows       NO repair applied

Each scenario must end COMPLETED with the expected category journaled in
the job's event stream and the applied repair drawn from the registered
safe list (``core.failures.SAFE_REPAIRS``); the ``unknown`` scenario must
provably fall back to a plain restart (no REPAIR event, no exclusions, no
knob writes).  Everything runs in virtual time — seconds of wall-clock,
no JAX.  ``--smoke`` skips rewriting the checked-in ``BENCH_chaos.json``.

    PYTHONPATH=src python -m benchmarks.dependability_fig3 [--chaos] [--smoke]
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path
from typing import List, Optional

STEPS = 60
WARMUP = 10

BENCH_OUT = Path(__file__).resolve().parents[1] / "BENCH_chaos.json"
REPORT_OUT = Path(__file__).resolve().parents[1] / "artifacts" / \
    "chaos_report.json"


# ---------------------------------------------------------------------------
# Overhead section (real JAX steps; unchanged semantics)
# ---------------------------------------------------------------------------
def run(arch: str = "paper-overhead-100m", ckpt_every: int = 10):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import RunConfig, get_config
    from repro.core.checkpoint import CheckpointManager
    from repro.core.objectstore import ObjectStore
    from repro.core.platform import DLaaSPlatform
    from repro.data.pipeline import SyntheticLMData
    from repro.models.layers import Ctx
    from repro.train.steps import init_train_state, make_train_step

    cfg = get_config(arch).reduced()
    run_cfg = RunConfig(learning_rate=1e-3, warmup_steps=5, total_steps=1000)
    data = SyntheticLMData(cfg.vocab_size, 64, 8, seed=0)
    step = jax.jit(make_train_step(cfg, Ctx(dtype=jnp.float32), run_cfg))

    def warm():
        s = init_train_state(cfg, jax.random.key(0), run_cfg)
        for i in range(WARMUP):
            s, m = step(s, data.batch_at(i))
        jax.block_until_ready(m["loss"])
        return s

    platform = DLaaSPlatform(seed=2)
    platform.run(5)
    store = ObjectStore()
    ck = CheckpointManager(store, "armed", keep_last=2)

    def run_minimal(s):
        t0 = time.perf_counter()
        for i in range(STEPS):
            s, m = step(s, data.batch_at(i))
        jax.block_until_ready(m["loss"])
        return STEPS / (time.perf_counter() - t0)

    def run_armed(s):
        t0 = time.perf_counter()
        for i in range(STEPS):
            s, m = step(s, data.batch_at(i))
            def put(i=i):
                yield from platform.statestore.put(
                    "status/armed/learner/0",
                    {"state": "RUNNING", "step": i, "loss": float(m["loss"])})
            platform.sim.spawn(put())
            platform.sim.run_for(0.3)
            if (i + 1) % ckpt_every == 0:
                ck.save(i, jax.tree.map(np.asarray, s))
        jax.block_until_ready(m["loss"])
        return STEPS / (time.perf_counter() - t0)

    # interleave repetitions and take medians (1-CPU timing is noisy)
    import statistics
    s = warm()
    mins, arms = [], []
    for _ in range(3):
        mins.append(run_minimal(s))
        arms.append(run_armed(s))
    minimal = statistics.median(mins)
    armed = statistics.median(arms)

    pct = 100.0 * (minimal - armed) / minimal
    return [
        ("dependability_fig3/minimal", minimal, 0.0, 0),
        (f"dependability_fig3/armed_ckpt{ckpt_every}", armed, pct,
         store.bytes_written),
    ]


# ---------------------------------------------------------------------------
# Chaos lane (virtual time; no JAX)
# ---------------------------------------------------------------------------
def _chaos_submit(p, name, *, learners, gpus=1, total_steps=60,
                  ckpt_s=10.0, recovery="checkpoint"):
    from repro.core.jobspec import JobSpec, Resources, TrainSpec
    h = p.submit(JobSpec(
        name=name,
        resources=Resources(replicas=learners, gpus_per_replica=gpus),
        max_restarts=10,
        train=TrainSpec(total_steps=total_steps, step_time_s=0.5,
                        checkpoint_interval_s=ckpt_s,
                        recovery_mode=recovery)))
    p.run(5)
    assert h.acked and h.job_id, f"{name}: submission not acked"
    return h


def _chaos_case(scenario: str, seed: int, *, n_nodes=8, gpus_per_node=4,
                learners=2, total_steps=60, ckpt_s=10.0,
                recovery="checkpoint", make_faults=None,
                expect_category="", expect_repair: Optional[str] = None,
                recovery_pod: Optional[str] = None):
    """Boot a fresh platform, submit, arm the scripted faults, run to a
    terminal state, then check journal + repair against expectations."""
    from repro.core.failures import FaultPlan
    from repro.core.platform import DLaaSPlatform

    p = DLaaSPlatform(seed=seed, n_nodes=n_nodes, gpus_per_node=gpus_per_node)
    p.run(10)
    h = _chaos_submit(p, f"chaos-{scenario}", learners=learners,
                      total_steps=total_steps, ckpt_s=ckpt_s,
                      recovery=recovery)
    t_inject = p.sim.now
    p.inject(FaultPlan(tuple(make_faults(p, h.job_id))))
    state = p.run_until_terminal(h.job_id, timeout=3000)

    ev = p.client.events(h.job_id)
    cats = [e["failure"]["category"] for e in ev if "failure" in e]
    repairs = [e["event"] for e in ev if e["event"].startswith("REPAIR ")]
    plains = [e["event"] for e in ev
              if e["event"].startswith("RESTART plain")]

    why: List[str] = []
    if state != "COMPLETED":
        why.append(f"terminal state {state} != COMPLETED")
    if expect_category not in cats:
        why.append(f"category {expect_category} not journaled (got {cats})")
    if expect_repair is not None:
        if not any(f"REPAIR {expect_repair} " in r for r in repairs):
            why.append(f"repair {expect_repair} not applied (got {repairs})")
    else:
        if repairs:
            why.append(f"unexpected repair applied: {repairs}")
        if not plains:
            why.append("no plain-restart fallback event")
    # the safe-list contract: every applied repair is a registered action
    from repro.core.failures import SAFE_REPAIRS
    for r in repairs:
        action = r.split()[1]
        if action not in SAFE_REPAIRS.values():
            why.append(f"unregistered repair action {action!r}")
    # exclusions never leak past the job
    if p.scheduler.excluded_for(h.job_id):
        why.append("node exclusions leaked past job teardown")

    rec = None
    if recovery_pod is not None:
        rec = p.recovery_time(recovery_pod.format(job=h.job_id), t_inject)
    return {
        "scenario": scenario, "state": state, "categories": cats,
        "repairs": repairs, "plain_restarts": len(plains),
        "recovery_s": round(rec, 2) if rec is not None else None,
        "ok": not why, "why": why,
    }


def run_chaos():
    """All chaos scenarios; returns (rows, n_failures)."""
    from repro.core.failures import Fault

    rows = []
    rows.append(_chaos_case(
        "oom", seed=41, learners=2, total_steps=60,
        make_faults=lambda p, j: [Fault(
            kind="oom", at=p.sim.now, job=j, learner=0, at_step=10)],
        expect_category="OOM", expect_repair="reduce_memory",
        recovery_pod="learner-{job}-0"))

    rows.append(_chaos_case(
        "ckpt_corrupt", seed=42, learners=2, total_steps=100, ckpt_s=8.0,
        make_faults=lambda p, j: [Fault(
            kind="ckpt_corrupt", at=p.sim.now + 55.0, job=j, learner=0)],
        expect_category="CKPT_CORRUPT", expect_repair="checkpoint_fallback",
        recovery_pod="learner-{job}-0"))

    rows.append(_chaos_case(
        "flaky_pod", seed=43, learners=2, total_steps=60,
        make_faults=lambda p, j: [Fault(
            kind="flaky_pod", at=p.sim.now + 35.0, job=j, learner=1)],
        expect_category="FLAKY_POD", expect_repair="restart_in_place",
        recovery_pod="learner-{job}-1"))

    # 4 × 1-GPU learners bin-pack onto one node; poisoning it takes the
    # whole gang down at once — classified from node co-occurrence, cured
    # by excluding the node and rescheduling the gang elsewhere
    rows.append(_chaos_case(
        "poisoned_node", seed=44, n_nodes=4, learners=4, total_steps=60,
        make_faults=lambda p, j: [Fault(
            kind="poison_node", at=p.sim.now + 35.0, job=j, learner=0)],
        expect_category="POISONED_NODE",
        expect_repair="reschedule_exclude_node",
        recovery_pod="learner-{job}-0"))

    rows.append(_chaos_case(
        "straggler", seed=45, learners=4, total_steps=120,
        recovery="rejoin",
        make_faults=lambda p, j: [Fault(
            kind="straggler", at=p.sim.now, job=j, learner=2,
            slow_factor=4.0, incarnations=1)],
        expect_category="STRAGGLER", expect_repair="restart_in_place"))

    # an exit detail nobody recognizes: journaled UNKNOWN at low
    # confidence, plain restart, provably NO repair action
    rows.append(_chaos_case(
        "unknown", seed=46, learners=2, total_steps=60,
        make_faults=lambda p, j: [Fault(
            kind="wedge", at=p.sim.now, job=j, learner=1, at_step=8,
            detail="container exited with status 139 (segfault?)")],
        expect_category="UNKNOWN", expect_repair=None,
        recovery_pod="learner-{job}-1"))

    return rows, sum(1 for r in rows if not r["ok"])


def chaos_main(smoke: bool) -> int:
    t0 = time.perf_counter()
    rows, failures = run_chaos()
    wall = time.perf_counter() - t0
    print("scenario,state,category,repair,recovery_s,ok")
    for r in rows:
        cat = r["categories"][0] if r["categories"] else ""
        rep = r["repairs"][0] if r["repairs"] else "plain-restart"
        print(f"{r['scenario']},{r['state']},{cat},{rep},"
              f"{r['recovery_s']},{'OK' if r['ok'] else 'FAIL'}")
        for w in r["why"]:
            print(f"  FAIL: {w}")
    report = {"lane": "chaos", "wall_s": round(wall, 2),
              "failures": failures, "scenarios": rows}
    REPORT_OUT.parent.mkdir(parents=True, exist_ok=True)
    REPORT_OUT.write_text(json.dumps(report, indent=2) + "\n")
    print(f"wrote {REPORT_OUT} ({failures} failures, {wall:.1f}s)")
    if not smoke:
        BENCH_OUT.write_text(json.dumps(report, indent=2) + "\n")
        print(f"wrote {BENCH_OUT}")
    return failures


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chaos", action="store_true",
                    help="run the self-healing chaos lane (virtual time)")
    ap.add_argument("--smoke", action="store_true",
                    help="chaos: don't rewrite the checked-in BENCH file")
    args = ap.parse_args(argv)

    if args.chaos:
        return chaos_main(smoke=args.smoke)

    print("config,steps_s,overhead_pct,ckpt_bytes")
    for r in run():
        print(f"{r[0]},{r[1]:.2f},{r[2]:.2f},{r[3]}")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
