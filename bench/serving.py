"""What every serving driver shares: the engine built on the benchmark's
weights and warmed up, the seeded sample of finished requests, and the
comparison of their served tokens with the reference."""
from __future__ import annotations

import numpy as np

from bench import program
from bench.reference import check

# served tokens the reference scores in every run: some hundreds
SAMPLE_TOKENS = 384
SAMPLE_MAX_REQUESTS = 12


def serve_spec(mix: dict, **extra):
    from repro.core.jobspec import ServeSpec
    return ServeSpec(batch=mix["slots"], prompt_len=mix["prompt_cap"],
                     gen=mix["output_cap"],
                     page_budget=mix.get("page_budget", 0), reduced=False,
                     continuous=True, **extra)


def build_engine(run, sv=None):
    """(ModelConfig, weights, warmed engine) for the run's cell."""
    from repro.launch.engine import ServingEngine
    from repro.launch.executor import serve_ctx

    mix = run.cell.traffic
    cfg = program.model_config(run.cell.config, mix["page_size"])
    sv = sv or serve_spec(mix)
    params = program.make_params(cfg, run.seed)
    engine = ServingEngine(cfg, serve_ctx(sv), params, sv)
    warm(engine, mix, run.cell.config["vocab_size"], run.seed)
    return cfg, params, engine


def warm(engine, mix: dict, vocab: int, seed: int) -> None:
    """Run every shape the mix can reach through the engine once: one
    request per prefill bucket (page multiples up to ``prompt_cap``), a
    copy-on-write of a partly shared page, and decode steps.  Request
    ids below zero keep them apart from the traffic; their responses
    are dropped."""
    import jax

    from repro.launch.engine import Request

    rng = np.random.default_rng([seed, 2])
    ps = mix["page_size"]
    for i, S0 in enumerate(range(ps, mix["prompt_cap"] + 1, ps)):
        engine.submit(Request(req=-1 - i, tokens=rng.integers(
            0, vocab, S0).astype(np.int32), gen_len=1))
        engine.admit()
    base = rng.integers(0, vocab, 2 * ps).astype(np.int32)
    engine.submit(Request(req=-10_000, tokens=base, gen_len=4))
    engine.run()
    engine.submit(Request(req=-10_001, tokens=np.concatenate(
        [base[:ps + ps // 2], base[:ps]]), gen_len=4))
    engine.run()
    jax.block_until_ready(engine.cache)
    for r in [r for r in engine.responses if r < 0]:
        del engine.responses[r]


def sample(run, done: list) -> list:
    """A seeded sample of finished requests: the longest, then random
    others until ``SAMPLE_TOKENS`` served tokens are covered."""
    if not done:
        return []
    rng = np.random.default_rng([run.seed, 3])
    longest = max(range(len(done)),
                  key=lambda i: len(done[i][0].tokens) + len(done[i][1]))
    picked, total = [longest], len(done[longest][1])
    for i in rng.permutation(len(done)):
        if total >= SAMPLE_TOKENS or len(picked) >= SAMPLE_MAX_REQUESTS:
            break
        if i != longest:
            picked.append(int(i))
            total += len(done[i][1])
    return [done[i] for i in picked]


def release(run, done: list) -> None:
    """Keep the finished answers, free the program's state (the weights
    are the benchmark's and stay for the reference)."""
    import jax

    st = run.state
    st["done"] = done
    st["short"] = sum(len(t) != r.gen_len for r, t in done)
    st.pop("engine", None)
    jax.clear_caches()


def compare(run, control: bool = False) -> dict:
    """Readings of the sampled served tokens against the reference; with
    ``control``, those of the fp8 reference beside them."""
    picked = sample(run, run.state["done"])
    w = program.reference_weights(run.state["params"], run.cell.config)
    return check.served_gaps(w, run.cell.config, picked, control=control)


def checks(run, control: bool = False, got: dict = None) -> list:
    """Each reading the cell's limits file names, beside its limit.  With
    ``control`` the fp8 control stands in for the program: the tokens it
    puts first at the served positions are read in the served tokens'
    place.  ``got`` is a ``compare`` already made."""
    got = got or compare(run, control)
    run.log(f"reference: {got['requests']} requests, {got['tokens']} served "
            f"tokens scored")
    pre = "control_" if control else ""
    readings = {"served_gap": got[pre + "gap"],
                "served_mean_gap": got[pre + "mean_gap"]}
    return [(k, readings[k], lim) for k, lim in run.limits.items()] + [
        ("unscored", 0 if got["tokens"] else 1, 0),
        ("answers_short", run.state["short"], 0),
    ]
