"""Driver kind ``serve_deepseek_v2``: the program's ``ServingEngine`` under
open-loop traffic, on DeepSeek-V2 cut to one chip's share.

The engine loop, its timings and its counters are ``serve_open_loop``'s,
run from a private copy of that module whose FLOP count is DeepSeek's
(``bench/flops_deepseek_v2.py``: absorbed MLA, the router and shared
experts, the held experts' expected share), so ``mfu.decode`` counts
this chip's required work.  What differs is set-up and the check: the
configuration maps through ``bench/deepseek_v2.py`` (a program without
the held-expert, routing and YaRN fields fails there, before any weight
is drawn), the weights are drawn in bfloat16, and the served tokens are
scored by ``bench/reference/deepseek_v2.py`` through a private copy of
``bench/reference/check.py``.
"""
from __future__ import annotations

import gc
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

from bench import deepseek_v2, flops_deepseek_v2, serving, traffic_gen
from bench.harness import load_module
from bench.reference import deepseek_v2 as reference

_BENCH = Path(__file__).resolve().parents[1]
_loop = load_module(_BENCH / "drivers" / "serve_open_loop.py",
                    "bench_driver_serve_open_loop_deepseek_v2")
_loop.flops = flops_deepseek_v2
_check = load_module(_BENCH / "reference" / "check.py",
                     "bench_check_deepseek_v2")
_check.model = reference
# coarser padding than the check's own: every sampled sequence opens with
# a 6144-token document and ends below 8192, so each precision compiles
# the reference once (padding lies past the causal horizon of every
# scored position)
_check.SEQ_BUCKET = 2048
_check.POS_BUCKET = 512

SPANS = _loop.SPANS
window = _loop.window
finished = _loop.finished


def setup(run) -> None:
    from repro.launch.engine import ServingEngine
    from repro.launch.executor import serve_ctx

    mix, cfgj = run.cell.traffic, run.cell.config
    cfg = deepseek_v2.model_config(cfgj, mix["page_size"])
    sv = serving.serve_spec(mix)
    params = deepseek_v2.make_params(cfg, run.seed)
    engine = ServingEngine(cfg, serve_ctx(sv), params, sv)
    precompile(engine, mix)
    serving.warm(engine, mix, cfgj["vocab_size"], run.seed)
    reqs = traffic_gen.build(mix, run.seed, cfgj["vocab_size"], run.seconds)
    run.state = {"cfg": cfg, "params": params, "engine": engine,
                 "reqs": reqs}


def precompile(engine, mix: dict, threads: int = 6) -> None:
    """Compile the engine's prefill for every bucket the mix can reach
    (page multiples up to ``prompt_cap``), several at once: the compiler
    runs outside the interpreter lock, and the jitted step keeps what
    ``lower().compile()`` made, so ``serving.warm`` then only runs them.
    The arguments are those of ``ServingEngine._prefill_round``."""
    import jax.numpy as jnp
    import numpy as np

    from repro.models.model import cache_slot_view

    view = cache_slot_view(engine.cache, engine.B, 0)
    one = jnp.asarray([1], jnp.int32)
    per_row = (one, jnp.asarray([0], jnp.int32)) if engine.prefix_cache \
        else (one,)

    def compile_width(width):
        batch = {"tokens": jnp.asarray(np.zeros((1, width), np.int32))}
        engine.prefill.lower(engine.params, batch, view, *per_row).compile()

    ps = mix["page_size"]
    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(compile_width, range(mix["prompt_cap"], ps - 1, -ps)))


def release(run) -> None:
    """Free the engine (its page pool too) before the reference runs
    beside the weights."""
    _loop.release(run)
    gc.collect()


def compare(run, control: bool = False) -> dict:
    picked = serving.sample(run, run.state["done"])
    w = deepseek_v2.reference_weights(run.state["params"], run.cell.config)
    return _check.served_gaps(w, run.cell.config, picked, control=control)


def checks(run, control: bool = False, got: dict = None) -> list:
    """Each reading the cell's limits file names, beside its limit; with
    ``control``, the fp8 control's readings stand in for the program's."""
    return serving.checks(run, control, got or compare(run, control))
