"""An admission round prefills each admitted request alone, in a one-row
view of the cache: rows mid-decode are untouched, every admitted request
answers as its solo run does, and once each prompt width has been seen a
round compiles nothing, whichever slots it fills and however many."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.jobspec import ServeSpec
from repro.launch.engine import Request, ServingEngine
from repro.models.layers import Ctx
from repro.models.params import init_params
from test_engine import _solo_response

_compiles = [0]
jax.monitoring.register_event_duration_secs_listener(
    lambda event, duration, **kw: _compiles.__setitem__(
        0, _compiles[0] + ("backend_compile" in event)))


def _model(arch):
    cfg = dataclasses.replace(get_config(arch).reduced(),
                              cache_layout="paged")
    return cfg, Ctx(dtype=jnp.float32), init_params(cfg, jax.random.key(0))


def _prompts(vocab):
    """Two prompts for the rows mid-decode, then three for one round: a
    fork of prompt 0 diverging mid-page at token 20, and two unrelated."""
    toks = np.array(jax.random.randint(jax.random.key(5), (5, 24), 0,
                                       vocab), np.int32)
    toks[2] = toks[0]
    toks[2, 20] = (toks[2, 20] + 1) % vocab
    return [toks[0], toks[1, :13], toks[2], toks[3, :17], toks[4, :9]]


def _start_two(eng, prompts):
    """Requests 0 and 1 admitted and two decode steps into their answers."""
    for i in (0, 1):
        eng.submit(Request(req=i, tokens=prompts[i], gen_len=8))
    assert eng.admit() == [0, 1]
    eng.step()
    eng.step()


@pytest.mark.parametrize("arch,prefix_cache,cow", [
    ("qwen3-0.6b", True, 1),          # chunked prefill at a row's start
    ("recurrentgemma-9b", False, 0),  # ring-local and recurrent carries
])
def test_round_rows_equal_solo_and_leave_decoding_rows(arch, prefix_cache,
                                                       cow):
    cfg, ctx, params = _model(arch)
    sv = ServeSpec(batch=5, prompt_len=24, gen=8, requests=0, reduced=True)
    prompts = _prompts(cfg.vocab_size)
    reqs = [Request(req=i, tokens=t, gen_len=8 if i < 2 else 4 + i)
            for i, t in enumerate(prompts)]

    eng = ServingEngine(cfg, ctx, params, sv)
    assert eng.ragged and eng.prefix_cache == prefix_cache
    _start_two(eng, prompts)
    for r in reqs[2:]:
        eng.submit(r)
    assert eng.admit() == [2, 3, 4]   # one round, three rows
    assert eng.cow_copies == cow
    if prefix_cache:
        assert eng.cached_tokens == 20  # 2 pages + the 4-token overlap
    eng.run()

    alone = ServingEngine(cfg, ctx, params, sv)
    _start_two(alone, prompts)
    alone.run()                       # the same steps, no round between
    for i in (0, 1):
        assert eng.responses[i] == alone.responses[i], (arch, i)
    for r in reqs:
        assert eng.responses[r.req] == _solo_response(
            cfg, ctx, params, sv, r), (arch, r.req)


def test_round_compiles_nothing_once_widths_are_warm():
    """Warm each width bucket with a one-request round, as the benchmark's
    set-up does (it fills slot 0 only), then admit three requests of
    warmed widths into other slots beside a row mid-decode."""
    cfg, ctx, params = _model("qwen3-0.6b")
    sv = ServeSpec(batch=5, prompt_len=24, gen=8, requests=0, reduced=True)
    eng = ServingEngine(cfg, ctx, params, sv)
    rng = np.random.default_rng(0)
    for i, width in enumerate(range(eng.ps, sv.prompt_len + 1, eng.ps)):
        eng.submit(Request(req=-1 - i, tokens=rng.integers(
            0, cfg.vocab_size, width).astype(np.int32), gen_len=1))
        eng.admit()
    eng.submit(Request(req=-10, tokens=rng.integers(
        0, cfg.vocab_size, 12).astype(np.int32), gen_len=3))
    eng.run()

    prompts = _prompts(cfg.vocab_size)
    _start_two(eng, prompts)
    cached, n0 = eng.prefill._cache_size(), _compiles[0]
    for i in (3, 4, 1):
        eng.submit(Request(req=10 + i, tokens=prompts[i], gen_len=4))
    assert len(eng.admit()) == 3
    eng.step()
    assert eng.prefill._cache_size() == cached
    assert _compiles[0] == n0
