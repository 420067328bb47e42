"""The seeded generator: the same seed gives the same requests, another
seed the same schedule with other tokens, arrivals begin before the
window, and chat sessions share prefixes that the engine's prefix index
serves."""
from __future__ import annotations

import json
import math

import numpy as np
import pytest

from bench import traffic_gen
from bench.tests import util


def _mix(name):
    return json.loads((util.REPO / f"bench/traffic/{name}.json").read_text())


@pytest.mark.parametrize("name", ["chat", "decode-heavy"])
def test_seed_draws_the_tokens_and_not_the_schedule(name):
    mix = _mix(name)
    a = traffic_gen.build(mix, 2**33 + 1, 1000, 30)
    b = traffic_gen.build(mix, 2**33 + 1, 1000, 30)
    c = traffic_gen.build(mix, 5, 1000, 30)
    assert [(r.due, r.tokens.tolist(), r.gen_len) for r in a] == \
        [(r.due, r.tokens.tolist(), r.gen_len) for r in b]
    assert [r.tokens.tolist() for r in a] != [r.tokens.tolist() for r in c]
    shape = lambda reqs: [(r.due, len(r.tokens), r.gen_len, r.session,
                           r.turn) for r in reqs]
    assert shape(a) == shape(c)
    for r in a:
        assert len(r.tokens) <= mix["prompt_cap"]
        assert mix["output_tokens"]["min"] <= r.gen_len \
            <= mix["output_tokens"]["max"]


@pytest.mark.parametrize("name", ["chat", "decode-heavy"])
def test_arrivals_begin_a_preroll_before_the_window(name):
    mix = _mix(name)
    pre = mix["preroll_s"]
    reqs = traffic_gen.build(mix, 3, 1000, 30)
    assert len(reqs) == math.ceil(mix["rate_per_s"] * (pre + 30))
    dues = [r.due for r in reqs]
    assert dues == sorted(dues)
    assert -pre < dues[0] < -pre + 5 / mix["rate_per_s"]
    assert abs(dues[-1] - 30) <= 1 / mix["rate_per_s"]
    in_window = sum(0 <= d < 30 for d in dues)
    assert in_window == pytest.approx(mix["rate_per_s"] * 30, abs=3)


def test_chat_sessions_share_prefixes():
    mix = _mix("chat")
    reqs = traffic_gen.build(mix, 3, 151936, 60)
    ses = mix["sessions"]
    heads = {tuple(r.tokens[:ses["system_prompt_tokens"]]) for r in reqs}
    assert len(heads) <= ses["system_prompts"]
    last = {}
    for r in reqs:
        if r.session in last and len(last[r.session]) < mix["prompt_cap"]:
            prev = last[r.session]
            assert r.turn > 0
            assert np.array_equal(r.tokens[:len(prev)], prev)
        last[r.session] = r.tokens
    assert max(r.turn for r in reqs) >= 2


def test_decode_heavy_prompts_share_nothing():
    reqs = traffic_gen.build(_mix("decode-heavy"), 3, 49155, 30)
    assert len({tuple(r.tokens[:8]) for r in reqs}) == len(reqs)


def test_shared_system_prompt_pages_hit_the_index():
    """Served in arrival order through the program's engine, the tiny
    chat mix's sessions attach cached system-prompt pages."""
    from repro.core.jobspec import ServeSpec
    from repro.launch.engine import Request, ServingEngine
    from repro.launch.executor import serve_ctx

    from bench import program
    mix = util.tiny_mix()
    cfg = program.model_config(util.tiny_config(False), mix["page_size"])
    sv = ServeSpec(batch=mix["slots"], prompt_len=mix["prompt_cap"],
                   gen=mix["output_cap"], reduced=False, continuous=True)
    eng = ServingEngine(cfg, serve_ctx(sv), program.make_params(cfg, 1), sv)
    reqs = traffic_gen.build(mix, 1, 503, 1.0)
    for r in reqs:
        eng.submit(Request(req=r.rid, tokens=r.tokens, gen_len=r.gen_len))
    eng.run()
    assert len(eng.responses) == len(reqs)
    assert eng.prefix_hits > 0
    assert eng.cached_tokens >= mix["sessions"]["system_prompt_tokens"]
