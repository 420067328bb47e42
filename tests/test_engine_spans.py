"""The program's spans (``launch/spans.py``) and where the serving engine
records them: one ``engine.admit`` per round with its ``engine.match``,
``engine.tables`` and one ``engine.prefill`` per admitted request inside
it, one ``engine.queue`` per admission (again after an eviction), one
``engine.step`` per decode step with its ``engine.grow``,
``engine.decode`` and ``engine.finish`` inside it; the ring stays
bounded and no span reaches a snapshot."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.jobspec import ServeSpec
from repro.launch import spans
from repro.launch.engine import Request, ServingEngine
from repro.models.layers import Ctx
from repro.models.params import init_params


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(get_config("qwen3-0.6b").reduced(),
                              cache_layout="paged")
    return cfg, Ctx(dtype=jnp.float32), init_params(cfg, jax.random.key(0))


def _engine(model, **sv):
    cfg, ctx, params = model
    base = dict(batch=2, prompt_len=24, gen=10, requests=0, reduced=True)
    return ServingEngine(cfg, ctx, params, ServeSpec(**{**base, **sv}))


def _recorded(fn):
    """What ``fn()`` returns, and the spans recorded while it ran."""
    t0 = time.perf_counter()
    out = fn()
    got = spans.between(t0, time.perf_counter())
    assert got is not None
    return out, got


def _named(recs, name):
    return [s for s in recs if s.name == name]


def _inside(child, parent):
    return parent.t0 <= child.t0 and child.t1 <= parent.t1


def _prompts(n, length, shared=0):
    toks = np.array(jax.random.randint(jax.random.key(3), (n, length),
                                       0, 503), np.int32)
    toks[:, :shared] = toks[0, :shared]
    return toks


# -- the recorder -------------------------------------------------------
def test_spans_nest_and_carry_their_rid():
    def body():
        with spans.span("outer"):
            with spans.span("inner", rid=7, k=1) as sp:
                sp.attrs["late"] = 2
            spans.mark("waited", sp.t0, sp.t0, rid=8)
        with pytest.raises(ValueError):
            with spans.span("raised"):
                raise ValueError
        with spans.span("after"):
            pass
    _, recs = _recorded(body)
    by = {s.name: s for s in recs}
    assert [s.name for s in recs] == ["inner", "waited", "outer", "raised",
                                      "after"]
    assert by["inner"].parent == "outer" and _inside(by["inner"],
                                                     by["outer"])
    assert (by["inner"].rid, by["inner"].attrs) == (7, {"k": 1, "late": 2})
    assert (by["waited"].parent, by["waited"].rid) == ("outer", 8)
    # a span that raised still ends, and leaves no parent behind
    assert by["raised"].parent is None and by["after"].parent is None


@pytest.mark.parametrize("capacity,added", [(4, 3), (4, 10), (1, 5)])
def test_ring_is_bounded(capacity, added):
    ring = spans.Ring(capacity)
    for i in range(added):
        ring.add(spans.Span("s", float(i), i + 0.5, None, None, {}))
    kept = ring.between(added - capacity, added)
    assert kept is not None and len(kept) == min(capacity, added)
    # asked from before what it dropped, the ring has no answer
    assert (ring.between(0.0, added) is None) == (added > capacity)
    assert spans._ring._spans.maxlen == spans.CAPACITY


# -- the engine ---------------------------------------------------------
def test_admission_round_spans(model):
    """Three prompts sharing two pages: round 1 prefills the leader (the
    followers wait for its pages), round 2 the followers' tails.  Each
    round is one ``engine.admit`` holding one ``engine.prefill`` per
    admitted request: one row, as wide as its own uncached tail rounded
    up to a page."""
    eng = _engine(model, batch=4)
    prompts = _prompts(3, 20, shared=16)
    t_submit = time.perf_counter()
    for i, toks in enumerate(prompts):
        eng.submit(Request(req=i, tokens=toks, gen_len=4))
    for want in (1, 2):
        mark = len(eng.journal)
        got, recs = _recorded(eng.admit)
        assert len(got) == want
        (adm,) = _named(recs, "engine.admit")
        assert adm.attrs == {"rows": want, "rids": tuple(got)}
        assert adm.parent is None
        kids = [s for s in recs if s.parent == "engine.admit"]
        assert {s.name for s in kids} == {"engine.match", "engine.tables",
                                          "engine.prefill"}
        assert all(_inside(s, adm) for s in kids)
        pres = _named(recs, "engine.prefill")
        tails = [len(prompts[e["req"]]) - e["cached"]
                 for e in eng.journal[mark:] if e["ev"] == "admit"]
        assert len(pres) == len(tails) == want
        assert all(p.parent == "engine.admit" for p in pres)
        assert [p.attrs for p in pres] == [
            {"slots": 1, "width": -(-t // eng.ps) * eng.ps, "tokens": t}
            for t in tails]
        assert sum(p.attrs["tokens"] for p in pres) == sum(tails)
        # each admitted request waited from its submit to the round
        queued = [s for s in _named(spans.between(t_submit, adm.t1),
                                    "engine.queue") if s.t1 == adm.t0]
        assert sorted(s.rid for s in queued) == sorted(got)
        assert all(t_submit <= s.t0 <= s.t1 for s in queued)
    assert eng.cached_tokens == 2 * 16


def test_round_that_admits_nothing(model):
    eng = _engine(model)
    got, recs = _recorded(eng.admit)
    assert got == [] and [s.name for s in recs] == ["engine.match",
                                                    "engine.admit"]
    assert _named(recs, "engine.admit")[0].attrs["rows"] == 0


def test_decode_step_spans(model):
    eng = _engine(model)
    for i, toks in enumerate(_prompts(2, 12)):
        eng.submit(Request(req=i, tokens=toks, gen_len=3 + i))
    eng.admit()
    n0 = eng.decode_steps
    done, recs = _recorded(lambda: [r for _ in range(3) for r in eng.step()])
    steps = _named(recs, "engine.step")
    assert len(steps) == eng.decode_steps - n0 == 3
    for st in steps:
        kids = [s for s in recs if s.parent == "engine.step"
                and _inside(s, st)]
        names = [s.name for s in kids]
        assert names.count("engine.grow") == names.count("engine.decode") \
            == 1
        assert set(names) <= {"engine.grow", "engine.decode",
                              "engine.finish"}
    finished = _named(recs, "engine.finish")
    assert sorted(s.rid for s in finished) == sorted(done) == [0, 1]
    # each finish uploads the emptied page table
    assert all(any(t.parent == "engine.finish" and _inside(t, f)
                   for t in _named(recs, "engine.tables"))
               for f in finished)


def test_queue_span_per_admission_and_after_eviction(model):
    """Overcommitted pool: page growth evicts the younger request back to
    the queue, and its re-admission records a second queue wait."""
    eng = _engine(model, prompt_len=8, page_budget=4, overcommit=2.0)

    def serve():
        for i, toks in enumerate(_prompts(2, 8)):
            eng.submit(Request(req=i, tokens=toks, gen_len=10))
        eng.run()
    _, recs = _recorded(serve)
    assert eng.evictions > 0
    admits = [e["req"] for e in eng.journal if e["ev"] == "admit"]
    queued = _named(recs, "engine.queue")
    assert sorted(s.rid for s in queued) == sorted(admits)
    assert all(s.t1 >= s.t0 for s in queued)
    evicted = {e["req"] for e in eng.journal if e["ev"] == "evict"}
    assert all(sum(s.rid == r for s in queued) >= 2 for r in evicted)
    # the eviction came from page growth inside a decode step
    assert any(s.parent == "engine.grow"
               for s in _named(recs, "engine.tables"))


def test_snapshot_holds_no_span_data(model):
    eng = _engine(model)
    for i, toks in enumerate(_prompts(2, 12)):
        eng.submit(Request(req=i, tokens=toks, gen_len=4))
    eng.admit()
    eng.step()
    snap, recs = _recorded(eng.snapshot)
    assert [s.name for s in recs] == ["engine.snapshot"]

    def walk(x):
        assert not isinstance(x, spans.Span)
        if isinstance(x, dict):
            for k, v in x.items():
                assert "queued" not in str(k) and "span" not in str(k)
                walk(v)
        elif isinstance(x, (list, tuple)):
            for v in x:
                walk(v)
    walk(snap)
    assert all("t0" not in e and "t1" not in e for e in eng.journal)
