"""ServingEngine: snapshot/restore golden-token equivalence, the
requeue-on-eviction path (optimistic admission), hash-addressed prefix
caching (shared-prefix dedup, CoW divergence, evict-then-readmit, restore
with live refcounts), and PagePool allocator/refcount invariants under
random traffic (hypothesis-stub properties)."""
import dataclasses
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.core.jobspec import ServeSpec
from repro.launch.engine import (
    PagePool, Request, ServingEngine, synthesize_requests)
from repro.models.layers import Ctx
from repro.models.params import init_params


def _build(sv: ServeSpec, head_dim: int = 0):
    """Reduced qwen3, paged; ``head_dim`` widens its heads (64: a kv head
    is half a lane row of the token-major pool)."""
    cfg = dataclasses.replace(get_config("qwen3-0.6b").reduced(),
                              cache_layout="paged")
    if head_dim:
        cfg = dataclasses.replace(cfg, head_dim=head_dim)
    ctx = Ctx(dtype=jnp.float32)
    params = init_params(cfg, jax.random.key(0))
    return cfg, ctx, params


def _drive(engine, snap_at=()):
    """engine.run(), capturing a snapshot after decode step k for every
    k in ``snap_at`` (the boundaries: post-admission, mid-flight, late)."""
    snaps = {}
    while not engine.idle:
        engine.admit()
        if 0 in snap_at and 0 not in snaps:
            snaps[0] = engine.snapshot()         # after the first admission
        if all(s is None for s in engine.slots):
            if not engine.queue:
                break
            continue
        engine.step()
        k = engine.decode_steps
        if k in snap_at and k not in snaps:
            snaps[k] = engine.snapshot()
    return snaps


# ---------------------------------------------------------------------------
# Kill-mid-stream / restore: golden-token equivalence
# ---------------------------------------------------------------------------
def test_snapshot_restore_golden_tokens():
    _golden_tokens_survive_restore()


def test_snapshot_restore_golden_tokens_hd64():
    _golden_tokens_survive_restore(head_dim=64)


def _golden_tokens_survive_restore(head_dim: int = 0):
    """Run the engine to completion, snapshotting at several boundaries
    (right after the first admission round, mid-decode, near the end).
    A FRESH engine restored from each snapshot must finish with responses
    byte-identical to the uninterrupted run — the recovery contract the
    platform's killed-server scenario rests on."""
    sv = ServeSpec(batch=2, prompt_len=16, gen=6, requests=5,
                   page_budget=6, reduced=True)
    cfg, ctx, params = _build(sv, head_dim)

    golden = ServingEngine(cfg, ctx, params, sv)
    for r in synthesize_requests(cfg, sv, seed=0, ragged=golden.ragged):
        golden.submit(r)
    snaps = _drive(golden, snap_at=(0, 3, 7))
    assert len(golden.responses) == sv.requests
    assert set(snaps) == {0, 3, 7}, set(snaps)

    for k, snap in snaps.items():
        eng = ServingEngine(cfg, ctx, params, sv)
        eng.restore(snap)
        _drive(eng)
        assert eng.responses == golden.responses, f"boundary {k}"
        # every request's stream has exactly its generation budget
        for r, toks in eng.responses.items():
            assert len(toks) > 0


def test_snapshot_is_plain_host_data():
    """Snapshots must be device-free (they live on the job volume and are
    restored by a different pod incarnation): numpy arrays + plain
    Python containers only."""
    sv = ServeSpec(batch=2, prompt_len=16, gen=4, requests=2, reduced=True)
    cfg, ctx, params = _build(sv)
    eng = ServingEngine(cfg, ctx, params, sv)
    for r in synthesize_requests(cfg, sv, seed=0, ragged=eng.ragged):
        eng.submit(r)
    eng.admit()
    eng.step()
    snap = eng.snapshot()
    for leaf in jax.tree.leaves(snap["cache"]):
        assert isinstance(leaf, np.ndarray), type(leaf)
    assert isinstance(snap["host_table"], np.ndarray)
    assert snap["journal_len"] == len(eng.journal)


# ---------------------------------------------------------------------------
# Optimistic admission + requeue-on-eviction
# ---------------------------------------------------------------------------
def _two_requests(ps=8):
    toks = np.asarray(jax.random.randint(
        jax.random.key(1), (2, 8), 0, 503))
    # gen 10: decode writes positions 8..16 — the 17th slot forces a third
    # page mid-decode, which a 4-page pool cannot give both sequences
    return [Request(req=0, tokens=toks[0], gen_len=10),
            Request(req=1, tokens=toks[1], gen_len=10)]


def test_overcommit_evicts_and_loses_nothing():
    _evict_replay_loses_nothing()


def test_overcommit_evicts_and_loses_nothing_hd64():
    _evict_replay_loses_nothing(head_dim=64)


def _evict_replay_loses_nothing(head_dim: int = 0):
    """Page-starved workload: budget 4 pages, two requests needing 3
    worst-case each.  Conservative admission (1.0) serializes them;
    overcommit 2.0 admits both optimistically, hits page exhaustion
    mid-decode, evicts the youngest back to the queue (requeue path) and
    still completes every request — with responses identical to the
    conservative run (greedy decode re-prefills deterministically)."""
    sv = ServeSpec(batch=2, prompt_len=8, gen=10, requests=2,
                   page_budget=4, reduced=True)
    cfg, ctx, params = _build(sv, head_dim)

    conservative = ServingEngine(cfg, ctx, params, sv)
    for r in _two_requests():
        conservative.submit(r)
    _drive(conservative)
    assert conservative.evictions == 0
    assert conservative.stalled_admissions > 0   # the pool forced a wait
    assert len(conservative.responses) == 2

    optimistic = ServingEngine(cfg, ctx, params,
                               dataclasses.replace(sv, overcommit=2.0))
    for r in _two_requests():
        optimistic.submit(r)
    _drive(optimistic)
    assert optimistic.evictions > 0              # preemption really fired
    assert len(optimistic.responses) == 2        # no request lost
    assert optimistic.responses == conservative.responses
    evicted = [e["req"] for e in optimistic.journal if e["ev"] == "evict"]
    assert evicted, "journal must record the eviction"
    # the evicted request was re-admitted after its eviction
    j = optimistic.journal
    last_evict = max(i for i, e in enumerate(j) if e["ev"] == "evict")
    assert any(e["ev"] == "admit" and e["req"] == j[last_evict]["req"]
               for e in j[last_evict + 1:])


def test_submit_rejects_undeadlockable_request():
    """A request whose worst-case pages exceed a shard's capacity can
    never be admitted — submit() rejects it up front instead of letting
    admission deadlock on it."""
    sv = ServeSpec(batch=2, prompt_len=8, gen=10, requests=1,
                   page_budget=4, reduced=True)
    cfg, ctx, params = _build(sv)
    eng = ServingEngine(cfg, ctx, params, sv)
    big = Request(req=0, tokens=np.zeros(17, np.int64), gen_len=24)
    with pytest.raises(ValueError, match="worst-case"):
        eng.submit(big)


# ---------------------------------------------------------------------------
# PagePool invariants (hypothesis-stub property tests)
# ---------------------------------------------------------------------------
@settings(max_examples=30, deadline=None)
@given(n_shards=st.sampled_from([1, 2, 4]),
       per_shard=st.integers(1, 8),
       ops=st.lists(st.tuples(st.integers(0, 3), st.integers(1, 6)),
                    min_size=1, max_size=40))
def test_page_pool_invariants(n_shards, per_shard, ops):
    """Random alloc/free traffic: no page is ever handed out twice, the
    free + in-use partition always covers exactly the pool, and shard
    locality survives any free/realloc interleaving (pages always return
    to — and are always handed out from — their own shard's range)."""
    n_pages = n_shards * per_shard
    pool = PagePool(n_pages, n_shards)
    rng = np.random.default_rng(per_shard * 1000 + len(ops))
    held = []                                  # lists of allocated pages
    for kind, n in ops:
        if kind == 0 and held:                 # free a random allocation
            pages = held.pop(rng.integers(len(held)))
            pool.free(pages)
        else:                                  # alloc n from a random shard
            shard = int(rng.integers(n_shards))
            got = pool.alloc(n, shard)
            if got is None:
                free_in_shard = len(pool.free_lists[shard])
                assert n > free_in_shard       # refusal only when starved
                continue
            assert len(got) == n
            lo, hi = shard * per_shard, (shard + 1) * per_shard
            assert all(lo <= p < hi for p in got)   # shard locality
            held.append(got)
        # global invariants after every operation
        out = [p for pages in held for p in pages]
        assert len(out) == len(set(out))       # no double allocation
        free = [p for fl in pool.free_lists for p in fl]
        assert len(free) == len(set(free))     # no double free
        assert sorted(out + free) == list(range(n_pages))
        assert pool.in_use == len(out)
        assert pool.high_water >= pool.in_use


@settings(max_examples=30, deadline=None)
@given(per_shard=st.integers(2, 8),
       ops=st.lists(st.tuples(st.integers(0, 3), st.integers(1, 4)),
                    min_size=1, max_size=50))
def test_page_pool_refcount_invariants(per_shard, ops):
    """Random alloc/free/publish/attach traffic (the prefix-cache op mix):
    a page's refcount always equals the number of live holders, the free
    lists never intersect the referenced set, nothing is double-freed, and
    every page is either free or referenced — never both, never neither."""
    n_shards = 2
    n_pages = n_shards * per_shard
    pool = PagePool(n_pages, n_shards)
    rng = np.random.default_rng(per_shard * 7 + len(ops))
    held = []                      # page lists; each entry holds one ref/page
    for kind, n in ops:
        shard = int(rng.integers(n_shards))
        if kind == 0 and held:                 # release one holder
            pool.free(held.pop(rng.integers(len(held))))
        elif kind == 1:                        # fresh allocation
            got = pool.alloc(n, shard)
            if got is not None:
                held.append(got)
        elif kind == 2 and held:               # publish a held page
            pages = held[int(rng.integers(len(held)))]
            p = pages[int(rng.integers(len(pages)))]
            pool.publish(p, "root", f"chain-{p}", [p])
        else:                                  # prefix hit: attach via index
            kids = pool.candidates(shard, "root")
            if kids:
                chain = sorted(kids)[int(rng.integers(len(kids)))]
                p = pool.lookup(shard, "root", chain)
                pool.attach(p)
                held.append([p])
        # invariants after every operation
        holders = Counter(p for pages in held for p in pages)
        free = [p for fl in pool.free_lists for p in fl]
        assert len(free) == len(set(free))     # no double free
        assert not set(free) & set(holders)    # free ∩ referenced = ∅
        for p in range(n_pages):
            assert pool.refcount[p] == holders.get(p, 0)
        assert sorted(set(free) | set(holders)) == list(range(n_pages))
        assert pool.in_use == len(holders)     # unique, not sum of refs
        assert pool.high_water >= pool.in_use
        # the index never points at a page whose metadata disagrees
        for s in range(n_shards):
            for parent, kids in pool.prefix_index[s].items():
                for chain, p in kids.items():
                    assert pool.page_meta[p]["hash"] == chain
                    assert pool.page_meta[p]["parent"] == parent
                    assert pool.shard_of(p) == s


def test_page_pool_shard_free_realloc_locality():
    """Freeing a foreign-shard page routes it back to its home shard's
    free list, so a later same-shard alloc returns it (the regression the
    property test covers, pinned deterministically)."""
    pool = PagePool(8, n_shards=2)
    a = pool.alloc(4, shard=0)
    b = pool.alloc(4, shard=1)
    assert a == [0, 1, 2, 3] and b == [4, 5, 6, 7]
    pool.free([5])                             # shard-1 page
    assert pool.alloc(1, shard=0) is None      # shard 0 still empty
    assert pool.alloc(1, shard=1) == [5]


def test_page_pool_cached_but_free_lifecycle():
    """A freed published page stays hittable (cached-but-free) until the
    allocator reuses its physical page, which deregisters it."""
    pool = PagePool(4, n_shards=1)
    (p,) = pool.alloc(1)
    pool.publish(p, "root", "c0", [1, 2])
    pool.free([p])                             # refcount 0, still indexed
    assert pool.lookup(0, "root", "c0") == p
    pool.attach(p)                             # hit revives it off the list
    assert pool.refcount[p] == 1 and pool.in_use == 1
    pool.free([p])
    # exhaust the pool: the cached page is eventually handed back out,
    # and reuse must end its cache life
    got = pool.alloc(4)
    assert got is not None and p in got
    assert pool.lookup(0, "root", "c0") is None
    assert p not in pool.page_meta


# ---------------------------------------------------------------------------
# Prefix caching: golden-token equivalence
# ---------------------------------------------------------------------------
def _solo_response(cfg, ctx, params, sv, req):
    """One request through a fresh engine (no sharing possible)."""
    eng = ServingEngine(cfg, ctx, params, sv)
    eng.submit(req)
    eng.run()
    return eng.responses[req.req]


def test_shared_prefix_batch_equals_solo():
    """A batch sharing a page-aligned 75% prefix pays one prefill over the
    shared span, keeps ONE physical copy of the prefix pages, and still
    answers every request exactly as a solo run would — aliasing is
    invisible to greedy decode."""
    sv = ServeSpec(batch=4, prompt_len=32, gen=4, requests=4,
                   page_budget=12, reduced=True, shared_prefix_frac=0.75)
    cfg, ctx, params = _build(sv)
    eng = ServingEngine(cfg, ctx, params, sv)
    reqs = synthesize_requests(cfg, sv, seed=0, ragged=eng.ragged)
    for r in reqs:
        eng.submit(r)
    eng.admit()                    # round 1: the leader (followers defer)
    assert sum(s is not None for s in eng.slots) == 1
    eng.admit()                    # round 2: followers hit the index
    assert eng.prefix_hits == 3 and eng.prefix_misses == 1
    assert eng.cow_copies == 0     # 24 shared tokens = 3 whole pages
    ps = eng.ps
    assert eng.resident_prefix_pages() == 24 // ps
    # one prefill over the shared span: 32 + 3 private 8-token tails
    assert eng.prefill_tokens == 32 + 3 * 8
    assert eng.cached_tokens == 3 * 24
    eng.run()
    assert len(eng.responses) == 4
    for r in reqs:
        assert eng.responses[r.req] == _solo_response(cfg, ctx, params,
                                                      sv, r), r.req


def test_cow_divergence_mid_page():
    _cow_divergence_equals_solo()


def test_cow_divergence_mid_page_hd64():
    _cow_divergence_equals_solo(head_dim=64)


def _cow_divergence_equals_solo(head_dim: int = 0):
    """Two prompts agreeing through token 19 and diverging at token 20
    (mid-page): the follower attaches the 2 whole shared pages, CoW-copies
    the partially-shared third page (4 of 8 tokens reused), prefills only
    the divergent tail — and answers exactly as its solo run."""
    sv = ServeSpec(batch=2, prompt_len=24, gen=4, requests=2,
                   page_budget=12, reduced=True)
    cfg, ctx, params = _build(sv, head_dim)
    base = np.array(jax.random.randint(
        jax.random.key(3), (24,), 0, cfg.vocab_size))
    fork = base.copy()
    fork[20] = (fork[20] + 1) % cfg.vocab_size
    reqs = [Request(req=0, tokens=base, gen_len=4),
            Request(req=1, tokens=fork, gen_len=4)]

    eng = ServingEngine(cfg, ctx, params, sv)
    for r in reqs:
        eng.submit(r)
    eng.admit()                    # leader prefills + publishes
    eng.admit()                    # follower: 2-page hit + mid-page CoW
    assert eng.prefix_hits == 1 and eng.cow_copies == 1
    assert eng.cached_tokens == 20            # 2 pages + 4-token overlap
    assert eng.prefill_tokens == 24 + 4
    # the CoW page is private: page 2 of the two rows must differ
    recs = [s for s in eng.slots if s is not None]
    assert recs[0].pages[2] != recs[1].pages[2]
    assert recs[0].pages[:2] == recs[1].pages[:2]     # aliased prefix
    eng.run()
    for r in reqs:
        assert eng.responses[r.req] == _solo_response(cfg, ctx, params,
                                                      sv, r), r.req


def test_evict_then_readmit_hits_cached_prefix():
    """Optimistic admission under page pressure with identical prompts:
    the evicted follower's private pages are freed but the shared prefix
    stays cached, so its re-admission is another prefix hit — and the
    final responses match the conservative (never-evicting) run."""
    # the leader generates longer than the follower, so it is still alive
    # (holding the last free page) when the follower's decode crosses its
    # own page boundary one step later — forcing the eviction
    sv = ServeSpec(batch=2, prompt_len=16, gen=12, requests=2,
                   page_budget=6, reduced=True)
    cfg, ctx, params = _build(sv)
    toks = np.array(jax.random.randint(
        jax.random.key(4), (16,), 0, cfg.vocab_size))
    mk = lambda: [Request(req=0, tokens=toks.copy(), gen_len=12),  # noqa: E731
                  Request(req=1, tokens=toks.copy(), gen_len=10)]

    conservative = ServingEngine(cfg, ctx, params, sv)
    for r in mk():
        conservative.submit(r)
    _drive(conservative)
    assert conservative.evictions == 0
    assert conservative.prefix_hits >= 1      # serialized follower still hits

    optimistic = ServingEngine(cfg, ctx, params,
                               dataclasses.replace(sv, overcommit=2.0))
    for r in mk():
        optimistic.submit(r)
    _drive(optimistic)
    assert optimistic.evictions > 0
    assert optimistic.prefix_hits >= 2        # initial admit + re-admit
    assert len(optimistic.responses) == 2
    assert optimistic.responses == conservative.responses


def test_snapshot_restore_with_shared_pages():
    """Kill-mid-stream with shared pages live: snapshots taken while
    prefix pages carry refcount > 1 must round-trip the refcounts, the
    prefix index and the page metadata byte-identically, and a restored
    engine must finish with the uninterrupted run's exact responses."""
    sv = ServeSpec(batch=4, prompt_len=32, gen=6, requests=6,
                   page_budget=16, reduced=True, shared_prefix_frac=0.9)
    cfg, ctx, params = _build(sv)
    golden = ServingEngine(cfg, ctx, params, sv)
    for r in synthesize_requests(cfg, sv, seed=0, ragged=golden.ragged):
        golden.submit(r)
    snaps = _drive(golden, snap_at=(2, 4))
    assert len(golden.responses) == sv.requests
    assert golden.prefix_hits > 0 and golden.cow_copies > 0   # 29-token share
    assert any(c > 1 for c in snaps[2]["refcount"])           # sharing live

    for k, snap in snaps.items():
        eng = ServingEngine(cfg, ctx, params, sv)
        eng.restore(snap)
        rt = eng.snapshot()       # restore → snapshot must be the identity
        assert rt["refcount"] == snap["refcount"], k
        assert rt["page_meta"] == snap["page_meta"], k
        assert rt["prefix_index"] == snap["prefix_index"], k
        _drive(eng)
        assert eng.responses == golden.responses, f"boundary {k}"
