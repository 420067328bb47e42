"""Paged KV cache: dense/paged decode equivalence, per-sequence decode
positions (continuous batching), sharded-cache placement, gather/scatter
locality well-formedness, and serving-driver smoke tests."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, RunConfig, get_config
from repro.dist.mesh import make_abstract_production_mesh
from repro.dist.sharding import DEFAULT_RULES, check_cache_locality
from repro.launch.specs import placement_report
from repro.models.layers import Ctx
from repro.models.model import abstract_cache, forward, init_cache, num_pages
from repro.models.params import init_params

B, S, S0 = 2, 40, 28      # S0 deliberately not a multiple of page_size=8


def _setup(arch):
    """``arch`` reduced; ``<arch>@hd64`` widens its heads to 64 (a kv head
    is half a lane row of the token-major pool)."""
    arch, _, hd = arch.partition("@hd")
    cfg = get_config(arch).reduced()
    if hd:
        cfg = dataclasses.replace(cfg, head_dim=int(hd))
    ctx = Ctx(dtype=jnp.float32)
    params = init_params(cfg, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    return cfg, ctx, params, toks


def _run_serve(cfg, ctx, params, toks):
    cache = init_cache(cfg, B, S)
    logits, cache, _ = forward(cfg, params, {"tokens": toks[:, :S0]}, ctx,
                               mode="prefill", cache=cache)
    outs = [logits]
    for t in range(S0, S):
        logits, cache, _ = forward(cfg, params, {"tokens": toks[:, t:t + 1]},
                                   ctx, mode="decode", cache=cache, pos=t)
        outs.append(logits)
    return jnp.concatenate(outs, axis=1), cache


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "gemma2-9b", "qwen2.5-32b",
                                  "deepseek-v2-236b", "qwen3-0.6b@hd64",
                                  "granite-moe-1b-a400m@hd64"])
def test_paged_matches_dense_decode(arch):
    """Decode logits must agree (fp32) between the dense fallback and the
    paged layout.  The paged model path is the O(pages) online-softmax walk
    (kernels.paged_attention), which reorders the reduction vs the dense
    full softmax — so the bound is a tight fp32 tolerance rather than the
    bitwise equality the old gather-reference permitted; op-level
    equivalence at ~1e-6 is covered in test_paged_kernel.py."""
    cfg, ctx, params, toks = _setup(arch)
    dense, _ = _run_serve(dataclasses.replace(cfg, cache_layout="dense"),
                          ctx, params, toks)
    paged, _ = _run_serve(dataclasses.replace(cfg, cache_layout="paged"),
                          ctx, params, toks)
    err = float(jnp.abs(dense - paged).max())
    assert err < 1e-4, (arch, err)


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "gemma2-9b",
                                  "deepseek-v2-236b", "qwen3-0.6b@hd64"])
def test_per_sequence_decode_positions(arch):
    """Continuous batching decodes rows at different positions: an active
    row with a (B,) position vector must produce the same logits as the
    lockstep run, and inactive rows (pos = -1) must not corrupt it."""
    cfg, ctx, params, toks = _setup(arch)
    cfg = dataclasses.replace(cfg, cache_layout="paged")
    lock, _ = _run_serve(cfg, ctx, params, toks)

    cache = init_cache(cfg, B, S)
    _, cache, _ = forward(cfg, params, {"tokens": toks[:, :S0]}, ctx,
                          mode="prefill", cache=cache)
    for t in range(S0, S):
        # row 1 inactive: feeds a junk token at pos -1 (dropped write)
        step_toks = jnp.stack([toks[0, t:t + 1], jnp.zeros((1,), toks.dtype)])
        pos = jnp.asarray([t, -1], jnp.int32)
        logits, cache, _ = forward(cfg, params, {"tokens": step_toks}, ctx,
                                   mode="decode", cache=cache, pos=pos)
        err = float(jnp.abs(logits[0, 0] - lock[0, t - S0 + 1]).max())
        assert err < 1e-5, (arch, t, err)


@pytest.mark.parametrize("hd", [16, 64])
def test_chunked_prefill_bit_exact_across_splits(hd):
    """Prefix caching writes a prompt's K/V in chunks, each opening where
    the pages already written end: the row writes of the chunks leave the
    token-major pool (a layer of a stack) bit for bit as one write of the
    whole prompt does, wherever the splits fall (mid-page, on a page
    boundary), and write nothing outside the row's pages."""
    from repro.models.attention import _write_paged

    L, P, ps, K, S0 = 3, 8, 8, 2, 28
    rng = np.random.default_rng(5)
    pools = {n: jnp.asarray(rng.normal(size=(L, P, ps, K * hd)), jnp.float32)
             for n in ("k_pages", "v_pages")}
    table = jnp.asarray([[6, 1, 4, 7, -1]], jnp.int32)
    k = jnp.asarray(rng.normal(size=(1, S0, K, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, S0, K, hd)), jnp.float32)

    def write(splits):
        cache = dict(pools, page_table=table, layer=jnp.int32(2))
        for a, b in zip((0,) + splits, splits + (S0,)):
            cache = _write_paged(cache, k[:, a:b], v[:, a:b],
                                 jnp.arange(a, b)[None],
                                 jnp.asarray([b - a], jnp.int32))
        return cache

    whole = write(())
    for n in pools:
        got = np.asarray(whole[n][2]).reshape(P, ps, K, hd)
        src = np.asarray(k if n == "k_pages" else v)[0]
        for t in range(S0):
            np.testing.assert_array_equal(
                got[int(table[0, t // ps]), t % ps], src[t])
        np.testing.assert_array_equal(np.asarray(whole[n][:2]),
                                      np.asarray(pools[n][:2]))
    for splits in ((13,), (16,), (5, 21)):
        cache = write(splits)
        for n in pools:
            np.testing.assert_array_equal(np.asarray(cache[n]),
                                          np.asarray(whole[n]), splits)


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "qwen3-0.6b@hd64",
                                  "deepseek-v2-236b"])
def test_chunked_prefill_matches_across_splits(arch):
    """The model's chunked prefill over pages written by earlier chunks
    gives the whole prompt's last logits, wherever the splits fall, to
    float32 rounding: the file's 1e-4 (chunks of other widths take other
    matmul shapes, so the projections round differently, and a stored
    bfloat16 K may land one unit apart; the writes themselves are pinned
    bit for bit above)."""
    cfg, ctx, params, toks = _setup(arch)
    cfg = dataclasses.replace(cfg, cache_layout="paged")

    def chunks(splits):
        cache = init_cache(cfg, 1, S)
        for a, b in zip((0,) + splits, splits + (S0,)):
            logits, cache, _ = forward(
                cfg, params, {"tokens": toks[:1, a:b]}, ctx, mode="prefill",
                cache=cache, lengths=jnp.asarray([b - a], jnp.int32),
                starts=jnp.asarray([a], jnp.int32))
        return logits

    whole = chunks(())
    for splits in ((13,), (16,), (5, 21)):
        np.testing.assert_allclose(np.asarray(chunks(splits)),
                                   np.asarray(whole), atol=1e-4)


def test_paged_cache_is_smaller_in_specs():
    """Pool + tables with a reduced page budget must spec out smaller than
    the dense worst-case cache (unsharded byte count)."""
    import jax.tree_util as jtu
    cfg = get_config("qwen3-0.6b")
    dense = abstract_cache(cfg, 8, 4096, layout="dense")
    paged = abstract_cache(dataclasses.replace(cfg, cache_layout="paged"),
                           8, 4096, layout="paged", page_budget=64)
    size = lambda tree: sum(
        int(np.prod(ab.shape)) for ab in jtu.tree_leaves(
            tree, is_leaf=lambda x: hasattr(x, "logical_axes")))
    assert size(paged) < size(dense) / 4


def test_decode_32k_placement_4x_reduction():
    """Acceptance: decode_32k on the 16×16 production mesh — the paged +
    sequence-sharded layout must report ≥4× lower cache_gb than the seed
    placement (kv_seq/cache_pages replicated)."""
    mesh = make_abstract_production_mesh()
    shape = SHAPES["decode_32k"]
    run = RunConfig()
    legacy = DEFAULT_RULES.override(kv_seq=(), cache_pages=())
    for arch in ("qwen3-0.6b", "mistral-large-123b", "gemma2-9b"):
        cfg = get_config(arch)
        seed_gb = placement_report(cfg, shape, run, mesh, legacy)["cache_gb"]
        paged = placement_report(
            dataclasses.replace(cfg, cache_layout="paged"), shape, run, mesh)
        assert paged["cache_gb"] * 4 <= seed_gb, (arch, seed_gb, paged)
        assert paged["cache_pages"] > 0
        # dense fallback with the new kv_seq rule also stops replicating
        dense_gb = placement_report(cfg, shape, run, mesh)["cache_gb"]
        assert dense_gb * 4 <= seed_gb, (arch, seed_gb, dense_gb)


def test_page_occupancy_scales_budget():
    mesh = make_abstract_production_mesh()
    shape = SHAPES["decode_32k"]
    cfg = dataclasses.replace(get_config("qwen3-0.6b"), cache_layout="paged")
    full = placement_report(cfg, shape, RunConfig(), mesh)
    half = placement_report(cfg, shape, RunConfig(page_occupancy=0.5), mesh)
    assert half["cache_pages"] * 2 == full["cache_pages"]
    assert half["cache_gb"] < full["cache_gb"]


def test_cache_locality_check_rejects_sharded_ring():
    """A rules override that shards the ring-buffer slot dim must be
    rejected: the pos%window scatter would cross shards every step."""
    cfg = get_config("gemma2-9b")           # has local-attention layers
    mesh = make_abstract_production_mesh()
    ab = abstract_cache(cfg, 128, 4096)
    check_cache_locality(ab, mesh, DEFAULT_RULES)          # well-formed
    bad = DEFAULT_RULES.override(window_seq=("model",))
    with pytest.raises(ValueError, match="window_seq|ring"):
        check_cache_locality(ab, mesh, bad)


def test_identity_tables_need_worst_case_pool():
    cfg = dataclasses.replace(get_config("qwen3-0.6b").reduced(),
                              cache_layout="paged")
    with pytest.raises(ValueError, match="identity"):
        init_cache(cfg, 4, 64, page_budget=3)
    # empty tables are fine with any budget
    c = init_cache(cfg, 4, 64, page_budget=3, paged_tables="empty")
    flat = jax.tree.leaves(c)
    assert all(jnp.all(l == -1) for l in flat if l.dtype == jnp.int32)


def test_num_pages():
    assert num_pages(64, 8) == 8
    assert num_pages(65, 8) == 9
    assert num_pages(1, 8) == 1


# ---------------------------------------------------------------------------
# Serving-driver smoke tests (ISSUE 2 satellite: launch.serve --reduced)
# ---------------------------------------------------------------------------
def test_serve_reduced_smoke():
    from repro.launch import serve
    assert serve.main(["--reduced", "--batch", "2", "--prompt-len", "16",
                       "--gen", "6"]) == 0


def test_serve_continuous_smoke():
    """Continuous batching: more requests than slots, a squeezed page
    budget (forces admission stalls), every request must complete."""
    from repro.launch import serve
    assert serve.main(["--reduced", "--batch", "2", "--prompt-len", "16",
                       "--gen", "6", "--continuous", "--requests", "4",
                       "--page-budget", "3"]) == 0


def test_serve_continuous_gen_one(capsys):
    """gen_len == 1 requests are done at prefill: no extra decode token
    (the prefill output IS the single requested token)."""
    from repro.launch import serve
    assert serve.main(["--reduced", "--batch", "2", "--prompt-len", "16",
                       "--gen", "1", "--continuous", "--requests", "3"]) == 0
    out = capsys.readouterr().out
    assert "completed 3/3 in 0 decode steps" in out, out


def test_page_pool_shard_partitioning():
    from repro.launch.serve import PagePool
    pool = PagePool(8, n_shards=2)
    a = pool.alloc(3, shard=0)
    b = pool.alloc(3, shard=1)
    assert all(p < 4 for p in a) and all(p >= 4 for p in b)
    assert pool.alloc(1, shard=0) == [3]
    assert pool.alloc(1, shard=0) is None       # shard 0 exhausted
    assert pool.high_water == 7
    pool.free(a)                                 # returns to shard 0's list
    assert pool.alloc(3, shard=0) == a
    assert pool.in_use == 7                      # shard 1 still has one free
