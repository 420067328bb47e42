"""DeepSeek-V2 as the benchmark cuts it — one chip's share of the experts,
group-limited routing, YaRN, blocked MLA prefill — against the plain
float32 reference (``bench/reference/deepseek_v2.py``), on the CPU at a
small DeepSeek-shaped size.

The small size keeps what makes the architecture: a q LoRA, 8 routing
groups over 16 router outputs with 3 kept, 4 experts held of the 16
(group 2 and 3), two shared experts, YaRN over an original context of 32
positions with sequences past it, and a dense first layer.  The program
computes in float32 here, so it and the reference differ only by
rounding (float32 epsilon x the depth: 1e-4 on logits of size ~5).
"""
from __future__ import annotations

import dataclasses
import json
import math
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import deepseek_v2 as ds
from bench.reference import deepseek_v2 as ref
from repro.configs import get_config
from repro.core.jobspec import ServeSpec
from repro.launch import spans
from repro.launch.engine import Request, ServingEngine
from repro.models.layers import Ctx, rope_freqs, yarn_correction_range
from repro.models.model import forward, init_cache
from repro.models.moe import moe_ffn, route

ROOT = Path(__file__).resolve().parents[1]
PS = 8
# float32 program vs float32 reference: rounding only, summed over 3 layers
ATOL = 1e-4


def tiny_json(**over) -> dict:
    c = json.loads((ROOT / "bench/configs/deepseek-v2-236b.json").read_text())
    c.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
             q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
             moe_intermediate_size=32, num_experts_per_tok=3,
             n_routed_experts=4, first_held_expert=4, num_hidden_layers=3,
             vocab_size=384, compute_dtype="float32", param_dtype="float32")
    c["published"] = dict(c["published"], n_routed_experts=16)
    c["rope_scaling"] = dict(c["rope_scaling"], factor=4,
                             original_max_position_embeddings=32)
    c.update(over)
    return c


@pytest.fixture(scope="module")
def model():
    cj = tiny_json()
    cfg = ds.model_config(cj, PS)
    params = ds.make_params(cfg, 2**33 + 7)
    return cj, cfg, params, ds.reference_weights(params, cj)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 384, n).astype(np.int32)


def test_prefill_then_paged_decode_matches_reference(model):
    cj, cfg, params, w = model
    toks = _prompt(60)
    want = np.asarray(ref.logits(w, cj, toks, np.arange(60)))
    ctx = Ctx(dtype=jnp.float32)
    cache = init_cache(cfg, 1, 64, layout="paged", paged_tables="identity")
    got, cache, _ = forward(cfg, params, {"tokens": jnp.asarray(toks[None, :40])},
                            ctx, mode="prefill", cache=cache,
                            lengths=jnp.array([40]), starts=jnp.array([0]))
    np.testing.assert_allclose(got[0, -1, :384], want[39], atol=ATOL)
    for t in range(40, 59):
        got, cache, _ = forward(cfg, params,
                                {"tokens": jnp.asarray(toks[None, t:t + 1])},
                                ctx, mode="decode", cache=cache,
                                pos=jnp.array([t]))
        np.testing.assert_allclose(got[0, -1, :384], want[t], atol=ATOL)


def _serve(cfg, params, prompts, gen):
    sv = ServeSpec(batch=2, prompt_len=64, gen=gen, continuous=True,
                   cache_layout="paged")
    eng = ServingEngine(cfg, Ctx(dtype=jnp.float32), params, sv)
    for i, p in enumerate(prompts):
        eng.submit(Request(req=i, tokens=p, gen_len=gen))
    eng.run()
    return eng


def test_serving_engine_tokens_match_reference(model):
    """Greedy tokens served through the engine (prefix cache, paged latent
    cache, continuous batching) read a gap of ~0 against the reference's
    full forward: the reference's best logit at each served position."""
    cj, cfg, params, w = model
    doc = _prompt(40, 1)
    prompts = [np.concatenate([doc, _prompt(n, 2 + n)]) for n in (9, 17, 3)]
    eng = _serve(cfg, params, prompts, gen=6)
    assert eng.prefix_hits >= 1           # the shared document was reused
    for i, p in enumerate(prompts):
        served = eng.responses[i]
        seq = np.concatenate([p, served[:-1]]).astype(np.int32)
        pos = np.arange(len(p) - 1, len(seq))
        lg = np.asarray(ref.logits(w, cj, seq, pos))
        gap = lg.max(-1) - lg[np.arange(len(pos)), served]
        assert gap.max() <= ATOL, (i, gap)


def test_span_counts_match_reference_router(model):
    """``picks_here`` and ``expert_rows`` of the engine's prefill and decode
    spans: the reference router's held picks over every token the engine
    computed, and the rows its sweep computed (padding and idle slots
    included)."""
    cj, cfg, params, w = model
    p = _prompt(21, 9)
    t0 = time.perf_counter()
    eng = _serve(cfg, params, [p], gen=5)
    recs = [s for s in spans.between(t0, float("inf"))
            if s.name in ("engine.prefill", "engine.decode")]
    pre = [s for s in recs if s.name == "engine.prefill"]
    dec = [s for s in recs if s.name == "engine.decode"]
    assert len(pre) == 1 and len(dec) == 4
    seq = np.concatenate([p, eng.responses[0][:-1]]).astype(np.int32)
    picks = np.asarray(ref.held_picks(w, cj, seq))
    n_moe = cj["num_hidden_layers"] - 1
    assert pre[0].attrs["picks_here"] == picks[:21].sum()
    assert pre[0].attrs["expert_rows"] == 24 * 4 * n_moe   # width 3 pages
    assert [s.attrs["picks_here"] for s in dec] == list(picks[21:])
    assert all(s.attrs["expert_rows"] == 2 * 4 * n_moe for s in dec)


def _moe_params(params):
    m = params["decoder"]["groups"]["0"]["moe"]
    return jax.tree.map(lambda a: a[0], m)


def test_shares_sum_to_the_uncut_layer(model):
    """Eight chips' shares of one MoE layer, each holding one routing group
    (the shared experts counted once), add up to the uncut reference
    layer."""
    cj, cfg, params, w = model
    E, n_group = 16, 8
    full = dataclasses.replace(cfg, num_experts_held=0, first_held_expert=0)
    fp = ds.make_params(full, 5)
    m = _moe_params(fp)
    x = jax.random.normal(jax.random.key(1), (1, 37, 64), jnp.float32)
    ctx = Ctx(dtype=jnp.float32)
    per = E // n_group
    total = 0.0
    for g in range(n_group):
        share = dataclasses.replace(cfg, num_experts_held=per,
                                    first_held_expert=g * per)
        sl = slice(g * per, (g + 1) * per)
        p = {**m, "we_g": m["we_g"][sl], "we_u": m["we_u"][sl],
             "we_d": m["we_d"][sl]}
        out, _ = moe_ffn(share, p, x, ctx, dropless=True)
        if g:
            out = out - _shared(m, x)
        total = total + out
    cu = dict(ref.run_values(tiny_json(n_routed_experts=16,
                                       first_held_expert=0)))
    lw = {"router": m["router"], "e_gate": m["we_g"], "e_up": m["we_u"],
          "e_down": m["we_d"], "s_gate": m["ws_g"], "s_up": m["ws_u"],
          "s_down": m["ws_d"]}
    with jax.default_matmul_precision("highest"):
        want, _ = ref.moe(cu, lw, x[0], "f32")
    np.testing.assert_allclose(total[0], want, atol=1e-5)


def _shared(m, x):
    h = jax.nn.silu(x @ m["ws_g"]) * (x @ m["ws_u"])
    return h @ m["ws_d"]


def _brute_force(probs, n_group, topk_group, k, scale, norm):
    out_w, out_e = [], []
    for row in probs:
        groups = row.reshape(n_group, -1)
        best = np.argsort(-groups.max(-1), kind="stable")[:topk_group]
        cand = [(row[e], e) for g in best
                for e in range(g * groups.shape[1], (g + 1) * groups.shape[1])]
        cand.sort(key=lambda t: (-t[0], t[1]))
        wt = np.array([c[0] for c in cand[:k]])
        if norm:
            wt = wt / wt.sum()
        out_w.append(wt * scale)
        out_e.append(sorted(c[1] for c in cand[:k]))
    return np.array(out_w), np.array(out_e)


def test_group_limited_greedy_matches_brute_force():
    cfg = get_config("deepseek-v2-236b")
    probs = np.asarray(jax.nn.softmax(jax.random.normal(
        jax.random.key(3), (64, 160)) * 3, -1))
    w, e = route(cfg, jnp.asarray(probs))
    bw, be = _brute_force(probs, 8, 3, 6, 16.0, False)
    assert (np.sort(np.asarray(e), -1) == be).all()
    np.testing.assert_allclose(np.sort(np.asarray(w), -1), np.sort(bw, -1),
                               rtol=1e-6)
    # every pick lies in one of the 3 groups kept
    assert all(len({int(x) // 20 for x in row}) <= 3 for row in np.asarray(e))


def test_granite_routing_unchanged():
    """Granite keeps plain top-k of the softmax, renormalised, unscaled."""
    cfg = get_config("granite-moe-1b-a400m")
    assert (cfg.n_group, cfg.routed_scaling_factor, cfg.norm_topk_prob,
            cfg.experts_held) == (1, 1.0, True, 32)
    probs = jax.nn.softmax(jax.random.normal(jax.random.key(4), (50, 32)), -1)
    w, e = route(cfg, probs)
    tp, te = jax.lax.top_k(probs, 8)
    np.testing.assert_array_equal(e, te)
    np.testing.assert_allclose(w, tp / tp.sum(-1, keepdims=True), rtol=1e-6)


def test_yarn_against_closed_form():
    assert yarn_correction_range(64, 10000.0, 4096, 32, 1) == (10, 23)
    cfg = get_config("deepseek-v2-236b")
    got = np.asarray(rope_freqs(64, 10000.0, cfg.rope_yarn))
    base = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    np.testing.assert_allclose(got, base / 40 * ramp + base * (1 - ramp),
                               rtol=1e-6)
    assert got[:10] == pytest.approx(base[:10])        # below low: unscaled
    assert got[23:] == pytest.approx(base[23:] / 40)   # above high: / 40
    from repro.models.attention import mla_scale
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert mscale == pytest.approx(1.2608, abs=1e-4)
    assert mla_scale(cfg) == pytest.approx(192 ** -0.5 * mscale ** 2)
    assert mla_scale(cfg) == pytest.approx(0.11472, abs=1e-5)


def _unblocked(q_nope, q_rope, w_kc, w_vc, ckv, krope, pos_k, pos_q, scale):
    q_eff = jnp.einsum("bshe,lhe->bshl", q_nope, w_kc)
    s = (jnp.einsum("bshl,btl->bsht", q_eff, ckv)
         + jnp.einsum("bshr,btr->bsht", q_rope, krope)) * scale
    ok = (pos_k[:, None, None, :] >= 0) \
        & (pos_k[:, None, None, :] <= pos_q[:, :, None, None])
    p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), -1)
    return jnp.einsum("bshl,lhe->bshe",
                      jnp.einsum("bsht,btl->bshl", p, ckv), w_vc)


def _mla_inputs(S0, T, H=4, lora=32, rd=8, nope=16, vd=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    n = jax.random.normal
    return (n(ks[0], (1, S0, H, nope)), n(ks[1], (1, S0, H, rd)),
            n(ks[2], (lora, H, nope)) * 0.2, n(ks[3], (lora, H, vd)) * 0.2,
            n(ks[4], (1, T, lora)), n(ks[5], (1, T, rd)))


def test_blocked_mla_prefill_equals_unblocked():
    from repro.models.attention import (mla_prefill_attention_fresh,
                                        mla_prefill_attention_paged)
    S0, start, ps = 40, 24, 8
    T = start + S0
    qn, qr, wk, wv, ckv, kr = _mla_inputs(S0, T)
    # the fresh form: keys are the chunk itself
    got = mla_prefill_attention_fresh(qn, qr, wk, wv, ckv[:, :S0],
                                      kr[:, :S0], jnp.array([S0]), scale=0.3,
                                      q_block=16, k_block=16)
    pos = jnp.arange(S0)[None]
    want = _unblocked(qn, qr, wk, wv, ckv[:, :S0], kr[:, :S0], pos, pos, 0.3)
    np.testing.assert_allclose(got, want, atol=1e-5)
    # the paged form: a chunk opening at ``start`` over a shuffled table,
    # in layer 1 of a two-layer stack whose layer 0 holds other latents
    pages = T // ps
    perm = np.random.default_rng(0).permutation(pages + 3)[:pages]
    noise = jax.random.normal(jax.random.key(9), (2, pages + 3, ps, 40))
    pool_c = noise[..., :32].at[1, perm].set(ckv[0].reshape(pages, ps, 32))
    pool_r = noise[..., 32:].at[1, perm].set(kr[0].reshape(pages, ps, 8))
    table = jnp.asarray(np.concatenate([perm, [-1, -1]])[None], jnp.int32)
    pos_q = start + jnp.arange(S0)[None]
    layer = jnp.int32(1)
    got = mla_prefill_attention_paged(qn, qr, wk, wv, pool_c, pool_r, table,
                                      pos_q, jnp.array([S0]), layer,
                                      scale=0.3, q_block=16, k_block=16)
    want = _unblocked(qn, qr, wk, wv, ckv, kr, jnp.arange(T)[None], pos_q,
                      0.3)
    np.testing.assert_allclose(got, want, atol=1e-5)
    # rows past ``lengths`` (padding, inactive slots) read zeros
    got = mla_prefill_attention_paged(qn, qr, wk, wv, pool_c, pool_r, table,
                                      pos_q, jnp.array([10]), layer,
                                      scale=0.3, q_block=16, k_block=16)
    assert not np.asarray(got[0, 10:]).any()
    np.testing.assert_allclose(got[0, :10], want[0, :10], atol=1e-5)


def test_blocked_mla_prefill_memory_grows_linearly():
    """No tensor of the blocked prefill grows with S0·T: its compiled
    temporaries at most double from S0 = T = 1024 to 2048."""
    from repro.models.attention import mla_prefill_attention_paged

    def temp(S):
        H, lora, rd, nope, ps = 16, 128, 32, 64, 64
        pages = S // ps
        args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in
                [(1, S, H, nope), (1, S, H, rd), (lora, H, nope),
                 (lora, H, nope), (1, pages, ps, lora), (1, pages, ps, rd)]]
        args += [jax.ShapeDtypeStruct((1, pages), jnp.int32),
                 jax.ShapeDtypeStruct((1, S), jnp.int32),
                 jax.ShapeDtypeStruct((1,), jnp.int32),
                 jax.ShapeDtypeStruct((), jnp.int32)]
        f = jax.jit(lambda *a: mla_prefill_attention_paged(*a, scale=0.1))
        return f.lower(*args).compile().memory_analysis().temp_size_in_bytes

    t1, t2 = temp(1024), temp(2048)
    # an (S0, H, T) float32 score tensor alone would be 16x 64 MiB at 1024
    assert t2 <= 2.05 * t1, (t1, t2)
    assert t1 < 1024 * 16 * 1024 * 4, t1


def test_dropless_moe_row_wider_than_a_group(model):
    """A one-row prefill 1,280 tokens wide (not a multiple of the training
    group of 1,024) runs dropless, and each token's output is its own."""
    _, cfg, params, _ = model
    m = _moe_params(params)
    x = jax.random.normal(jax.random.key(2), (1, 1280, 64), jnp.float32)
    ctx = Ctx(dtype=jnp.float32)
    out, counts = moe_ffn(cfg, m, x, ctx, dropless=True)
    part, _ = moe_ffn(cfg, m, x[:, 1000:1010], ctx, dropless=True)
    np.testing.assert_allclose(out[:, 1000:1010], part, atol=1e-5)
    assert float(counts[1]) == 1280 * cfg.experts_held
