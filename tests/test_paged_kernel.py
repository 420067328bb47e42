"""Paged flash-decode kernel + ragged prefill: op-level equivalence on
ragged page tables, the unallocated-page gather bugfix, the int8 wire
round-trip, and the serve-decode benchmark smoke."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.ops import paged_decode_bhd
from repro.kernels.paged_attention import paged_decode_jnp
from repro.models.attention import decode_attention_jnp, decode_attention_paged
from repro.models.layers import Ctx
from repro.models.model import forward, init_cache
from repro.models.params import init_params

RNG = np.random.default_rng(7)
#: pools are token-major stacks of LAYERS layers; every walk reads LAYER,
#: so one that addresses another layer reads other random pages
LAYERS, LAYER = 3, jnp.int32(1)


def _stack(P, ps, W):
    return jnp.asarray(RNG.normal(size=(LAYERS, P, ps, W)), jnp.float32)


def _pool(B, K, hd, ps, pps, pool=None):
    P = pool or B * pps
    q = jnp.asarray(RNG.normal(size=(B, 1, 2 * K, hd)), jnp.float32)
    return q, _stack(P, ps, K * hd), _stack(P, ps, K * hd), P


def _ragged_tables(B, pps, P, live_pages):
    """Contiguous-prefix allocations of distinct physical pages, -1 tail."""
    table = np.full((B, pps), -1, np.int32)
    perm = RNG.permutation(P)
    used = 0
    for b, n in enumerate(live_pages):
        table[b, :n] = perm[used:used + n]
        used += n
    return jnp.asarray(table)


# ---------------------------------------------------------------------------
# Kernel ≡ reference ≡ scan fallback ≡ dense, on ragged tables
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("hd", [16, 64])
@pytest.mark.parametrize("logit_cap", [0.0, 30.0])
def test_kernel_matches_reference_ragged(logit_cap, hd):
    """Ragged tables (different live-page counts per row, partially filled
    last page, -1 holes in the tail) with per-sequence pos_q: the Pallas
    kernel (interpret), the lax.scan fallback, and the gather reference
    agree in fp32 on every active row, reading one layer of the stack."""
    B, K, ps, pps = 4, 2, 8, 6
    q, kp, vp, P = _pool(B, K, hd, ps, pps)
    table = _ragged_tables(B, pps, P, [3, 6, 1, 4])
    # row positions: partial last page (19 in page 2 of 3), full table,
    # single token, inactive slot
    pos = jnp.asarray([19, 47, 0, -1], jnp.int32)
    kw = dict(scale=hd ** -0.5, logit_cap=logit_cap)

    ref = decode_attention_paged(q, kp, vp, table, pos, LAYER, **kw)
    ker = paged_decode_bhd(q, kp, vp, table, pos, LAYER, **kw)
    H = q.shape[2]
    scan = paged_decode_jnp(q.reshape(B, K, H // K, hd), kp, vp, table, pos,
                            LAYER, **kw).reshape(B, 1, H, hd)
    active = slice(0, 3)                       # row 3 is the inactive slot
    np.testing.assert_allclose(np.asarray(ker[active]),
                               np.asarray(ref[active]), atol=2e-6)
    np.testing.assert_allclose(np.asarray(scan[active]),
                               np.asarray(ref[active]), atol=2e-6)
    # inactive rows: the kernel/scan contract is zeros (ignored by callers)
    assert float(jnp.abs(ker[3]).max()) == 0.0
    assert float(jnp.abs(scan[3]).max()) == 0.0


@pytest.mark.parametrize("hd", [16, 64, 128])
@pytest.mark.parametrize("logit_cap", [0.0, 30.0])
def test_grouped_kernel_matches_ungrouped_and_oracle(logit_cap, hd):
    """The grouped (head-tiled, one-MXU-call-per-page) variant ≡ the
    narrowest lane tile ≡ the scan fallback ≡ the gather oracle, on the
    same ragged tables.  G sweeps both sides of the old ``G <= 4``
    auto-cap (since removed — grouped is the default for every G) plus
    the non-divisor boundary G=5; hd sweeps tiles of several kv heads
    (16, 64: their block-diagonal queries) and of one (128)."""
    from repro.kernels.paged_attention import paged_decode_attention

    for G in (1, 2, 4, 5, 8):
        B, K, ps, pps = 4, 4, 8, 6
        P = B * pps
        q = jnp.asarray(RNG.normal(size=(B, K, G, hd)), jnp.float32)
        kp, vp = _stack(P, ps, K * hd), _stack(P, ps, K * hd)
        table = _ragged_tables(B, pps, P, [3, 6, 1, 4])
        pos = jnp.asarray([19, 47, 0, -1], jnp.int32)
        kw = dict(scale=hd ** -0.5, logit_cap=logit_cap)

        grp = paged_decode_attention(q, kp, vp, table, pos, LAYER,
                                     interpret=True, grouped=True, **kw)
        ung = paged_decode_attention(q, kp, vp, table, pos, LAYER,
                                     interpret=True, grouped=False, **kw)
        scan = paged_decode_jnp(q, kp, vp, table, pos, LAYER, **kw)
        ref = decode_attention_paged(
            q.reshape(B, 1, K * G, hd), kp, vp, table, pos, LAYER,
            **kw).reshape(B, K, G, hd)
        act = slice(0, 3)                      # row 3 is the inactive slot
        np.testing.assert_allclose(np.asarray(grp[act]), np.asarray(ung[act]),
                                   atol=2e-6)
        np.testing.assert_allclose(np.asarray(grp[act]),
                                   np.asarray(scan[act]), atol=2e-6)
        np.testing.assert_allclose(np.asarray(grp[act]), np.asarray(ref[act]),
                                   atol=2e-6)
        assert float(jnp.abs(grp[3]).max()) == 0.0  # inactive row → zeros


def test_group_tile_and_default_grouped():
    """The head tiler returns the largest divisor of K whose fused block
    stays within the MXU budget, raised until its columns fill whole
    128-lane rows of the token-major pool (the whole row where no divisor
    does); the grouped grid is the default for every G."""
    from repro.kernels.paged_attention import (
        group_tile, lane_tile, paged_decode_attention, paged_layout)

    assert group_tile(2, 2, 128) == 2     # whole K fuses: 2·2 ≤ 8
    assert group_tile(8, 1, 128) == 8
    assert group_tile(4, 4, 128) == 2     # 4·4 > 8 → tile at 2
    assert group_tile(2, 5, 128) == 1     # 2·5 > 8 → per-head
    assert group_tile(2, 8, 128) == 1     # G > budget: one head per tile
    assert group_tile(3, 4, 128) == 1     # non-divisor G, prime-ish K
    assert group_tile(8, 2, 64) == 4      # granite: 256 lanes
    assert group_tile(8, 2, 128) == 4     # qwen3: 512 lanes
    assert group_tile(2, 8, 64) == 2      # one head is 64 lanes: take two
    assert group_tile(3, 4, 16) == 3      # no divisor fills 128: whole row
    assert lane_tile(8, 128) == 1 and lane_tile(8, 64) == 2
    assert lane_tile(8, 32) == 4 and lane_tile(1, 64) == 1
    for K, G, hd in ((8, 2, 64), (8, 2, 128), (2, 8, 64), (4, 2, 128)):
        for grouped in (True, False):
            cols = paged_layout(2, K, G, hd, 128, 4, 8,
                                grouped=grouped).in_specs[1].block[-1]
            assert cols % 128 == 0 or cols == K * hd, (K, G, hd, cols)

    B, K, G, hd, ps, pps = 2, 2, 8, 16, 8, 3
    P = B * pps
    q = jnp.asarray(RNG.normal(size=(B, K, G, hd)), jnp.float32)
    kp, vp = _stack(P, ps, K * hd), _stack(P, ps, K * hd)
    table = _ragged_tables(B, pps, P, [2, 3])
    pos = jnp.asarray([10, 23], jnp.int32)
    kw = dict(scale=hd ** -0.5, logit_cap=0.0)
    auto = paged_decode_attention(q, kp, vp, table, pos, LAYER,
                                  interpret=True, **kw)
    grp = paged_decode_attention(q, kp, vp, table, pos, LAYER,
                                 interpret=True, grouped=True, **kw)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(grp))


def test_mla_kernel_matches_scan_and_oracle():
    """MLA latent flash-decode on ragged tables: the Pallas kernel
    (interpret), the lax.scan fallback, and a dense gather oracle agree —
    scores over concat(ckv, k_rope) latents, values = ckv, inactive rows
    zero."""
    from repro.kernels.ops import mla_paged_decode_bhd
    from repro.kernels.paged_attention import (
        mla_paged_decode_attention, mla_paged_decode_jnp)

    B, H, lora, rd, ps, pps = 4, 3, 16, 8, 8, 6
    P = B * pps
    q = jnp.asarray(RNG.normal(size=(B, H, lora + rd)), jnp.float32)
    ckv, krope = _stack(P, ps, lora), _stack(P, ps, rd)
    table = _ragged_tables(B, pps, P, [3, 6, 1, 4])
    pos = jnp.asarray([19, 47, 0, -1], jnp.int32)
    scale = (lora + rd) ** -0.5

    ker = mla_paged_decode_attention(q, ckv, krope, table, pos, LAYER,
                                     scale=scale, interpret=True)
    scan = mla_paged_decode_jnp(q, ckv, krope, table, pos, LAYER,
                                scale=scale)
    ops = mla_paged_decode_bhd(q, ckv, krope, table, pos, LAYER,
                               scale=scale)

    # dense oracle: gather each row's live tokens, full softmax in fp64
    tnp, pnp = np.asarray(table), np.asarray(pos)
    qn = np.asarray(q, np.float64)
    oracle = np.zeros((B, H, lora))
    for b in range(B):
        if pnp[b] < 0:
            continue
        ks, vs = [], []
        for t in range(pnp[b] + 1):
            page = tnp[b, t // ps]
            assert page >= 0
            ks.append(np.concatenate([np.asarray(ckv[LAYER, page, t % ps]),
                                      np.asarray(krope[LAYER, page, t % ps])]))
            vs.append(np.asarray(ckv[LAYER, page, t % ps]))
        kmat, vmat = np.stack(ks), np.stack(vs)          # (T, lora+rd/lora)
        s = qn[b] @ kmat.T * scale                       # (H, T)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        oracle[b] = p @ vmat
    act = slice(0, 3)
    np.testing.assert_allclose(np.asarray(ker[act]), oracle[act], atol=2e-6)
    np.testing.assert_allclose(np.asarray(scan[act]), oracle[act], atol=2e-6)
    np.testing.assert_allclose(np.asarray(ops[act]), oracle[act], atol=2e-6)
    assert float(jnp.abs(ker[3]).max()) == 0.0
    assert float(jnp.abs(scan[3]).max()) == 0.0


def test_kernel_matches_dense_layout():
    """Paged walks ≡ the dense cache layout: pack the same K/V into a
    dense (B, K, T, hd) buffer and into pages, same masked softmax."""
    B, K, hd, ps, pps = 3, 2, 16, 8, 4
    T = pps * ps
    q, kp, vp, P = _pool(B, K, hd, ps, pps)
    live = [4, 2, 3]
    table = _ragged_tables(B, pps, P, live)
    pos = jnp.asarray([T - 1, 11, 17], jnp.int32)
    scale = hd ** -0.5

    # scatter pages into the dense layout
    kd = np.zeros((B, K, T, hd), np.float32)
    vd = np.zeros((B, K, T, hd), np.float32)
    tnp = np.asarray(table)
    page = lambda pool, i: np.asarray(pool[LAYER, i]).reshape(
        ps, K, hd).transpose(1, 0, 2)                    # (K, ps, hd)
    for b in range(B):
        for i in range(pps):
            if tnp[b, i] >= 0:
                kd[b, :, i * ps:(i + 1) * ps] = page(kp, tnp[b, i])
                vd[b, :, i * ps:(i + 1) * ps] = page(vp, tnp[b, i])
    pos_k = np.broadcast_to(np.arange(T, dtype=np.int32), (B, T)).copy()
    for b in range(B):
        pos_k[b, np.repeat(tnp[b] < 0, ps)] = -1

    dense = decode_attention_jnp(q, jnp.asarray(kd), jnp.asarray(vd),
                                 jnp.asarray(pos_k), pos, scale=scale)
    ker = paged_decode_bhd(q, kp, vp, table, pos, LAYER, scale=scale)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(dense), atol=2e-6)


def test_unallocated_pages_never_gathered():
    """Bugfix: the old reference clamped -1 table entries to 0 and gathered
    physical page 0 for every hole.  Poison page 0 with NaN and keep it
    out of every table: no walk may touch it."""
    B, K, hd, ps, pps = 2, 2, 16, 8, 4
    q, kp, vp, P = _pool(B, K, hd, ps, pps)
    kp = kp.at[LAYER, 0].set(jnp.nan)
    vp = vp.at[LAYER, 0].set(jnp.nan)
    table = np.full((B, pps), -1, np.int32)
    table[0, :2] = [3, 5]                      # page 0 unused everywhere
    table[1, :1] = [7]
    table = jnp.asarray(table)
    pos = jnp.asarray([12, 4], jnp.int32)
    kw = dict(scale=hd ** -0.5)
    for out in (decode_attention_paged(q, kp, vp, table, pos, LAYER, **kw),
                paged_decode_bhd(q, kp, vp, table, pos, LAYER, **kw)):
        assert not bool(jnp.isnan(out).any()), "page 0 leaked into the walk"


def test_aliased_prefix_pages_match_dealiased_oracle():
    """Prefix caching aliases ONE physical page into many rows' tables.
    Every decode walk reads K/V pages without mutation, so rows sharing
    physical prefix pages must produce bitwise the same output as rows
    reading private de-aliased copies of those pages — for the gather
    reference, the scan fallback, and the Pallas kernel (interpret)."""
    from repro.kernels.paged_attention import paged_decode_attention

    B, K, hd, ps, pps = 3, 2, 16, 8, 5
    q, kp, vp, _ = _pool(B, K, hd, ps, pps, pool=8)
    # pages 0,1 are the shared prefix in every row; private tails differ
    aliased = jnp.asarray([[0, 1, 2, 3, -1],
                           [0, 1, 4, -1, -1],
                           [0, 1, 5, 6, 7]], jnp.int32)
    # oracle pool: rows 1 and 2 get their own verbatim copies at 8..11
    kp2 = jnp.concatenate([kp, kp[:, jnp.asarray([0, 1, 0, 1])]], axis=1)
    vp2 = jnp.concatenate([vp, vp[:, jnp.asarray([0, 1, 0, 1])]], axis=1)
    dealiased = jnp.asarray([[0, 1, 2, 3, -1],
                             [8, 9, 4, -1, -1],
                             [10, 11, 5, 6, 7]], jnp.int32)
    pos = jnp.asarray([27, 20, 39], jnp.int32)
    kw = dict(scale=hd ** -0.5)

    for fn in (
        lambda k_, v_, t: decode_attention_paged(q, k_, v_, t, pos, LAYER,
                                                 **kw),
        lambda k_, v_, t: paged_decode_jnp(
            q.reshape(B, K, 2, hd), k_, v_, t, pos, LAYER, **kw),
        lambda k_, v_, t: paged_decode_attention(
            q.reshape(B, K, 2, hd), k_, v_, t, pos, LAYER, interpret=True,
            **kw),
    ):
        shared = fn(kp, vp, aliased)
        oracle = fn(kp2, vp2, dealiased)
        np.testing.assert_array_equal(np.asarray(shared), np.asarray(oracle))


# ---------------------------------------------------------------------------
# Ragged prefill
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ["qwen3-0.6b", "gemma2-9b",
                                  "recurrentgemma-9b", "rwkv6-7b",
                                  "deepseek-v2-236b"])
def test_ragged_prefill_matches_padded(arch):
    """One batched ragged prefill (prompts padded to the batch max, per-row
    lengths) must produce, per row, the same last-token logits as prefilling
    that row alone at its exact length — and identical follow-on decode.
    Covers attention (paged writes masked per row), MLA (latent scatter),
    and recurrent/RWKV stacks (length-masked carries)."""
    cfg = dataclasses.replace(get_config(arch).reduced(),
                              cache_layout="paged")
    ctx = Ctx(dtype=jnp.float32)
    params = init_params(cfg, jax.random.key(0))
    B, S = 3, 40
    lens = [28, 17, 9]
    toks = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)

    cache = init_cache(cfg, B, S)
    padded = jnp.where(jnp.arange(max(lens))[None, :] <
                       jnp.asarray(lens)[:, None],
                       toks[:, :max(lens)], 0)
    rag_logits, rag_cache, _ = forward(
        cfg, params, {"tokens": padded}, ctx, mode="prefill", cache=cache,
        lengths=jnp.asarray(lens, jnp.int32))

    for b, L in enumerate(lens):
        solo_cache = init_cache(cfg, 1, S)
        solo_logits, _, _ = forward(
            cfg, params, {"tokens": toks[b:b + 1, :L]}, ctx,
            mode="prefill", cache=solo_cache)
        err = float(jnp.abs(rag_logits[b] - solo_logits[0]).max())
        assert err < 1e-4, (arch, b, err)

    # follow-on decode at per-row positions stays consistent with a
    # lockstep decode of row 0 alone
    solo_cache = init_cache(cfg, 1, S)
    _, solo_cache, _ = forward(cfg, params, {"tokens": toks[:1, :lens[0]]},
                               ctx, mode="prefill", cache=solo_cache)
    tok = toks[:, lens[0]:lens[0] + 1]
    pos = jnp.asarray([lens[0], -1, -1], jnp.int32)
    d_rag, _, _ = forward(cfg, params, {"tokens": tok}, ctx, mode="decode",
                          cache=rag_cache, pos=pos)
    d_solo, _, _ = forward(cfg, params, {"tokens": tok[:1]}, ctx,
                           mode="decode", cache=solo_cache,
                           pos=jnp.asarray([lens[0]], jnp.int32))
    err = float(jnp.abs(d_rag[0] - d_solo[0]).max())
    assert err < 1e-4, (arch, err)


@pytest.mark.parametrize("arch", ["gemma2-9b", "recurrentgemma-9b",
                                  "rwkv6-7b", "deepseek-v2-236b"])
def test_ragged_prefill_preserves_other_rows(arch):
    """Length-0 rows (continuous-batching slots mid-decode) must come out
    of a ragged prefill byte-identical — the padded batch writes nothing
    through their page tables, ring buffers, latent pools, or recurrent
    carries."""
    cfg = dataclasses.replace(get_config(arch).reduced(),
                              cache_layout="paged")
    ctx = Ctx(dtype=jnp.float32)
    params = init_params(cfg, jax.random.key(0))
    B, S = 2, 32
    toks = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    cache = init_cache(cfg, B, S)
    _, cache, _ = forward(cfg, params, {"tokens": toks}, ctx,
                          mode="prefill", cache=cache)
    before = jax.tree.map(lambda x: np.asarray(x).copy(), cache)
    # ragged prefill that touches only... nobody (both rows length 0)
    _, after, _ = forward(cfg, params, {"tokens": toks[:, :8]}, ctx,
                          mode="prefill", cache=cache,
                          lengths=jnp.zeros((B,), jnp.int32))
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_serve_continuous_pallas_smoke():
    """End-to-end: continuous batching decoding through the interpret-mode
    Pallas kernel with ragged batched prefill."""
    from repro.launch import serve
    assert serve.main(["--reduced", "--batch", "2", "--prompt-len", "16",
                       "--gen", "4", "--continuous", "--requests", "3",
                       "--use-pallas"]) == 0


# ---------------------------------------------------------------------------
# int8 wire packing (dist.compression satellite)
# ---------------------------------------------------------------------------
def test_int8_pack_roundtrip():
    from repro.dist.compression import (
        CompressionConfig, _int8_leaf, pack_int8, unpack_int8,
        wire_bytes_int8)
    t = jnp.asarray(RNG.normal(size=(13, 29)) *
                    np.exp(3 * RNG.normal(size=(13, 29))), jnp.float32)
    # per-tensor packing reproduces the historical values path exactly
    cfg = CompressionConfig()
    payload, scales = pack_int8(t, cfg)
    assert payload.dtype == jnp.int8 and scales.shape == (1,)
    rt = unpack_int8(payload, scales, t.shape)
    np.testing.assert_array_equal(np.asarray(rt), np.asarray(_int8_leaf(t, cfg)))
    # per-chunk scales: tighter than per-tensor on heavy-tailed data,
    # odd sizes pad the payload, wire accounting matches
    cfgc = CompressionConfig(chunk_size=64)
    pc, sc = pack_int8(t, cfgc)
    assert pc.size == -(-t.size // 64) * 64
    assert sc.shape == (-(-t.size // 64),)
    assert wire_bytes_int8(t, cfgc) == pc.size + 4 * sc.size
    rtc = unpack_int8(pc, sc, t.shape)
    assert float(jnp.abs(rtc - t).mean()) < float(jnp.abs(rt - t).mean())
    # zero tensors ship scale 0 and decode to exact zeros
    pz, sz = pack_int8(jnp.zeros((5,)), cfgc)
    np.testing.assert_array_equal(np.asarray(unpack_int8(pz, sz, (5,))),
                                  np.zeros(5, np.float32))


def test_int8_error_feedback_still_exact():
    """Cumulative transmitted gradient stays exact through the *packed*
    wire path (error feedback carries the quantization residual)."""
    from repro.dist.compression import CompressionConfig, compress_grads
    cfg = CompressionConfig(chunk_size=32)
    g = {"w": jnp.asarray(RNG.normal(size=(50,)), jnp.float32)}
    err = {"w": jnp.zeros((50,), jnp.float32)}
    total_sent = jnp.zeros((50,))
    for _ in range(6):
        sent, err = compress_grads(g, err, cfg)
        total_sent = total_sent + sent["w"]
    target = 6 * g["w"]
    np.testing.assert_allclose(np.asarray(total_sent + err["w"]),
                               np.asarray(target), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Benchmark lane smoke (make bench-smoke / CI)
# ---------------------------------------------------------------------------
def test_serve_decode_bench_smoke():
    from benchmarks import serve_decode
    assert serve_decode.main(["--smoke", "--no-write"]) == 0


def test_decode_attn_bytes_pricing():
    """Reference pricing is occupancy-flat (table-bounded); kernel pricing
    scales with resident pages — 4x at 25% occupancy."""
    from repro.configs import SHAPES, RunConfig, get_config
    from repro.launch.specs import decode_attn_bytes
    cfg = dataclasses.replace(get_config("qwen3-0.6b"), cache_layout="paged")
    sh = SHAPES["decode_32k"]
    full = RunConfig(page_occupancy=1.0)
    quarter = RunConfig(page_occupancy=0.25)
    ref_f = decode_attn_bytes(cfg, sh, full, "reference")
    ref_q = decode_attn_bytes(cfg, sh, quarter, "reference")
    kern_q = decode_attn_bytes(cfg, sh, quarter, "kernel")
    assert ref_f == ref_q
    assert ref_q >= 4 * kern_q
    assert decode_attn_bytes(cfg, sh, full, "kernel") == ref_f
