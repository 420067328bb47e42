"""Attention: GQA (global / sliding-window) and DeepSeek-V2 MLA.

Two execution modes:

* ``full``   — train and prefill.  Flash-style **kv-chunked online-softmax**
  (never materializes the (S, S) score matrix; block sizes from Ctx).  The
  same math as ``kernels/flash_attention`` — the Pallas kernel replaces it
  when ``ctx.use_pallas`` on TPU.
* ``decode`` — one new token against a cache.  Global attention uses a
  positionally-indexed cache; local attention a ring buffer of ``window``
  slots; MLA uses the **latent cache + weight absorption** (the memory win
  that motivates MLA — expanding per-head K/V for 32k cached tokens would be
  O(S·H·hd)).

Keys are RoPE-rotated at *write* time, so cached keys never re-rotate.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import LOCAL_ATTN, ModelConfig
from repro.models.layers import Ctx, apply_rope, rms_norm, softcap, yarn_mscale

Cache = Dict[str, jax.Array]
NEG_INF = -2.0e38


# ---------------------------------------------------------------------------
# Core: blocked online-softmax attention (full mode)
# ---------------------------------------------------------------------------
def flash_attention_jnp(
    q: jax.Array,          # (B, Sq, H, hd)   positions 0..Sq-1
    k: jax.Array,          # (B, Sk, K, hd)   positions 0..Sk-1
    v: jax.Array,          # (B, Sk, K, vd)
    *,
    scale: float,
    causal: bool = True,
    window: int = 0,       # 0 = unlimited
    logit_cap: float = 0.0,
    q_block: int = 1024,
    kv_block: int = 1024,
) -> jax.Array:
    """Blocked flash attention with *static* block skipping.

    Positions are arange on both sides (full/prefill self-attention; for
    non-causal cross-attention every block is live).  The (q, kv) block loop
    is a python double loop, NOT lax.scan, intentionally:

    * blocks dead under the causal/window mask are skipped at trace time —
      causal costs ~S²/2, sliding-window costs O(S·W) instead of O(S²);
    * XLA's cost model counts while-bodies once; inline blocks keep the
      dry-run roofline FLOPs exact.

    This mirrors the grid of kernels/flash_attention.  fp32 accumulation.
    """
    B, Sq, H, hd = q.shape
    Sk, K = k.shape[1], k.shape[2]
    G = H // K
    vd = v.shape[-1]
    qg = q.reshape(B, Sq, K, G, hd).astype(jnp.float32) * scale

    q_block = min(q_block, Sq)
    kv_block = min(kv_block, Sk)
    outs = []
    for q0 in range(0, Sq, q_block):
        q1 = min(q0 + q_block, Sq)
        nq = q1 - q0
        m = jnp.full((B, nq, K, G), NEG_INF, jnp.float32)
        l = jnp.zeros((B, nq, K, G), jnp.float32)
        acc = jnp.zeros((B, nq, K, G, vd), jnp.float32)
        qc = qg[:, q0:q1]
        for t0 in range(0, Sk, kv_block):
            t1 = min(t0 + kv_block, Sk)
            if causal and t0 > q1 - 1:
                continue                       # entirely in the future
            if window and t1 - 1 < q0 - window + 1:
                continue                       # entirely before the window
            kc = k[:, t0:t1].astype(jnp.float32)
            vc = v[:, t0:t1].astype(jnp.float32)
            s = jnp.einsum("bskgd,btkd->bskgt", qc, kc)
            s = softcap(s, logit_cap)
            need_mask = (causal and t1 - 1 > q0) or \
                        (window and t0 < q1 - 1 - window + 1)
            if need_mask:
                pq = q0 + jnp.arange(nq)
                pk = t0 + jnp.arange(t1 - t0)
                valid = jnp.ones((nq, t1 - t0), bool)
                if causal:
                    valid &= pk[None, :] <= pq[:, None]
                if window:
                    valid &= pq[:, None] - pk[None, :] < window
                s = jnp.where(valid[None, :, None, None, :], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum("bskgt,btkd->bskgd", p, vc)
            m = m_new
        outs.append(acc / jnp.maximum(l, 1e-37)[..., None])
    out = jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    return out.reshape(B, Sq, H, vd).astype(q.dtype)


def decode_attention_jnp(
    q: jax.Array,          # (B, 1, H, hd)
    k: jax.Array,          # (B, K, Skv, hd)  cache layout, already rotated
    v: jax.Array,          # (B, K, Skv, vd)
    pos_k: jax.Array,      # (Skv,) or (B, Skv) absolute positions; -1 = invalid
    pos_q: jax.Array,      # scalar, or (B,) per-sequence positions
    *,
    scale: float,
    window: int = 0,
    logit_cap: float = 0.0,
) -> jax.Array:
    """One-token attention against a cache.  ``pos_k``/``pos_q`` may carry a
    leading batch dim (continuous batching decodes sequences at different
    positions); 1-D / scalar forms broadcast — the lockstep fast path."""
    B, _, H, hd = q.shape
    K = k.shape[1]
    G = H // K
    vd = v.shape[-1]
    qg = q.reshape(B, K, G, hd).astype(jnp.float32) * scale
    s = jnp.einsum("bkgd,bktd->bkgt", qg, k.astype(jnp.float32))
    s = softcap(s, logit_cap)
    pk = pos_k if pos_k.ndim == 2 else pos_k[None, :]          # (B|1, Skv)
    pq = jnp.reshape(jnp.asarray(pos_q, jnp.int32), (-1, 1))   # (B|1, 1)
    valid = (pk >= 0) & (pk <= pq)
    if window:
        valid = valid & (pq - pk < window)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,bktd->bkgd", p, v.astype(jnp.float32))
    return out.reshape(B, 1, H, vd).astype(q.dtype)


def _gather_pages(pages: jax.Array, layer, page_table: jax.Array,
                  K: int) -> jax.Array:
    """The table-bounded view of layer ``layer`` of a token-major pool
    stack, (B, K, pps·ps, d).  Unallocated entries are masked by every
    caller, so what they gather is never read."""
    B, pps = page_table.shape
    ps = pages.shape[2]
    g = pages.at[layer, page_table].get(mode="fill", fill_value=0)
    return g.reshape(B, pps * ps, K, -1).transpose(0, 2, 1, 3)


def prefill_attention_paged(
    q: jax.Array,            # (B, S0, H, hd) chunk queries, rotated
    k_pages: jax.Array,      # (L, P, page_size, K·hd) token-major pool stack
    v_pages: jax.Array,      # (L, P, page_size, K·vd)
    page_table: jax.Array,   # (B, pages_per_seq) int32; -1 = unallocated
    pos_q: jax.Array,        # (B, S0) absolute positions of the chunk queries
    lengths: jax.Array,      # (B,) valid chunk tokens; 0 = inactive row
    layer: jax.Array,        # () int32: the stack's layer to read
    *,
    scale: float,
    logit_cap: float = 0.0,
) -> jax.Array:
    """Chunked-prefill attention over the page table (prefix caching).

    The chunk's own K/V were already written into the pool
    (``_write_paged`` — write-then-read), so one masked walk
    serves both the *cached prefix* (shared, possibly aliased pages holding
    positions ``< pos_q``) and within-chunk causality: a key at slot ``t``
    of an allocated page is live iff ``t <= pos_q[b, s]``.  Rows with
    ``lengths == 0`` (slots mid-decode in a continuous batch) return zero
    rows the caller ignores.

    Like ``decode_attention_paged`` this is the reference-grade walk: the
    gather materializes the table-bounded (B, pps·ps, K, hd) view.  Tail
    chunks are short under prefix caching (the whole point), so the
    transient (B, S0, K, G, T) score block stays small; a Pallas chunk
    kernel is future work."""
    B, S0, H, hd = q.shape
    ps = k_pages.shape[2]
    K = k_pages.shape[3] // hd
    G = H // K
    pps = page_table.shape[1]
    T = pps * ps
    kb = _gather_pages(k_pages, layer, page_table, K)          # (B, K, T, hd)
    vb = _gather_pages(v_pages, layer, page_table, K)
    pos_k = jnp.where(jnp.repeat(page_table >= 0, ps, axis=1),
                      jnp.arange(T, dtype=jnp.int32)[None, :], -1)   # (B, T)
    qg = q.reshape(B, S0, K, G, hd).astype(jnp.float32) * scale
    s = jnp.einsum("bskgd,bktd->bskgt", qg, kb.astype(jnp.float32))
    s = softcap(s, logit_cap)
    valid = (pos_k[:, None, :] >= 0) \
        & (pos_k[:, None, :] <= pos_q[:, :, None]) \
        & (jnp.arange(S0, dtype=jnp.int32)[None, :, None]
           < lengths.astype(jnp.int32)[:, None, None])               # (B,S0,T)
    vm = valid[:, :, None, None, :]
    s = jnp.where(vm, s, NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    # mask p explicitly: fully-dead rows (inactive slots) would otherwise
    # see exp(NEG_INF - NEG_INF) == 1 (NEG_INF is a finite sentinel)
    p = jnp.where(vm, jnp.exp(s - m), 0.0)
    l = p.sum(axis=-1)
    out = jnp.einsum("bskgt,bktd->bskgd", p, vb.astype(jnp.float32))
    out = out / jnp.maximum(l, 1e-37)[..., None]
    return out.reshape(B, S0, H, vb.shape[-1]).astype(q.dtype)


def decode_attention_paged(
    q: jax.Array,            # (B, 1, H, hd)
    k_pages: jax.Array,      # (L, P, page_size, K·hd) token-major pool stack
    v_pages: jax.Array,      # (L, P, page_size, K·vd)
    page_table: jax.Array,   # (B, pages_per_seq) int32; -1 = unallocated
    pos_q: jax.Array,        # scalar or (B,) current position per sequence
    layer: jax.Array,        # () int32: the stack's layer to read
    *,
    scale: float,
    logit_cap: float = 0.0,
) -> jax.Array:
    """Paged decode attention: walk each sequence's page table, gather its
    pages from the pool, and run the same masked one-token softmax as the
    dense path.  Slot ``t`` of a sequence holds position ``t`` (global
    caches are position-indexed), so validity is ``t <= pos_q`` AND the
    page being allocated — identical math to the dense layout, which is
    what makes the paged/dense equivalence test exact.

    This is the *reference* walk: the gather materializes the table-bounded
    (B, pps·ps, K, hd) view, so per-step transient memory is bounded by the
    page-table length, not by what's resident.  The serving hot path uses
    ``kernels.paged_attention`` instead (Pallas flash-decode over the page
    table, or the O(pages) ``lax.scan`` fallback); this walk stays as the
    equivalence oracle and the benchmark baseline."""
    B, _, _, hd = q.shape
    ps = k_pages.shape[2]
    K = k_pages.shape[3] // hd
    pps = page_table.shape[1]
    kb = _gather_pages(k_pages, layer, page_table, K)          # (B, K, T, hd)
    vb = _gather_pages(v_pages, layer, page_table, K)
    T = pps * ps
    pos_k = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    alloc = jnp.repeat(page_table >= 0, ps, axis=1)  # (B, T)
    pos_k = jnp.where(alloc, pos_k, -1)
    return decode_attention_jnp(q, kb, vb, pos_k, pos_q, scale=scale,
                                logit_cap=logit_cap)


#: MLA prefill block sizes: the transient score block is
#: (B, MLA_Q_BLOCK, H, MLA_K_BLOCK) float32, whatever the prompt's length
MLA_Q_BLOCK = 256
MLA_K_BLOCK = 512


def _mla_blocked(q_nope, q_rope, w_kc, w_vc, key_block, n_kblocks: int,
                 k_block: int, pos_q, lengths, *, scale: float,
                 q_block: int) -> jax.Array:
    """Absorbed MLA attention in blocks: ``lax.map`` over query blocks,
    each an online softmax over the key blocks up to its last visible
    position (a dynamic trip count: causal blocks are skipped, not
    masked).  Per query block the W_kc-absorbed queries, the latent
    context and its W_vc read-out are made and dropped, so the transient
    is (B, q_block, H, k_block) and no tensor grows with S0·T.

    ``key_block(j)`` returns key block ``j``'s latents ``(B, kb, lora)``,
    rotated rope keys ``(B, kb, rd)`` and absolute positions ``(B, kb)``
    (-1 = no key).  A key is live for query ``s`` of row ``b`` iff its
    position is ``<= pos_q[b, s]`` and ``s < lengths[b]``; rows with no
    live key (inactive slots, padding) return zeros.  Returns
    (B, S0, H, vd) in ``q_nope``'s dtype."""
    B, S0, H, _ = q_nope.shape
    dt = q_nope.dtype
    qb = min(q_block, S0)
    nq = -(-S0 // qb)
    pad = nq * qb - S0
    live = jnp.arange(S0, dtype=jnp.int32)[None, :] \
        < lengths.astype(jnp.int32)[:, None]                       # (B, S0)
    pq = jnp.where(live, pos_q.astype(jnp.int32), -1)

    def blocks(x, fill):
        x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2),
                    constant_values=fill)
        return jnp.moveaxis(x.reshape(B, nq, qb, *x.shape[2:]), 1, 0)

    def one_block(args):
        qn, qr, pqb = args                                # (B, qb, ...)
        q_eff = jnp.einsum("bshe,lhe->bshl", qn, w_kc.astype(dt))
        q_eff = q_eff.astype(jnp.float32)
        qr = qr.astype(jnp.float32)
        n_k = jnp.minimum(jnp.max(pqb) // k_block + 1, n_kblocks)

        def step(j, carry):
            m, l, acc = carry
            cb, rb, pk = key_block(j)
            cb = cb.astype(jnp.float32)
            s = jnp.einsum("bshl,btl->bsht", q_eff, cb)
            s = s + jnp.einsum("bshr,btr->bsht", qr, rb.astype(jnp.float32))
            s = s * scale
            vm = ((pk[:, None, :] >= 0)
                  & (pk[:, None, :] <= pqb[:, :, None]))[:, :, None, :]
            s = jnp.where(vm, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            # explicit p-masking: a dead row's exp(NEG_INF - NEG_INF) == 1
            p = jnp.where(vm, jnp.exp(s - m_new[..., None]), 0.0)
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum("bsht,btl->bshl", p, cb)
            return m_new, l, acc

        init = (jnp.full((B, qb, H), NEG_INF, jnp.float32),
                jnp.zeros((B, qb, H), jnp.float32),
                jnp.zeros((B, qb, H, q_eff.shape[-1]), jnp.float32))
        _, l, acc = jax.lax.fori_loop(0, n_k, step, init)
        ctx_lat = (acc / jnp.maximum(l, 1e-37)[..., None]).astype(dt)
        return jnp.einsum("bshl,lhe->bshe", ctx_lat, w_vc.astype(dt))

    out = jax.lax.map(one_block, (blocks(q_nope, 0), blocks(q_rope, 0),
                                  blocks(pq, -1)))         # (nq, B, qb, H, vd)
    out = jnp.moveaxis(out, 0, 1).reshape(B, nq * qb, H, -1)
    return out[:, :S0]


def mla_prefill_attention_paged(
    q_nope: jax.Array,       # (B, S0, H, nope)
    q_rope: jax.Array,       # (B, S0, H, rd)   — rotated rope queries
    w_kc: jax.Array,         # (lora, H, nope)  — absorbed into the queries
    w_vc: jax.Array,         # (lora, H, vd)    — reads the latent context
    ckv_pages: jax.Array,    # (L, P, page_size, lora) latent pool stack
    krope_pages: jax.Array,  # (L, P, page_size, rd)
    page_table: jax.Array,   # (B, pages_per_seq) int32; -1 = unallocated
    pos_q: jax.Array,        # (B, S0) absolute positions of the chunk queries
    lengths: jax.Array,      # (B,) valid chunk tokens; 0 = inactive row
    layer: jax.Array,        # () int32: the stack's layer to read
    *,
    scale: float,
    q_block: int = MLA_Q_BLOCK,
    k_block: int = MLA_K_BLOCK,
) -> jax.Array:
    """Chunked/ragged MLA prefill over the latent page table, in blocks.

    The latent cache is MQA-shaped — ONE shared latent "kv head" serves
    all H query heads; scores are ``q_eff·ckv + q_rope·krope`` and the
    value read is the latent itself, turned into per-head values by
    ``W_vc``.  Same write-then-read contract as
    :func:`prefill_attention_paged`: the chunk's latents were already
    scattered into the pool, so one masked walk covers the cached prefix
    and within-chunk causality.  Key blocks are whole pages gathered from
    the table (``k_block`` rounded to pages).  Returns (B, S0, H, vd)."""
    B = q_nope.shape[0]
    ps = ckv_pages.shape[2]
    per = max(1, k_block // ps)                  # pages per key block
    n_k = -(-page_table.shape[1] // per)
    table = jnp.pad(page_table, [(0, 0), (0, n_k * per
                                          - page_table.shape[1])],
                    constant_values=-1)
    kb = per * ps

    def key_block(j):
        t = jax.lax.dynamic_slice_in_dim(table, j * per, per, axis=1)
        cb = ckv_pages.at[layer, t].get(mode="fill", fill_value=0)
        rb = krope_pages.at[layer, t].get(mode="fill", fill_value=0)
        pk = jnp.where(jnp.repeat(t >= 0, ps, axis=1),
                       j * kb + jnp.arange(kb, dtype=jnp.int32)[None, :], -1)
        return cb.reshape(B, kb, -1), rb.reshape(B, kb, -1), pk

    return _mla_blocked(q_nope, q_rope, w_kc, w_vc, key_block, n_k, kb,
                        pos_q, lengths, scale=scale, q_block=q_block)


def mla_prefill_attention_fresh(
    q_nope: jax.Array,       # (B, S0, H, nope)
    q_rope: jax.Array,       # (B, S0, H, rd)
    w_kc: jax.Array,
    w_vc: jax.Array,
    ckv: jax.Array,          # (B, S0, lora) the chunk's own latents
    krope: jax.Array,        # (B, S0, rd)   rotated
    lengths: jax.Array,      # (B,) valid tokens
    *,
    scale: float,
    q_block: int = MLA_Q_BLOCK,
    k_block: int = MLA_K_BLOCK,
) -> jax.Array:
    """MLA prefill of a chunk opening at position 0 against its own fresh
    latents, in blocks; causality isolates each row's last valid query
    from the ragged padding keys (they sit at later positions).  Returns
    (B, S0, H, vd)."""
    B, S0 = ckv.shape[:2]
    kb = min(k_block, S0)
    n_k = -(-S0 // kb)
    pad = [(0, 0), (0, n_k * kb - S0), (0, 0)]
    ckv, krope = jnp.pad(ckv, pad), jnp.pad(krope, pad)
    pos = jnp.arange(n_k * kb, dtype=jnp.int32)
    pos = jnp.where(pos < S0, pos, -1)

    def key_block(j):
        return (jax.lax.dynamic_slice_in_dim(ckv, j * kb, kb, axis=1),
                jax.lax.dynamic_slice_in_dim(krope, j * kb, kb, axis=1),
                jnp.broadcast_to(jax.lax.dynamic_slice_in_dim(
                    pos, j * kb, kb), (B, kb)))

    pos_q = jnp.broadcast_to(jnp.arange(S0, dtype=jnp.int32), (B, S0))
    return _mla_blocked(q_nope, q_rope, w_kc, w_vc, key_block, n_k, kb,
                        pos_q, lengths, scale=scale, q_block=q_block)


# ---------------------------------------------------------------------------
# GQA block
# ---------------------------------------------------------------------------
def _attn_scale(cfg: ModelConfig) -> float:
    if cfg.query_pre_attn_scalar > 0:
        return cfg.query_pre_attn_scalar ** -0.5
    return cfg.head_dim ** -0.5


def gqa_attention(
    cfg: ModelConfig,
    p: Dict[str, jax.Array],
    x: jax.Array,
    ctx: Ctx,
    *,
    kind: str,
    mode: str,                      # full | decode
    cache: Optional[Cache],
    pos: jax.Array,                 # full: (S,) positions; decode: scalar
    cross_kv: Optional[jax.Array] = None,   # encoder output for cross-attn
    is_cross: bool = False,
    causal: bool = True,
    lengths: Optional[jax.Array] = None,    # ragged prefill: (B,) true lens
) -> Tuple[jax.Array, Optional[Cache]]:
    B = x.shape[0]
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    window = cfg.window_size if kind == LOCAL_ATTN else 0
    scale = _attn_scale(cfg)

    q = jnp.einsum("bsd,dhk->bshk", x, p["q"].astype(x.dtype))
    if "qb" in p:
        q = q + p["qb"].astype(q.dtype)

    is_cross = is_cross or cross_kv is not None
    kv_src = cross_kv if cross_kv is not None else x
    if mode == "decode" and is_cross and cache is not None:
        # encoder K/V precomputed at prefill; cache layout (B, K, S, hd)
        k, v = cache["k"], cache["v"]
        new_cache = cache
    else:
        k = jnp.einsum("bsd,dhk->bshk", kv_src, p["k"].astype(kv_src.dtype))
        v = jnp.einsum("bsd,dhk->bshk", kv_src, p["v"].astype(kv_src.dtype))
        if "kb" in p:
            k = k + p["kb"].astype(k.dtype)
            v = v + p["vb"].astype(v.dtype)
        new_cache = None

    fresh_kv = not (mode == "decode" and is_cross and cache is not None)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        if fresh_kv:
            k = rms_norm(k, p["k_norm"], cfg.norm_eps)

    if mode == "full":
        q = ctx.constrain(q, ("batch", "seq", "heads", None))
        if not is_cross:
            k = apply_rope(k, pos, cfg.rope_theta)
        q = apply_rope(q, pos, cfg.rope_theta) if not is_cross else q
        if is_cross:
            out = flash_attention_jnp(
                q, k, v, scale=scale, causal=False,
                logit_cap=cfg.attn_logit_softcap,
                q_block=ctx.attn_q_block, kv_block=ctx.attn_kv_block)
            if cache is not None:       # prefill: stash encoder K/V
                new_cache = {"k": k.transpose(0, 2, 1, 3).astype(cache["k"].dtype),
                             "v": v.transpose(0, 2, 1, 3).astype(cache["v"].dtype)}
        elif pos.ndim == 2:
            # chunked prefix prefill (prefix caching): per-row absolute
            # positions — the chunk opens at each row's first uncached
            # token, and the cached prefix K/V already sit in (possibly
            # aliased) pages.  Write-then-read: the chunk's K/V go into
            # the private tail pages first, then ONE masked paged walk
            # covers both the cached prefix and within-chunk causality.
            if cache is None or "k_pages" not in cache or window:
                raise NotImplementedError(
                    "chunked prefix prefill needs the paged global layout")
            assert lengths is not None, "chunked prefill is ragged-only"
            new_cache = _write_paged(cache, k, v, pos, lengths)
            (kp, vp), layer = _stacks(new_cache, "k_pages", "v_pages")
            out = prefill_attention_paged(
                q, kp, vp, new_cache["page_table"], pos, lengths, layer,
                scale=scale, logit_cap=cfg.attn_logit_softcap)
        else:
            S = q.shape[1]
            if ctx.use_pallas and S % 128 == 0:
                from repro.kernels.ops import flash_attention_bshd
                out = flash_attention_bshd(
                    q, k, v, scale=scale, causal=causal, window=window,
                    logit_cap=cfg.attn_logit_softcap)
            else:
                out = flash_attention_jnp(
                    q, k, v, scale=scale, causal=causal, window=window,
                    logit_cap=cfg.attn_logit_softcap,
                    q_block=ctx.attn_q_block, kv_block=ctx.attn_kv_block)
            if cache is not None:       # prefill: write the kv cache
                if "k_pages" in cache:
                    # prefill opens at position 0; lockstep rows are whole
                    lens = jnp.full((B,), S, jnp.int32) if lengths is None \
                        else lengths
                    new_cache = _write_paged(
                        cache, k, v, jnp.broadcast_to(pos[None, :], (B, S)),
                        lens)
                elif lengths is not None:
                    if not window:
                        raise NotImplementedError(
                            "ragged prefill needs the paged layout for "
                            "global layers (dense caches are lockstep-only)")
                    # works for the true ring (W == window) and the short
                    # dense-local buffer (W == S_max < window) alike: the
                    # mod-W gather degenerates to the identity there
                    new_cache = _write_prefill_ring_ragged(
                        cache, k, v, lengths, cache["k"].shape[2])
                else:
                    new_cache = _write_full_kv(cache, k, v, pos, window)
    else:  # decode, self-attention
        # pos: scalar (lockstep batch) or (B,) per-sequence positions
        # (continuous batching; inactive slots carry -1).
        pos_r = jnp.reshape(pos, (-1, 1)) if pos.ndim else pos[None]
        q = apply_rope(q, pos_r, cfg.rope_theta)
        if not is_cross:
            k = apply_rope(k, pos_r, cfg.rope_theta)
            if "k_pages" in cache:
                assert not window, \
                    "paged layout covers global layers; local layers ring"
                posb = jnp.broadcast_to(
                    jnp.reshape(jnp.asarray(pos, jnp.int32), (-1,)),
                    (B,))
                new_cache = _write_paged(cache, k, v, posb[:, None],
                                         (posb >= 0).astype(jnp.int32))
                (kp, vp), layer = _stacks(new_cache, "k_pages", "v_pages")
                pt = new_cache["page_table"]
                if ctx.use_pallas:
                    from repro.kernels.ops import paged_decode_bhd
                    out = paged_decode_bhd(
                        q, kp, vp, pt, posb, layer, scale=scale,
                        logit_cap=cfg.attn_logit_softcap)
                else:
                    # O(pages) lax.scan walk — same contract as the kernel
                    from repro.kernels.paged_attention import paged_decode_jnp
                    out = paged_decode_jnp(
                        q.reshape(B, K, H // K, hd), kp, vp, pt, posb, layer,
                        scale=scale,
                        logit_cap=cfg.attn_logit_softcap).reshape(B, 1, H, hd)
            else:
                new_cache, k_all, v_all, pos_all = _update_decode_kv(
                    cache, k, v, pos, window)
                out = decode_attention_jnp(
                    q, k_all, v_all, pos_all, pos, scale=scale, window=window,
                    logit_cap=cfg.attn_logit_softcap)
        else:
            if fresh_kv:   # cross-attn decode without a prefilled cache
                k = k.transpose(0, 2, 1, 3)
                v = v.transpose(0, 2, 1, 3)
            pos_k = jnp.arange(k.shape[2], dtype=jnp.int32)
            out = decode_attention_jnp(
                q, k, v, pos_k, jnp.asarray(2**30, jnp.int32), scale=scale,
                logit_cap=cfg.attn_logit_softcap)
            new_cache = cache

    out = ctx.constrain(out, ("batch", "seq", "heads", None))
    return jnp.einsum("bshk,hkd->bsd", out, p["o"].astype(out.dtype)), new_cache


@jax.named_scope("kv_write")
def _write_full_kv(cache: Cache, k, v, pos, window: int) -> Cache:
    """Prefill: write rotated K/V into the cache buffer.

    Cache layout (B, K, S_max, hd).  Global cache is position-indexed with a
    shared ``pos (S_max,)`` slot map (prefill is lockstep); local cache keeps
    a ring of ``window`` slots with a *per-sequence* ``pos (B, W)`` map —
    slot = pos % window."""
    S_max = cache["k"].shape[2]
    k = k.transpose(0, 2, 1, 3)      # (B,S,K,hd) -> (B,K,S,hd)
    v = v.transpose(0, 2, 1, 3)
    if window and S_max == window:
        # ring buffer: only the last `window` positions survive; slicing to
        # them first makes the scatter indices unique (well-defined).
        k, v, pos = k[:, :, -window:], v[:, :, -window:], pos[-window:]
        slots = pos % window
        ck = cache["k"].at[:, :, slots].set(k.astype(cache["k"].dtype))
        cv = cache["v"].at[:, :, slots].set(v.astype(cache["v"].dtype))
        cp = cache["pos"].at[:, slots].set(pos[None, :].astype(jnp.int32))
        return {"k": ck, "v": cv, "pos": cp}
    ck = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k.astype(cache["k"].dtype), pos[0], axis=2)
    cv = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v.astype(cache["v"].dtype), pos[0], axis=2)
    cp = jax.lax.dynamic_update_slice_in_dim(
        cache["pos"], pos.astype(jnp.int32), pos[0], axis=0)
    return {"k": ck, "v": cv, "pos": cp}


def _stacks(cache: Cache, *names: str):
    """The named pools as layer stacks ``(L, P, ps, W)`` and the layer
    this call addresses: a scanned group passes its whole stacks with the
    scan index under ``"layer"`` (``models.model.run_stack``); an
    unrolled layer's own ``(P, ps, W)`` pool is a stack of one."""
    if "layer" in cache:
        return tuple(cache[n] for n in names), cache["layer"]
    return tuple(cache[n][None] for n in names), jnp.zeros((), jnp.int32)


def _with_stacks(cache: Cache, **pools) -> Cache:
    """``cache`` with its pools replaced by the written stacks, in the
    form it gave them (whole stacks, or one layer's pool)."""
    out = dict(cache)
    for name, x in pools.items():
        out[name] = x if "layer" in cache else x[0]
    return out


def _slots(page_table: jax.Array, pos: jax.Array, lengths: jax.Array,
           n_pool: int, ps: int) -> Tuple[jax.Array, jax.Array]:
    """Where each token of a (B, S0) block of absolute positions lands in
    the pool: ``(phys, off)``, page and slot, per token.  Tokens at or past
    ``lengths[b]``, past the table or on an unallocated entry get ``phys``
    one past the pool, which a ``mode="drop"`` scatter drops — the pool
    needs no scratch page, so its size stays mesh-divisible."""
    S0 = pos.shape[1]
    pps = page_table.shape[1]
    pidx = pos // ps
    entry = jnp.take_along_axis(page_table, jnp.clip(pidx, 0, pps - 1),
                                axis=1)
    valid = (jnp.arange(S0, dtype=jnp.int32)[None, :]
             < lengths.astype(jnp.int32)[:, None]) \
        & (pos >= 0) & (entry >= 0) & (pidx < pps)
    return jnp.where(valid, entry, jnp.int32(n_pool)), pos % ps


@jax.named_scope("kv_write")
def _write_paged(cache: Cache, k, v, pos, lengths) -> Cache:
    """Write K/V rows into the token-major page pool in place: token
    ``s`` of row ``b`` (``k, v`` arrive as (B, S0, K, hd), rotated) is
    one row of all its kv heads, ``pool[layer, phys, off, :]``, at
    absolute position ``pos[b, s]``.  Only tokens ``s < lengths[b]``
    write (decode: one token, length 0 for inactive slots).  The
    engine's CoW rule guarantees a chunk never writes a *shared* page
    (the first written page is always a private copy), so scatter
    targets are unique."""
    (kp, vp), layer = _stacks(cache, "k_pages", "v_pages")
    B, S0 = k.shape[:2]
    phys, off = _slots(cache["page_table"], pos, lengths, kp.shape[1],
                       kp.shape[2])
    kp = kp.at[layer, phys, off].set(
        k.reshape(B, S0, -1).astype(kp.dtype), mode="drop")
    vp = vp.at[layer, phys, off].set(
        v.reshape(B, S0, -1).astype(vp.dtype), mode="drop")
    return _with_stacks(cache, k_pages=kp, v_pages=vp)


@jax.named_scope("kv_write")
def _write_prefill_ring_ragged(cache: Cache, k, v, lengths: jax.Array,
                               window: int) -> Cache:
    """Ragged prefill into a ring buffer: row ``b`` keeps the last
    ``min(window, lengths[b])`` of its *own* tokens (a lockstep tail slice
    would keep the tail of the padded batch, dropping short rows' real
    tokens whenever the padding exceeds the window).

    Gather formulation: for ring slot ``s``, the surviving token is the
    largest position ``t < lengths[b]`` with ``t ≡ s (mod window)`` — a
    per-row ``take_along_axis``, so indices are unique by construction.
    Slots with no surviving token (short rows) keep their previous
    contents and stay masked via the recorded ``pos`` map."""
    S0 = k.shape[1]
    W = cache["k"].shape[2]
    assert W == window, (W, window)
    k = k.transpose(0, 2, 1, 3)      # (B, K, S0, hd)
    v = v.transpose(0, 2, 1, 3)
    s = jnp.arange(W, dtype=jnp.int32)
    lm1 = lengths.astype(jnp.int32)[:, None] - 1               # (B, 1)
    t = lm1 - ((lm1 - s[None, :]) % W)                         # (B, W)
    valid = (lengths[:, None] > 0) & (t >= 0) & \
        (t >= lengths[:, None] - W)
    tc = jnp.clip(t, 0, S0 - 1)
    kg = jnp.take_along_axis(k, tc[:, None, :, None], axis=2)  # (B, K, W, hd)
    vg = jnp.take_along_axis(v, tc[:, None, :, None], axis=2)
    ck = jnp.where(valid[:, None, :, None], kg.astype(cache["k"].dtype),
                   cache["k"])
    cv = jnp.where(valid[:, None, :, None], vg.astype(cache["v"].dtype),
                   cache["v"])
    cp = jnp.where(valid, t, cache["pos"])
    return {"k": ck, "v": cv, "pos": cp}


@jax.named_scope("kv_write")
def _update_decode_kv(cache: Cache, k, v, pos, window: int):
    """Insert one token's K/V; return (new_cache, k_all, v_all, pos_all).
    ``k, v`` arrive as (B, 1, K, hd); cache layout is (B, K, S, hd).

    ``pos`` may be per-sequence (B,) for ring buffers (continuous batching;
    inactive slots carry -1 and only dirty their own row).  Dense *global*
    caches are lockstep-only — per-sequence positions require the paged
    layout, which keeps the scatter per-row by construction."""
    ring = bool(window) and cache["k"].shape[2] == window
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    if pos.ndim == 1:
        if not ring:
            raise NotImplementedError(
                "per-sequence decode positions on a dense global cache; "
                "use cache_layout='paged' for continuous batching")
        B = k.shape[0]
        b = jnp.arange(B)
        slot = jnp.maximum(pos, 0) % window
        ck = cache["k"].at[b, :, slot].set(k[:, :, 0].astype(cache["k"].dtype))
        cv = cache["v"].at[b, :, slot].set(v[:, :, 0].astype(cache["v"].dtype))
        cp = cache["pos"].at[b, slot].set(pos.astype(jnp.int32))
        return {"k": ck, "v": cv, "pos": cp}, ck, cv, cp
    slot = (pos % window) if ring else pos
    ck = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k.astype(cache["k"].dtype), slot, axis=2)
    cv = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v.astype(cache["v"].dtype), slot, axis=2)
    if cache["pos"].ndim == 2:       # ring: per-sequence (B, W) slot map
        upd = jnp.broadcast_to(jnp.reshape(pos, (1, 1)),
                               (cache["pos"].shape[0], 1)).astype(jnp.int32)
        cp = jax.lax.dynamic_update_slice_in_dim(cache["pos"], upd, slot,
                                                 axis=1)
    else:                            # global: shared (S,) slot map
        cp = jax.lax.dynamic_update_slice_in_dim(
            cache["pos"], jnp.reshape(pos, (1,)).astype(jnp.int32), slot,
            axis=0)
    return {"k": ck, "v": cv, "pos": cp}, ck, cv, cp


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2)
# ---------------------------------------------------------------------------
@jax.named_scope("kv_write")
def _write_latent_paged(cache: Cache, ckv, krope, pos, lengths) -> Cache:
    """Scatter latents into the paged latent pool in place.  ``ckv (B,
    S0, lora)`` / ``krope (B, S0, rd)`` are the compressed latents and
    rotated rope keys; token ``s`` of row ``b`` lands at absolute
    position ``pos[b, s]`` — same contract as :func:`_write_paged`."""
    (cp, rp), layer = _stacks(cache, "ckv_pages", "krope_pages")
    phys, off = _slots(cache["page_table"], pos, lengths, cp.shape[1],
                       cp.shape[2])
    cp = cp.at[layer, phys, off].set(ckv.astype(cp.dtype), mode="drop")
    rp = rp.at[layer, phys, off].set(krope.astype(rp.dtype), mode="drop")
    return _with_stacks(cache, ckv_pages=cp, krope_pages=rp)


def _mla_q(cfg: ModelConfig, p, x, pos) -> Tuple[jax.Array, jax.Array]:
    """Returns (q_nope (B,S,H,nope), q_rope (B,S,H,rd)) — rope applied."""
    nope, rd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    if cfg.q_lora_rank:
        qa = rms_norm(x @ p["q_a"], p["q_norm"], cfg.norm_eps)
        q = jnp.einsum("bsl,lhk->bshk", qa, p["q_b"].astype(qa.dtype))
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["q"].astype(x.dtype))
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, pos, cfg.rope_theta, cfg.rope_yarn)
    return q_nope, q_rope


def mla_scale(cfg: ModelConfig) -> float:
    """The MLA softmax scale: (nope+rope)^-1/2, times YaRN's mscale^2
    where the rope is YaRN-scaled (DeepSeek-V2's ``mscale_all_dim``)."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.rope_yarn is None:
        return scale
    m = yarn_mscale(cfg.rope_scaling_factor, cfg.yarn_mscale)
    return scale * m * m


def mla_attention(
    cfg: ModelConfig,
    p: Dict[str, jax.Array],
    x: jax.Array,
    ctx: Ctx,
    *,
    mode: str,
    cache: Optional[Cache],
    pos: jax.Array,
    lengths: Optional[jax.Array] = None,   # ragged prefill: (B,) true lens
) -> Tuple[jax.Array, Optional[Cache]]:
    B = x.shape[0]
    H = cfg.num_heads
    nope, rd, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    lora = cfg.kv_lora_rank
    scale = mla_scale(cfg)
    yarn = cfg.rope_yarn
    kv_b = p["kv_b"]                                      # (lora, H, nope+vd)
    w_kc = kv_b[..., :nope]                               # (lora, H, nope)
    w_vc = kv_b[..., nope:]                               # (lora, H, vd)

    kv_a = x @ p["kv_a"]                                  # (B,S,lora+rd)
    ckv = rms_norm(kv_a[..., :lora], p["kv_norm"], cfg.norm_eps)
    k_rope = kv_a[..., None, lora:]                       # (B,S,1,rd) shared head

    paged = cache is not None and "ckv_pages" in cache
    if mode == "full" and paged:
        # ---- paged latent prefill.  Writes always scatter the chunk's
        # latents into the pool (length-masked per row).  The attention
        # read splits like the GQA path: lockstep/ragged chunks opening at
        # position 0 score against the FRESH latents (the pool stores the
        # cache dtype, and rounding keys through it would cost ~1e-3 vs the
        # dense oracle at float32), while chunked prefix prefill (2-D pos)
        # must read the pool — the cached prefix only exists there, and
        # both sides of a chunk split see identical pool bytes, keeping
        # replay bit-exact.  Both read in blocks (``_mla_blocked``).
        S0 = x.shape[1]
        pos_q = pos if pos.ndim == 2 else \
            jnp.broadcast_to(pos[None, :], (B, S0))       # (B, S0) absolute
        lens = jnp.full((B,), S0, jnp.int32) if lengths is None \
            else lengths.astype(jnp.int32)
        q_nope, q_rope = _mla_q(cfg, p, x, pos)
        k_rope = apply_rope(k_rope, pos, cfg.rope_theta, yarn)
        new_cache = _write_latent_paged(
            cache, ckv, k_rope[:, :, 0], pos_q, lens)
        if pos.ndim == 2:
            (cp, rp), layer = _stacks(new_cache, "ckv_pages", "krope_pages")
            out = mla_prefill_attention_paged(
                q_nope, q_rope, w_kc, w_vc, cp, rp, new_cache["page_table"],
                pos_q, lens, layer, scale=scale)
        else:
            out = mla_prefill_attention_fresh(
                q_nope, q_rope, w_kc, w_vc, ckv, k_rope[:, :, 0], lens,
                scale=scale)
    elif mode == "full":
        q_nope, q_rope = _mla_q(cfg, p, x, pos)
        k_rope = apply_rope(k_rope, pos, cfg.rope_theta, yarn)
        kv = jnp.einsum("bsl,lhe->bshe", ckv, kv_b.astype(ckv.dtype))  # expand
        k_nope, v = kv[..., :nope], kv[..., nope:]
        # fold the shared rope head into per-head keys
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (*k_rope.shape[:2], H, rd))], -1)
        q = jnp.concatenate([q_nope, q_rope], -1)
        q = ctx.constrain(q, ("batch", "seq", "heads", None))
        out = flash_attention_jnp(
            q, k, v, scale=scale, causal=True,
            q_block=ctx.attn_q_block, kv_block=ctx.attn_kv_block)
        new_cache = None
        if cache is not None:
            c = jax.lax.dynamic_update_slice_in_dim(
                cache["ckv"], ckv.astype(cache["ckv"].dtype), pos[0], axis=1)
            r = jax.lax.dynamic_update_slice_in_dim(
                cache["krope"], k_rope[:, :, 0].astype(cache["krope"].dtype),
                pos[0], axis=1)
            cp = jax.lax.dynamic_update_slice_in_dim(
                cache["pos"], pos.astype(jnp.int32), pos[0], axis=0)
            new_cache = {"ckv": c, "krope": r, "pos": cp}
    elif paged:
        # ---- paged latent decode: per-sequence positions (continuous
        # batching; inactive slots carry -1).  Weight absorption makes the
        # walk MQA-shaped — H query heads against ONE latent kv head of
        # width lora+rd — so bytes/step are the latent pages, not the
        # hypothetical expanded K/V.
        posb = jnp.broadcast_to(
            jnp.reshape(jnp.asarray(pos, jnp.int32), (-1,)), (B,))
        pos_r = jnp.reshape(posb, (-1, 1))                # (B, 1) for rope
        q_nope, q_rope = _mla_q(cfg, p, x, pos_r)
        k_rope = apply_rope(k_rope, pos_r, cfg.rope_theta, yarn)
        new_cache = _write_latent_paged(
            cache, ckv, k_rope[:, :, 0], pos_r, (posb >= 0).astype(jnp.int32))
        (cp, rp), layer = _stacks(new_cache, "ckv_pages", "krope_pages")
        pt = new_cache["page_table"]
        q_eff = jnp.einsum("bshe,lhe->bshl", q_nope, w_kc)  # (B,1,H,lora)
        q_lat = jnp.concatenate([q_eff[:, 0], q_rope[:, 0]], -1)
        if ctx.use_pallas:
            from repro.kernels.ops import mla_paged_decode_bhd
            ctx_lat = mla_paged_decode_bhd(q_lat, cp, rp, pt, posb, layer,
                                           scale=scale)
        else:
            from repro.kernels.paged_attention import mla_paged_decode_jnp
            ctx_lat = mla_paged_decode_jnp(q_lat, cp, rp, pt, posb, layer,
                                           scale=scale)
        out = jnp.einsum("bshl,lhe->bshe", ctx_lat[:, None].astype(x.dtype),
                         w_vc.astype(x.dtype))
    else:
        # ---- dense decode with weight absorption: score and read in
        # latent space against the lockstep dense latent cache
        assert pos.ndim == 0, \
            "per-sequence MLA decode positions need the paged latent " \
            "cache (cache_layout='paged'); the dense cache is lockstep-only"
        q_nope, q_rope = _mla_q(cfg, p, x, pos[None] if pos.ndim == 0 else pos)
        k_rope = apply_rope(k_rope, jnp.reshape(pos, (1,)), cfg.rope_theta,
                            yarn)
        c_new = jax.lax.dynamic_update_slice_in_dim(
            cache["ckv"], ckv.astype(cache["ckv"].dtype), pos, axis=1)
        r_new = jax.lax.dynamic_update_slice_in_dim(
            cache["krope"], k_rope[:, :, 0].astype(cache["krope"].dtype),
            pos, axis=1)
        cp = jax.lax.dynamic_update_slice_in_dim(
            cache["pos"], jnp.reshape(pos, (1,)).astype(jnp.int32), pos, axis=0)
        new_cache = {"ckv": c_new, "krope": r_new, "pos": cp}

        q_eff = jnp.einsum("bshe,lhe->bshl", q_nope, w_kc)  # absorb W_kc
        s = jnp.einsum("bshl,btl->bsht", q_eff.astype(jnp.float32),
                       c_new.astype(jnp.float32))
        s = s + jnp.einsum("bshr,btr->bsht", q_rope.astype(jnp.float32),
                           r_new.astype(jnp.float32))
        s = s * scale
        valid = (cp >= 0) & (cp <= pos)
        s = jnp.where(valid[None, None, None, :], s, NEG_INF)
        pr = jax.nn.softmax(s, axis=-1)
        ctx_lat = jnp.einsum("bsht,btl->bshl", pr, c_new.astype(jnp.float32))
        out = jnp.einsum("bshl,lhe->bshe", ctx_lat.astype(x.dtype),
                         w_vc.astype(x.dtype))

    out = ctx.constrain(out, ("batch", "seq", "heads", None))
    return jnp.einsum("bshe,hed->bsd", out, p["o"].astype(out.dtype)), new_cache
