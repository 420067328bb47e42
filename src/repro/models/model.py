"""Model assembly: embedding → scanned block stacks → logits.

One substrate serves all 10 assigned architectures; the per-layer *block
pattern* (global/local attention, RG-LRU, RWKV) plus feature flags (MLA, MoE,
enc-dec, frontend stubs) come from :class:`ModelConfig`.

Layer stacks are grouped for ``jax.lax.scan`` (compile-time & HLO size):
``num_layers`` = prefix (unrolled, e.g. DeepSeek first-k-dense) + n_groups ×
pattern (scanned, stacked weights) + tail (unrolled remainder).  KV caches
carry a matching leading group dim: the page pools ride the scan's carry,
every other cache leaf its xs/ys.

Modes
-----
* ``train``   — tokens → logits for every position (loss in repro.train).
* ``prefill`` — tokens → last-position logits + a filled cache.
* ``decode``  — one token + cache + pos → next logits + updated cache.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (
    GLOBAL_ATTN,
    LOCAL_ATTN,
    RECURRENT,
    RWKV,
    ModelConfig,
)
from repro.models import params as P
from repro.models.attention import gqa_attention, mla_attention
from repro.models.layers import Ctx, dense_ffn, rms_norm
from repro.models.moe import moe_ffn
from repro.models.recurrent import rglru_block
from repro.models.rwkv import rwkv_channel_mix, rwkv_time_mix

Tree = Dict[str, Any]


# ---------------------------------------------------------------------------
# Single block
# ---------------------------------------------------------------------------
def apply_block(
    cfg: ModelConfig,
    kind: str,
    p: Tree,
    h: jax.Array,
    ctx: Ctx,
    *,
    mode: str,
    cache: Optional[Tree],
    pos: jax.Array,
    enc_out: Optional[jax.Array] = None,
    dense_only: bool = False,
    causal: bool = True,
    lengths: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Tree], jax.Array]:
    """Residual block: temporal mixer + (cross-attn) + channel mixer.

    Returns (h, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    new_cache: Tree = {} if cache is not None else None
    full_mode = mode != "decode"
    amode = "full" if full_mode else "decode"

    def _post(name, y):
        return rms_norm(y, p[name], cfg.norm_eps) if name in p else y

    # ---- temporal mixer ---------------------------------------------------
    # With sequence-parallel residuals, gather ONCE at the norm output (the
    # Megatron-SP transition point) instead of per consuming matmul.
    x = ctx.constrain(rms_norm(h, p["pre_norm"], cfg.norm_eps),
                      ("batch", "seq", "embed_act"))
    if kind in (GLOBAL_ATTN, LOCAL_ATTN):
        sub = cache.get("attn") if cache is not None else None
        with jax.named_scope("attention"):
            if cfg.use_mla:
                y, nc = mla_attention(cfg, p["attn"], x, ctx, mode=amode,
                                      cache=sub, pos=pos, lengths=lengths)
            else:
                y, nc = gqa_attention(cfg, p["attn"], x, ctx, kind=kind,
                                      mode=amode, cache=sub, pos=pos,
                                      causal=causal, lengths=lengths)
        if new_cache is not None:
            new_cache["attn"] = nc
    elif kind == RECURRENT:
        sub = cache.get("rec") if cache is not None else None
        y, nc = rglru_block(cfg, p["rec"], x, ctx, mode=amode, cache=sub,
                            lengths=lengths)
        if new_cache is not None:
            new_cache["rec"] = nc
    elif kind == RWKV:
        sub = cache.get("rwkv") if cache is not None else None
        y, nc = rwkv_time_mix(cfg, p["tm"], x, ctx, mode=amode, cache=sub,
                              lengths=lengths)
        if new_cache is not None:
            new_cache["rwkv"] = nc
    else:
        raise ValueError(kind)
    # Constrain the mixer output to the sharded-residual layout BEFORE the
    # add: the TP output all-reduce then lowers to the cheaper
    # reduce-scatter (Megatron-SP's AR = AG + RS split).
    y = ctx.constrain(y, ("batch", "resid_seq", "embed_act"))
    h = h + _post("post_norm", y)
    h = ctx.constrain(h, ("batch", "resid_seq", "embed_act"))

    # ---- cross attention (enc-dec decoder) --------------------------------
    # full mode needs enc_out; decode reads the cached encoder K/V instead
    if "cross" in p and (enc_out is not None or
                         (cache is not None and "cross" in cache)):
        x = ctx.constrain(rms_norm(h, p["cross_norm"], cfg.norm_eps),
                          ("batch", "seq", "embed_act"))
        sub = cache.get("cross") if cache is not None else None
        y, nc = gqa_attention(cfg, p["cross"], x, ctx, kind=GLOBAL_ATTN,
                              mode=amode, cache=sub, pos=pos,
                              cross_kv=enc_out, is_cross=True, causal=False)
        if new_cache is not None:
            new_cache["cross"] = nc
        y = ctx.constrain(y, ("batch", "resid_seq", "embed_act"))
        h = h + _post("post_cross_norm", y)

    # ---- channel mixer ----------------------------------------------------
    if kind == RWKV:
        x = ctx.constrain(rms_norm(h, p["cm_norm"], cfg.norm_eps),
                          ("batch", "seq", "embed_act"))
        sub = new_cache.get("rwkv") if new_cache is not None else None
        y, nc = rwkv_channel_mix(cfg, p["cm"], x, ctx, mode=amode, cache=sub,
                                 lengths=lengths)
        if new_cache is not None:
            new_cache["rwkv"] = nc
    else:
        x = ctx.constrain(rms_norm(h, p["ffn_norm"], cfg.norm_eps),
                          ("batch", "seq", "embed_act"))
        if "moe" in p and not dense_only:
            with jax.named_scope("moe"):
                y, aux = moe_ffn(cfg, p["moe"], x, ctx,
                                 dropless=mode != "train",
                                 valid=_counted(mode, pos, lengths,
                                                x.shape[1]))
        else:
            with jax.named_scope("ffn"):
                y = dense_ffn(p["ffn"], x, cfg.act, ctx)
    y = ctx.constrain(y, ("batch", "resid_seq", "embed_act"))
    h = h + _post("post_ffn_norm", y)
    h = ctx.constrain(h, ("batch", "resid_seq", "embed_act"))
    return h, new_cache, aux


def _counted(mode: str, pos: jax.Array, lengths: Optional[jax.Array],
             S: int) -> Optional[jax.Array]:
    """The tokens a serving step's expert counts take in, (B|1, S|1):
    decode rows with a position (inactive slots carry -1), prefill rows'
    first ``lengths`` tokens; None = all."""
    if mode == "decode":
        return jnp.reshape(pos >= 0, (-1, 1))
    if lengths is not None:
        return jnp.arange(S)[None, :] < lengths[:, None]
    return None


def _aux_init(cfg: ModelConfig, mode: str) -> jax.Array:
    """The aux accumulator: the load-balancing loss in training; a MoE
    stack's serving steps sum the layers' (picks_here, expert_rows)."""
    return jnp.zeros((2,) if cfg.is_moe and mode != "train" else (),
                     jnp.float32)


# ---------------------------------------------------------------------------
# Stacks
# ---------------------------------------------------------------------------
#: Cache leaves that are shared physical page pools (paged layout).
_POOL_LEAVES = ("k_pages", "v_pages", "ckv_pages", "krope_pages")


def _split_pools(tree: Optional[Tree]) -> Tuple[Tree, Optional[Tree]]:
    """``(pools, rest)`` of a cache tree: its page-pool leaves, and every
    other leaf (the ``layer`` a scanned layer's view carries is dropped),
    each in ``tree``'s nesting."""
    if tree is None:
        return {}, None
    pools: Tree = {}
    rest: Tree = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            pools[k], rest[k] = _split_pools(v)
        elif k in _POOL_LEAVES:
            pools[k] = v
        elif k != "layer":
            rest[k] = v
    return pools, rest


def _join_pools(rest: Tree, pools: Tree, layer=None) -> Tree:
    """Inverse of :func:`_split_pools`.  With ``layer``, a scanned layer's
    view: its own leaves, the group's whole pool stacks, and beside them
    the layer they address (``models.attention._stacks``)."""
    out = dict(rest)
    for k, v in pools.items():
        if isinstance(v, dict):
            out[k] = _join_pools(rest.get(k, {}), v, layer)
        else:
            out[k] = v
            if layer is not None:
                out["layer"] = layer
    return out


def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "full":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    raise ValueError(policy)


def run_stack(
    cfg: ModelConfig,
    stack: Tree,                    # {"prefix": .., "groups": .., "tail": ..}
    h: jax.Array,
    ctx: Ctx,
    *,
    mode: str,
    cache: Optional[Tree],
    pos: jax.Array,
    enc_out: Optional[jax.Array] = None,
    causal: bool = True,
    stack_name: str = "decoder",
    remat_policy: str = "none",
    lengths: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Tree], jax.Array]:
    pat = cfg.block_pattern
    aux_total = _aux_init(cfg, mode)
    new_cache: Tree = {} if cache is not None else None

    def run_layer(i_kind, p, h, c):
        return apply_block(cfg, i_kind, p, h, ctx, mode=mode, cache=c,
                           pos=pos, enc_out=enc_out, causal=causal,
                           dense_only=False, lengths=lengths)

    # ---- prefix (first-k-dense, unrolled) ---------------------------------
    if "prefix" in stack:
        sub_nc = {}
        for i in sorted(stack["prefix"], key=int):
            kind = cfg.layer_kinds()[int(i)]
            c = cache["prefix"][i] if cache is not None else None
            h, nc, aux = apply_block(cfg, kind, stack["prefix"][i], h, ctx,
                                     mode=mode, cache=c, pos=pos,
                                     enc_out=enc_out, causal=causal,
                                     dense_only=True, lengths=lengths)
            aux_total = aux_total + aux
            sub_nc[i] = nc
        if new_cache is not None:
            new_cache["prefix"] = sub_nc

    # ---- scanned groups ----------------------------------------------------
    if "groups" in stack:
        gcache = cache["groups"] if cache is not None else None
        # Optionally re-constrain the per-iteration weight slices to their
        # FSDP/TP shardings.  Hypothesis (perf log #A0): prevents XLA from
        # hoisting the data-axis all-gather out of the loop.  MEASURED:
        # no memory change on mistral-large train (58.6 -> 59.7 GB), i.e.
        # refuted — XLA already keeps the gather in-loop; the stacks were
        # CPU float-normalization artifacts.  Kept behind a flag, off by
        # default.
        group_axes = None
        if ctx.mesh is not None and ctx.constrain_scan_weights:
            ab_groups = P.abstract_params(cfg).get(stack_name, {}).get("groups")
            if ab_groups is not None:
                group_axes = P.tree_logical_axes(ab_groups, drop_leading=1)

        # the page pools ride the carry as whole (L, ...) stacks, each
        # layer writing its rows into its own layer in place; every other
        # cache leaf (page tables, rings, recurrent state) is xs/ys
        pools, gcache = _split_pools(gcache)

        def body(carry, xs):
            h, aux, pools = carry
            gp, gc, layer = xs
            if group_axes is not None:
                gp = jax.tree.map(lambda w, ax: ctx.constrain(w, ax),
                                  gp, group_axes)
            pools = dict(pools)
            nc_out = {} if gc is not None else None
            for j, kind in enumerate(pat):
                c = _join_pools(gc[str(j)], pools[str(j)], layer) \
                    if gc is not None else None
                h, nc, a = run_layer(kind, gp[str(j)], h, c)
                aux = aux + a
                if nc_out is not None:
                    pools[str(j)], nc_out[str(j)] = _split_pools(nc)
            return (h, aux, pools), nc_out

        body = _remat(body, remat_policy)
        n_groups = jax.tree.leaves(stack["groups"])[0].shape[0]
        (h, aux_total, pools), g_nc = jax.lax.scan(
            body, (h, aux_total, pools),
            (stack["groups"], gcache, jnp.arange(n_groups, dtype=jnp.int32)),
            unroll=True if ctx.scan_unroll else 1)
        if new_cache is not None:
            new_cache["groups"] = _join_pools(g_nc, pools)

    # ---- tail (unrolled remainder) -----------------------------------------
    if "tail" in stack:
        sub_nc = {}
        for i in sorted(stack["tail"], key=int):
            kind = pat[int(i)]
            c = cache["tail"][i] if cache is not None else None
            h, nc, aux = run_layer(kind, stack["tail"][i], h, c)
            aux_total = aux_total + aux
            sub_nc[i] = nc
        if new_cache is not None:
            new_cache["tail"] = sub_nc

    return h, new_cache, aux_total


# ---------------------------------------------------------------------------
# Whole model
# ---------------------------------------------------------------------------
def cast_params(params: Tree, dtype) -> Tree:
    """Mixed precision: matrices (ndim≥2) compute in ``dtype`` (bf16 on TPU);
    1-D leaves (norm gains, biases, Λ) stay fp32.  Master params remain fp32
    in the train state — this cast happens inside the jitted forward."""
    def c(p):
        if p.ndim >= 2 and p.dtype == jnp.float32:
            return p.astype(dtype)
        return p
    return jax.tree.map(c, params)


def _embed(cfg: ModelConfig, params: Tree, tokens: jax.Array, ctx: Ctx) -> jax.Array:
    h = jnp.take(params["embed"], tokens, axis=0).astype(ctx.dtype)
    if cfg.embed_scale_by_sqrt_dim:
        h = h * jnp.asarray(cfg.d_model ** 0.5, ctx.dtype)
    return ctx.constrain(h, ("batch", "seq", "embed_act"))


@jax.named_scope("logits")
def _unembed(cfg: ModelConfig, params: Tree, h: jax.Array, ctx: Ctx) -> jax.Array:
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    table = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (h @ table.astype(h.dtype)).astype(jnp.float32)
    logits = ctx.constrain(logits, ("batch", "seq", "vocab_act"))
    from repro.models.layers import softcap as _sc
    logits = _sc(logits, cfg.final_logit_softcap)
    # mask vocab-padding ids
    pad_mask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
    return jnp.where(pad_mask, logits, -1e9)


def _encoder_out(cfg: ModelConfig, params: Tree, src_embeds: jax.Array,
                 ctx: Ctx, remat_policy: str) -> jax.Array:
    """Encoder stack over precomputed (stub) frontend embeddings."""
    h = src_embeds.astype(ctx.dtype)
    pos = jnp.arange(h.shape[1], dtype=jnp.int32)
    h, _, _ = run_stack(cfg, params["encoder"], h, ctx, mode="train",
                        cache=None, pos=pos, causal=False,
                        stack_name="encoder", remat_policy=remat_policy)
    return rms_norm(h, params["encoder_norm"], cfg.norm_eps)


def forward(
    cfg: ModelConfig,
    params: Tree,
    batch: Tree,
    ctx: Ctx,
    *,
    mode: str = "train",             # train | prefill | decode
    cache: Optional[Tree] = None,
    pos: Optional[jax.Array] = None, # decode: scalar position
    remat_policy: str = "none",
    lengths: Optional[jax.Array] = None,  # ragged prefill: (B,) prompt lens
    starts: Optional[jax.Array] = None,   # chunked prefill: (B,) first positions
) -> Tuple[jax.Array, Optional[Tree], jax.Array]:
    """Returns (logits, new_cache, aux).

    train:   logits (B, S, V) for every position
    prefill: logits (B, 1, V) for the last position + filled cache
    decode:  logits (B, 1, V) + updated cache

    ``aux`` is the load-balancing loss in training; in prefill and decode
    a MoE stack gives ``(picks_here, expert_rows)`` summed over its MoE
    layers (``models.moe``), other stacks zero.

    ``lengths`` makes prefill *ragged*: the (B, S0) token batch is padded
    to the round's max prompt length, row ``b``'s true prompt is its first
    ``lengths[b]`` tokens, and the returned logits are each row's *last
    valid* position.  Causality already isolates that query from the
    padding keys (they sit at later positions), and cache writes are
    masked per row — length-0 rows (active continuous-batching slots not
    being prefilled this round) leave the cache untouched.  Supported for
    every decoder-only stack: paged globals + ring locals mask their
    writes, paged MLA latents scatter per row, and recurrent / RWKV
    carries are length-masked (padding steps neither read nor write
    state).  Enc-dec keeps the per-slot path (cross K/V is per round).

    ``starts`` makes a ragged prefill *chunked* (prefix caching): row
    ``b``'s tokens are the uncached TAIL of its prompt, opening at
    absolute position ``starts[b]`` — the cached prefix K/V already sit
    in (possibly shared) pages its table points to, so attention walks
    the whole page table while only the chunk is computed.  Needs an
    all-global paged decoder (ring locals would have to replay the
    evicted prefix) and no frontend (frontend embeds precede position 0).
    """
    params = cast_params(params, ctx.dtype)
    tokens = batch["tokens"]
    if lengths is not None:
        if mode != "prefill":
            raise ValueError("lengths is a prefill-only argument")
        if cfg.is_encoder_decoder:
            raise NotImplementedError(
                "ragged prefill needs a decoder-only stack: the cross-"
                "attention K/V of rows not in this round would be "
                "overwritten by the new encoder output")
        if cfg.use_mla and cfg.cache_layout != "paged":
            raise NotImplementedError(
                "ragged prefill over MLA needs the paged latent cache "
                "(the dense MLA cache keeps a lockstep shared position "
                "slot)")
        lengths = jnp.asarray(lengths, jnp.int32)
    if starts is not None:
        if lengths is None:
            raise ValueError("starts requires ragged prefill (lengths)")
        if set(cfg.layer_kinds()) != {GLOBAL_ATTN} \
                or cfg.is_encoder_decoder or cfg.frontend == "vision":
            raise NotImplementedError(
                "chunked prefix prefill needs an all-global paged decoder "
                "without a frontend")
        starts = jnp.asarray(starts, jnp.int32)
    enc_out = None
    # decode reuses the cross K/V cached at prefill — no encoder re-run
    if cfg.is_encoder_decoder and mode != "decode":
        enc_out = _encoder_out(cfg, params, batch["src_embeds"], ctx,
                               remat_policy)

    h = _embed(cfg, params, tokens, ctx)
    n_front = 0
    if cfg.frontend == "vision" and "frontend_embeds" in batch:
        fe = batch["frontend_embeds"].astype(ctx.dtype)
        n_front = fe.shape[1]
        h = jnp.concatenate([fe, h], axis=1)

    if mode == "decode":
        assert pos is not None and cache is not None
        p_arr = jnp.asarray(pos, jnp.int32)
    else:
        p_arr = jnp.arange(h.shape[1], dtype=jnp.int32)
        if starts is not None:
            # chunked prefill: per-row absolute positions (B, S0)
            p_arr = starts[:, None] + p_arr[None, :]
    if lengths is not None and n_front:
        # frontend tokens are real (per-row) prefix content: fold them into
        # the valid length; length-0 rows stay untouched
        lengths = jnp.where(lengths > 0, lengths + n_front, 0)

    h, new_cache, aux = run_stack(
        cfg, params["decoder"], h, ctx, mode=mode, cache=cache, pos=p_arr,
        enc_out=enc_out, causal=True, remat_policy=remat_policy,
        lengths=lengths)

    if mode == "train":
        if n_front:
            h = h[:, n_front:]
        logits = _unembed(cfg, params, h, ctx)
    elif lengths is not None:
        # ragged prefill: each row's last *valid* position (length-0 rows
        # return garbage logits the caller ignores)
        idx = jnp.maximum(lengths, 1) - 1                      # (B,)
        hl = jnp.take_along_axis(h, idx[:, None, None], axis=1)
        logits = _unembed(cfg, params, hl, ctx)
    else:
        logits = _unembed(cfg, params, h[:, -1:], ctx)
    return logits, new_cache, aux


# ---------------------------------------------------------------------------
# Cache construction
# ---------------------------------------------------------------------------
def num_pages(seq_len: int, page_size: int) -> int:
    """Logical pages needed to hold ``seq_len`` tokens."""
    return -(-seq_len // page_size)


def _layer_cache_ab(cfg: ModelConfig, kind: str, B: int, S_max: int,
                    src_len: int, cross: bool, layout: str = "dense",
                    page_budget: Optional[int] = None) -> Tree:
    """Abstract cache (ParamAb reused as shape+axes carrier) for one layer.

    ``layout="paged"`` replaces the dense (B, K, S_max, hd) buffer of
    *global* attention layers with a shared physical page pool, token-major
    ``(P, page_size, K·hd)``, plus a per-sequence page table (vLLM-style).
    ``page_budget`` is the pool size in pages (default: worst case,
    B × ceil(S_max/page_size)).  Masked decode writes (inactive slots)
    scatter out of bounds and are dropped, so the pool carries no scratch
    page — its size stays divisible by the mesh axes and shards cleanly
    over ``cache_pages``.  MLA global layers
    page their *latent* cache (compressed latents + rope keys) the same
    way; only ring-buffer (local) caches stay dense — already bounded.
    """
    K, hd = cfg.num_kv_heads, cfg.head_dim
    dt = cfg.dtype
    c: Tree = {}
    if kind in (GLOBAL_ATTN, LOCAL_ATTN):
        if cfg.use_mla and kind == GLOBAL_ATTN and layout == "paged":
            # paged MLA latent cache: pages hold compressed latents + rope
            # keys (one shared "kv head" in latent space), walked through
            # the same per-sequence page tables as the GQA pool.  A latent
            # token is (kv_lora_rank + qk_rope_head_dim) wide — ~an order
            # smaller than the expanded K/V it stands for.
            ps = cfg.page_size
            pps = num_pages(S_max, ps)
            pool = page_budget if page_budget is not None else B * pps
            c["attn"] = {
                "ckv_pages": P.ParamAb((pool, ps, cfg.kv_lora_rank),
                                       ("cache_pages", None, "lora"),
                                       "zeros", dt),
                "krope_pages": P.ParamAb((pool, ps, cfg.qk_rope_head_dim),
                                         ("cache_pages", None, None),
                                         "zeros", dt),
                "page_table": P.ParamAb((B, pps), ("cache_batch", None),
                                        "zeros", "int32"),
            }
        elif cfg.use_mla:
            c["attn"] = {
                "ckv": P.ParamAb((B, S_max, cfg.kv_lora_rank),
                                 ("cache_batch", "kv_seq", "lora"), "zeros", dt),
                "krope": P.ParamAb((B, S_max, cfg.qk_rope_head_dim),
                                   ("cache_batch", "kv_seq", None), "zeros", dt),
                "pos": P.ParamAb((S_max,), (None,), "zeros", "int32"),
            }
        elif kind == GLOBAL_ATTN and layout == "paged":
            ps = cfg.page_size
            pps = num_pages(S_max, ps)
            pool = page_budget if page_budget is not None else B * pps
            # token-major: a token's K (or V) for every kv head is one
            # lane-dense row, written in place by one row scatter; the
            # row shards only by whole kv heads
            c["attn"] = {
                "k_pages": P.ParamAb((pool, ps, K * hd),
                                     ("cache_pages", None, ("kv_heads", hd)),
                                     "zeros", dt),
                "v_pages": P.ParamAb((pool, ps, K * hd),
                                     ("cache_pages", None, ("kv_heads", hd)),
                                     "zeros", dt),
                "page_table": P.ParamAb((B, pps), ("cache_batch", None),
                                        "zeros", "int32"),
            }
        elif kind == GLOBAL_ATTN:
            c["attn"] = {
                "k": P.ParamAb((B, K, S_max, hd),
                               ("cache_batch", "kv_heads", "kv_seq", "head_dim"),
                               "zeros", dt),
                "v": P.ParamAb((B, K, S_max, hd),
                               ("cache_batch", "kv_heads", "kv_seq", "head_dim"),
                               "zeros", dt),
                "pos": P.ParamAb((S_max,), (None,), "zeros", "int32"),
            }
        else:                            # local: per-sequence ring buffer
            W = min(cfg.window_size, S_max)
            c["attn"] = {
                "k": P.ParamAb((B, K, W, hd),
                               ("cache_batch", "kv_heads", "window_seq",
                                "head_dim"), "zeros", dt),
                "v": P.ParamAb((B, K, W, hd),
                               ("cache_batch", "kv_heads", "window_seq",
                                "head_dim"), "zeros", dt),
                "pos": P.ParamAb((B, W), ("cache_batch", "window_seq"),
                                 "zeros", "int32"),
            }
    elif kind == RECURRENT:
        R, CW = cfg.rnn_width, cfg.conv1d_width
        c["rec"] = {
            "h": P.ParamAb((B, R), ("cache_batch", "rnn"), "zeros", "float32"),
            "conv": P.ParamAb((B, CW - 1, R), ("cache_batch", None, "rnn"),
                              "zeros", dt),
        }
    elif kind == RWKV:
        N = cfg.rwkv_head_dim
        H = cfg.d_model // N
        c["rwkv"] = {
            "s": P.ParamAb((B, H, N, N), ("cache_batch", "heads", None, None),
                           "zeros", "float32"),
            "shift_tm": P.ParamAb((B, cfg.d_model), ("cache_batch", None),
                                  "zeros", dt),
            "shift_cm": P.ParamAb((B, cfg.d_model), ("cache_batch", None),
                                  "zeros", dt),
        }
    if cross:
        c["cross"] = {
            "k": P.ParamAb((B, K, src_len, hd),
                           ("cache_batch", "kv_heads", "kv_seq", "head_dim"),
                           "zeros", dt),
            "v": P.ParamAb((B, K, src_len, hd),
                           ("cache_batch", "kv_heads", "kv_seq", "head_dim"),
                           "zeros", dt),
        }
    return c


def abstract_cache(cfg: ModelConfig, batch_size: int, max_len: int,
                   src_len: int = 0, *, layout: Optional[str] = None,
                   page_budget: Optional[int] = None) -> Tree:
    """Abstract decode/prefill cache matching the decoder stack layout.
    ``layout`` defaults to ``cfg.cache_layout``; ``page_budget`` sizes the
    per-layer page pool (paged layout only; None = worst case)."""
    layout = cfg.cache_layout if layout is None else layout
    if layout not in ("dense", "paged"):
        raise ValueError(f"unknown cache_layout {layout!r}")
    kinds = cfg.layer_kinds()
    pat = cfg.block_pattern
    cross = cfg.is_encoder_decoder
    prefix_n = cfg.first_k_dense
    body = kinds[prefix_n:]
    n_groups, tail_n = divmod(len(body), len(pat))
    mk = lambda kind: _layer_cache_ab(cfg, kind, batch_size, max_len,
                                      src_len, cross, layout, page_budget)
    out: Tree = {}
    if prefix_n:
        out["prefix"] = {str(i): mk(kinds[i]) for i in range(prefix_n)}
    if n_groups:
        group = {str(j): mk(pat[j]) for j in range(len(pat))}
        out["groups"] = P._stack(group, n_groups)
    if tail_n:
        out["tail"] = {str(j): mk(pat[j]) for j in range(tail_n)}
    return out


def init_cache(cfg: ModelConfig, batch_size: int, max_len: int,
               src_len: int = 0, *, layout: Optional[str] = None,
               page_budget: Optional[int] = None,
               paged_tables: str = "identity") -> Tree:
    """Concrete cache.  For the paged layout, ``paged_tables`` selects the
    page-table init: ``"identity"`` (default; sequence ``b`` owns pages
    ``b*pps .. (b+1)*pps-1`` — lockstep serving with a worst-case pool) or
    ``"empty"`` (all -1; a host-side allocator assigns pages at admission —
    see launch.executor).  Identity requires the worst-case pool, so it is
    rejected when a smaller ``page_budget`` is given."""
    ab = abstract_cache(cfg, batch_size, max_len, src_len,
                        layout=layout, page_budget=page_budget)
    if paged_tables == "identity" and page_budget is not None and \
            page_budget < batch_size * num_pages(max_len, cfg.page_size):
        raise ValueError(
            "identity page tables need the worst-case pool; pass "
            "paged_tables='empty' with a reduced page_budget")

    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        ab, is_leaf=lambda x: isinstance(x, P.ParamAb))

    def mk(path, leaf: P.ParamAb):
        key = getattr(path[-1], "key", None)
        if key == "page_table":
            if paged_tables == "identity":
                pps = leaf.shape[-1]
                ident = jnp.arange(batch_size * pps,
                                   dtype=jnp.int32).reshape(batch_size, pps)
                return jnp.broadcast_to(ident, leaf.shape)
            return jnp.full(leaf.shape, -1, jnp.int32)
        if leaf.dtype == "int32":       # position slots start invalid
            return jnp.full(leaf.shape, -1, jnp.int32)
        return jnp.zeros(leaf.shape, jnp.dtype(leaf.dtype))

    return jax.tree.unflatten(treedef, [mk(p, l) for p, l in leaves])


# ---------------------------------------------------------------------------
# Continuous-batching helpers (host-side; see launch/executor.py).
#
# A "slot view" is the cache restricted to one batch row: per-sequence
# leaves (page tables, ring buffers, recurrent state, …) are sliced to
# batch 1, while the *shared* page pools pass through whole — a prefill
# run on the view writes only the pages that row's table points to.
# ---------------------------------------------------------------------------
def _slot_axis(path) -> int:
    """Batch axis of a cache leaf: scanned group leaves carry a leading
    ``layers`` dim, so their batch dim is 1."""
    return 1 if any(getattr(p, "key", None) == "groups" for p in path) else 0


def _is_pool(path) -> bool:
    return getattr(path[-1], "key", None) in _POOL_LEAVES


def _is_shared_pos(path, leaf, batch_size: int, axis: int) -> bool:
    """Lockstep-only shared slot maps ((S,) pos of dense-global / MLA
    caches) have no batch dim and are left whole in a slot view."""
    return getattr(path[-1], "key", None) == "pos" and \
        (leaf.ndim <= axis or leaf.shape[axis] != batch_size)


def cache_slot_view(cache: Tree, batch_size: int, b: int) -> Tree:
    """Batch-1 view of ``cache`` for slot ``b`` (page pools shared).  The
    slot index is an operand of the slice, so views of every slot share
    one compiled slice per leaf shape."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(cache)
    out = []
    for path, leaf in leaves:
        ax = _slot_axis(path)
        if _is_pool(path) or _is_shared_pos(path, leaf, batch_size, ax):
            out.append(leaf)
        else:
            out.append(jax.lax.dynamic_slice_in_dim(leaf, b, 1, axis=ax))
    return jax.tree.unflatten(treedef, out)


def cache_slot_merge(cache: Tree, view: Tree, batch_size: int, b: int) -> Tree:
    """Write a slot view (as returned by prefill) back into the full cache:
    pool leaves replace wholesale, per-sequence leaves update row ``b``.
    Page tables are the host's and no step writes them, so the cache's
    own are kept."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(cache)
    vleaves = jax.tree.leaves(view)
    assert len(leaves) == len(vleaves), (len(leaves), len(vleaves))
    out = []
    for (path, leaf), vleaf in zip(leaves, vleaves):
        ax = _slot_axis(path)
        if getattr(path[-1], "key", None) == "page_table":
            out.append(leaf)
        elif _is_pool(path) or _is_shared_pos(path, leaf, batch_size, ax):
            out.append(vleaf)
        else:
            out.append(jax.lax.dynamic_update_slice_in_dim(
                leaf, vleaf.astype(leaf.dtype), b, axis=ax))
    return jax.tree.unflatten(treedef, out)


def count_params(cfg: ModelConfig, active_only: bool = False) -> int:
    return P.count_params(cfg, active_only=active_only)
