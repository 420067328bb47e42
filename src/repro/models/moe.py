"""Mixture-of-Experts FFN: routing over all experts, the held experts'
part of the result, shared experts.

The router scores all E experts and picks k per token: the top-k of the
softmax, or DeepSeek-V2's ``group_limited_greedy`` (``n_group > 1``: the
E experts form ``n_group`` groups, each group scores as its best expert,
the top ``topk_group`` groups stay and the top-k are taken inside them).
The k weights are renormalised to sum 1 where ``norm_topk_prob`` (Granite)
and scaled by ``routed_scaling_factor`` (DeepSeek-V2: 16, unnormalised).

A layer holds ``cfg.experts_held`` experts from ``cfg.first_held_expert``
on (all E by default): its expert weights are ``(E_held, ...)`` and it
adds only the picks that land on them — one chip's share under expert
parallelism, the exchange left out.  Shared experts are added once.

Training dispatch is capacity-based (GShard): the one-hot-einsum
formulation SPMD-partitions cleanly, tokens are processed in groups so
the (S, E, C) dispatch tensor stays small, capacity per group
C = ceil(Sg * top_k / E * capacity_factor).  Its aux loss is the
load-balancing loss (Switch/GShard): E * Σ_e f_e · p_e.

Serving (``dropless=True``) bypasses the capacity queue entirely: a
served token's routing must depend on that token alone — capacity drops
would make a request's logits a function of its co-batched neighbors and
of ragged padding, breaking per-request determinism under continuous
batching.  It sweeps every held expert over every token, and returns in
place of the aux loss the counts ``(picks_here, expert_rows)``: the
valid tokens' picks that land on held experts, and the (token, held
expert) rows the sweep computed.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import Ctx, activation

MOE_GROUP_SIZE = 1024     # tokens per training dispatch group


def route(cfg: ModelConfig, probs: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(weights, experts), each (..., k), of the router's softmax ``probs``
    (..., E)."""
    k = cfg.num_experts_per_tok
    if cfg.n_group > 1:
        grouped = probs.reshape(*probs.shape[:-1], cfg.n_group, -1)
        _, top_g = jax.lax.top_k(grouped.max(-1), cfg.topk_group)
        keep = jax.nn.one_hot(top_g, cfg.n_group, dtype=bool).any(-2)
        probs = jnp.where(keep[..., None], grouped, 0.0).reshape(probs.shape)
    topk_p, topk_e = jax.lax.top_k(probs, k)
    if cfg.norm_topk_prob:
        topk_p = topk_p / jnp.maximum(topk_p.sum(-1, keepdims=True), 1e-20)
    return topk_p * cfg.routed_scaling_factor, topk_e


def moe_ffn(
    cfg: ModelConfig,
    p: Dict[str, jax.Array],
    x: jax.Array,                 # (B, S, D)
    ctx: Ctx,
    *,
    dropless: bool = False,
    valid: Optional[jax.Array] = None,   # (B|1, S|1) tokens that count
) -> Tuple[jax.Array, jax.Array]:
    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    e0, Eh = cfg.first_held_expert, cfg.experts_held
    dt = x.dtype

    with jax.named_scope("router"):
        # float32 at full precision, as DeepSeek-V2 publishes its gate: a
        # bfloat16 router flips near-tied picks
        logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                            p["router"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        topk_p, topk_e = route(cfg, probs)                 # (B,S,k)
        onehot = jax.nn.one_hot(topk_e, E, dtype=jnp.float32)  # (B,S,k,E)
        held = onehot[..., e0:e0 + Eh]                     # (B,S,k,Eh)

    if dropless:
        # serving path: capacity dropping is a training-throughput
        # artifact — a served token's output must depend on that token
        # alone, so route exactly what the router chose via a dense sweep
        # over the held experts (E_held x the routed FFN FLOPs)
        with jax.named_scope("held_experts"):
            w = (held * topk_p[..., None]).sum(-2)         # (B,S,Eh)
            h = activation(jnp.einsum("bsd,edf->bsef", x, p["we_g"]),
                           cfg.act) \
                * jnp.einsum("bsd,edf->bsef", x, p["we_u"])
            h = ctx.constrain(h, ("batch", None, "experts", "expert_ffn"))
            # the gate weights scale each expert's hidden row before one
            # contraction over (expert, ffn): no (B, S, Eh, D) tensor
            hw = (h.astype(jnp.float32) * w[..., None]).astype(dt)
            out = jnp.einsum("bsef,efd->bsd", hw, p["we_d"],
                             preferred_element_type=jnp.float32).astype(dt)
        mask = jnp.ones((B, S), jnp.float32) if valid is None else \
            jnp.broadcast_to(valid, (B, S)).astype(jnp.float32)
        aux = jnp.stack([(held.sum((-2, -1)) * mask).sum(),
                         jnp.float32(B * S * Eh)])
    else:
        # --- per-expert capacity dispatch (GShard) ------------------------
        # groups never span rows: capacity contention across sequences
        # would couple co-batched requests (a neighbor's routing could
        # drop YOUR tokens)
        T = B * S
        Sg = min(cfg.moe_group_size or MOE_GROUP_SIZE, S)
        assert S % Sg == 0, f"row length {S} not divisible by group size {Sg}"
        G = T // Sg
        C = max(1, int(Sg * k / E * cfg.capacity_factor))
        xt = x.reshape(G, Sg, D)
        oh = onehot.reshape(G, Sg, k, E)
        gp = topk_p.reshape(G, Sg, k)
        # position of each (token, choice) within its expert queue,
        # priority by token order then choice order (GShard convention)
        flat = oh.reshape(G, Sg * k, E)
        pos_in_e = (jnp.cumsum(flat, axis=1) - flat).reshape(G, Sg, k, E)
        pos = (pos_in_e * oh).sum(-1)                      # (G,Sg,k)
        keep = pos < C
        gates = gp * keep

        # dispatch/combine tensors over the held experts (G, Sg, Eh, C)
        ohh = oh[..., e0:e0 + Eh]
        pos_oh = jax.nn.one_hot(pos, C, dtype=jnp.float32) * keep[..., None]
        disp = jnp.einsum("gske,gskc->gsec", ohh, pos_oh)
        comb = jnp.einsum("gsk,gske,gskc->gsec", gates, ohh, pos_oh)

        with jax.named_scope("held_experts"):
            xd = jnp.einsum("gsd,gsec->gecd", xt, disp.astype(dt))
            xd = ctx.constrain(xd, ("batch", "experts", None, None))
            h = activation(jnp.einsum("gecd,edf->gecf", xd, p["we_g"]),
                           cfg.act) \
                * jnp.einsum("gecd,edf->gecf", xd, p["we_u"])
            h = ctx.constrain(h, ("batch", "experts", None, "expert_ffn"))
            ye = jnp.einsum("gecf,efd->gecd", h, p["we_d"])
            ye = ctx.constrain(ye, ("batch", "experts", None, None))
            out = jnp.einsum("gecd,gsec->gsd", ye,
                             comb.astype(dt)).reshape(B, S, D)

        # --- load-balancing aux loss over all E ---------------------------
        me = probs.mean(axis=(0, 1))                        # mean prob per e
        ce = onehot.sum(2).mean(axis=(0, 1)) / k            # frac tokens per e
        aux = E * jnp.sum(me * ce) * cfg.router_aux_loss_coef

    # --- shared experts (always-on dense path) ----------------------------
    if cfg.num_shared_experts:
        with jax.named_scope("shared_experts"):
            hs = activation(x @ p["ws_g"], cfg.act) * (x @ p["ws_u"])
            hs = ctx.constrain(hs, ("batch", "seq", "ffn"))
            out = out + hs @ p["ws_d"]
    return out, aux
