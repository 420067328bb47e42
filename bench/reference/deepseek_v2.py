"""Plain float32 reference of DeepSeek-V2 as the benchmark cuts it: one
chip's share of an expert-parallel layer stack.

It imports nothing of the program.  It follows the published
architecture (arXiv:2405.04434; the ``modeling_deepseek.py`` of
``deepseek-ai/DeepSeek-V2``) and takes its numbers from the
configuration file:

* MLA in the published, non-absorbed form: q through ``q_a``, RMSNorm,
  ``q_b``; the latent ``kv_a`` split into the compressed KV (RMSNorm)
  and one rope key shared by all heads; K (nope part) and V expanded per
  head from the latent by ``kv_b``; the softmax over all keys a query
  can see, computed in query blocks so that an 8k sequence fits beside
  the weights on one chip;
* YaRN from the published equations: each inverse frequency blends
  theta^(-2i/d) with its interpolation by ``factor`` along the linear
  ramp between the correction dims of ``beta_fast`` and ``beta_slow``
  rotations over ``original_max_position_embeddings``, and the softmax
  scale is (nope+rope)^-1/2 times mscale(factor, mscale_all_dim)^2,
  mscale(s, m) = 0.1 m ln s + 1;
* group-limited greedy routing over the published expert count: the
  softmax of the float32 router logits, each of ``n_group`` groups
  scored by its best expert, the ``topk_group`` best groups kept, the
  top ``num_experts_per_tok`` experts inside them, their weights times
  ``routed_scaling_factor`` (renormalised first only where
  ``norm_topk_prob``);
* the experts held here (``n_routed_experts`` from ``first_held_expert``)
  add their gated SwiGLU outputs, swept one expert at a time over every
  token; the ``n_shared_experts`` shared experts (one SwiGLU of their
  summed width) are added once; the absent experts add nothing;
* the first ``first_k_dense_replace`` layer(s) dense, the untied head
  over the vocabulary slice.

Departures, each noted in the configuration file: RoPE rotates the two
halves of the rope columns, where DeepSeek-V2 rotates interleaved
(even, odd) pairs — one fixed permutation of the rope columns of ``q_b``
and ``kv_a``, which random weights absorb; the weights are bfloat16 as
stored and are upcast to float32 here, layer by layer.

Every matmul at ``Precision.HIGHEST`` under
``jax.default_matmul_precision("highest")``.  ``precision="fp8"`` is the
control: every matmul operand is rounded to float8 e4m3 with one scale
per tensor before the same float32 matmul — the step below the bfloat16
the configuration states.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0
Q_BLOCK = 128            # query rows per attention block


def _q8(x):
    """Round to float8 e4m3 at one scale per tensor."""
    s = (jnp.max(jnp.abs(x)) + 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _dot(spec, a, b, precision):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if precision == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _mscale(scale, m):
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def yarn_inv_freq(c) -> jnp.ndarray:
    """DeepSeek-V2's YaRN inverse frequencies over the rope dims."""
    d, base = c["qk_rope_head_dim"], c["rope_theta"]
    factor, orig = c["yarn_factor"], c["yarn_original"]
    extra = 1.0 / base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if factor <= 1:
        return extra

    def corr_dim(rot):
        return d * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(corr_dim(c["yarn_beta_fast"])), 0)
    high = min(math.ceil(corr_dim(c["yarn_beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return extra / factor * (1.0 - mask) + extra * mask


def softmax_scale(c) -> float:
    s = (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5
    m = _mscale(c["yarn_factor"], c["yarn_mscale_all_dim"])
    return s * m * m


def _rope(c, x, pos, inv):
    """Rotate-half RoPE over the last axis of (S, heads, d); YaRN scales
    cos and sin by mscale(factor, mscale) / mscale(factor, mscale_all_dim)."""
    d = x.shape[-1]
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    m = _mscale(c["yarn_factor"], c["yarn_mscale"]) \
        / _mscale(c["yarn_factor"], c["yarn_mscale_all_dim"])
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    cs, sn = jnp.cos(ang) * m, jnp.sin(ang) * m
    return jnp.concatenate([x1 * cs - x2 * sn, x2 * cs + x1 * sn], -1)


def _attention(c, lw, h, precision):
    """MLA over one sequence ``h`` (S, D), causal, in query blocks."""
    S = h.shape[0]
    eps = c["rms_norm_eps"]
    nope, rd = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    lora = c["kv_lora_rank"]
    inv = yarn_inv_freq(c)
    pos = jnp.arange(S)
    x = _rms(h, lw["attn_norm"], eps)
    kva = _dot("sd,dl->sl", x, lw["kv_a"], precision)
    ckv = _rms(kva[:, :lora], lw["kv_norm"], eps)
    k_pe = _rope(c, kva[:, None, lora:], pos, inv)[:, 0]         # (S, rd)
    kv_b = lw["kv_b"]
    k_nope = _dot("sl,lhk->shk", ckv, kv_b[..., :nope], precision)
    v = _dot("sl,lhk->shk", ckv, kv_b[..., nope:], precision)
    scale = softmax_scale(c)
    qb = min(Q_BLOCK, S)
    nq = -(-S // qb)
    xq = jnp.pad(x, [(0, nq * qb - S), (0, 0)]).reshape(nq, qb, -1)

    def block(args):
        i, xb = args
        qpos = i * qb + jnp.arange(qb)
        qa = _rms(_dot("sd,dl->sl", xb, lw["q_a"], precision),
                  lw["q_norm"], eps)
        q = _dot("sl,lhk->shk", qa, lw["q_b"], precision)
        q_pe = _rope(c, q[..., nope:], qpos, inv)
        s = _dot("qhd,khd->hqk", q[..., :nope], k_nope, precision) \
            + _dot("qhr,kr->hqk", q_pe, k_pe, precision)
        s = jnp.where(qpos[None, :, None] >= pos[None, None, :],
                      s * scale, -jnp.inf)
        return _dot("hqk,khd->qhd", jax.nn.softmax(s, -1), v, precision)

    o = jax.lax.map(block, (jnp.arange(nq), xq)).reshape(nq * qb, *v.shape[1:])
    return _dot("shd,hdm->sm", o[:S], lw["wo"], precision)


def _swiglu(x, g, u, d, precision):
    return _dot("sf,fd->sd", jax.nn.silu(_dot("sd,df->sf", x, g, precision))
                * _dot("sd,df->sf", x, u, precision), d, precision)


def route(c, probs):
    """Group-limited greedy: (weights, experts), each (S, k)."""
    n_group, k = c["n_group"], c["num_experts_per_tok"]
    S, E = probs.shape
    if n_group > 1:
        groups = probs.reshape(S, n_group, E // n_group)
        _, top_g = jax.lax.top_k(groups.max(-1), c["topk_group"])
        keep = jnp.zeros((S, n_group), bool).at[
            jnp.arange(S)[:, None], top_g].set(True)
        probs = jnp.where(keep[:, :, None], groups, 0.0).reshape(S, E)
    w, e = jax.lax.top_k(probs, k)
    if c["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * c["routed_scaling_factor"], e


def moe(c, lw, x, precision):
    """This chip's share of one MoE layer: the held experts' part of the
    routed result, plus the shared experts."""
    E = c["router_experts"]
    logits = _dot("sd,de->se", x, lw["router"], precision)
    w, e = route(c, jax.nn.softmax(logits, -1))
    gate = (jax.nn.one_hot(e, E) * w[..., None]).sum(-2)     # (S, E)
    e0, Eh = c["first_held_expert"], c["n_routed_experts"]
    gate = jax.lax.dynamic_slice_in_dim(gate, e0, Eh, axis=1)

    def expert(y, args):
        g, u, d, we = args
        return y + we[:, None] * _swiglu(x, g, u, d, precision), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (lw["e_gate"], lw["e_up"], lw["e_down"], gate.T))
    picks = ((e >= e0) & (e < e0 + Eh)).sum(-1)              # held, per token
    return y + _swiglu(x, lw["s_gate"], lw["s_up"], lw["s_down"],
                       precision), picks


def _layer(c, precision, dense, h, lw):
    """One layer; returns (h, the routed picks per token landing on the
    held experts, None for a dense layer)."""
    h = h + _attention(c, lw, h, precision)
    x = _rms(h, lw["ffn_norm"], c["rms_norm_eps"])
    if dense:
        return h + _swiglu(x, lw["w_gate"], lw["w_up"], lw["w_down"],
                           precision), None
    y, picks = moe(c, lw, x, precision)
    return h + y, picks


def _stack(w, tokens, c, precision):
    """(final hidden states (S, D), held picks per token (S,) summed over
    the MoE layers)."""
    h = w["embed"][tokens].astype(jnp.float32)
    h, _ = _layer(c, precision, True, h, w["dense"])
    h, picks = jax.lax.scan(functools.partial(_layer, c, precision, False),
                            h, w["moe"])
    return _rms(h, w["final_norm"], c["rms_norm_eps"]), picks.sum(0)


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _logits(w, tokens, score_pos, *, cfg_items, precision):
    hs = _stack(w, tokens, dict(cfg_items), precision)[0][score_pos]
    return _dot("nd,dv->nv", hs, w["lm_head"], precision)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _held_picks(w, tokens, *, cfg_items):
    return _stack(w, tokens, dict(cfg_items), "f32")[1]


CONFIG_KEYS = ("rms_norm_eps", "rope_theta", "qk_nope_head_dim",
               "qk_rope_head_dim", "kv_lora_rank", "num_experts_per_tok",
               "n_group", "topk_group", "norm_topk_prob",
               "routed_scaling_factor", "n_routed_experts")


def run_values(cfgj: dict) -> tuple:
    """The scalars the reference computes with, from a configuration
    file."""
    if cfgj.get("first_k_dense_replace", 1) != 1:
        raise ValueError("the reference holds one dense first layer")
    rs = cfgj.get("rope_scaling") or {}
    c = {k: cfgj[k] for k in CONFIG_KEYS}
    c.update(router_experts=cfgj.get("published", {}).get(
                 "n_routed_experts", cfgj["n_routed_experts"]),
             first_held_expert=cfgj.get("first_held_expert", 0),
             yarn_factor=float(rs.get("factor", 1.0)),
             yarn_original=int(rs.get("original_max_position_embeddings",
                                      0)),
             yarn_beta_fast=float(rs.get("beta_fast", 32)),
             yarn_beta_slow=float(rs.get("beta_slow", 1)),
             yarn_mscale=float(rs.get("mscale", 1.0)),
             yarn_mscale_all_dim=float(rs.get("mscale_all_dim", 0.0)))
    return tuple(sorted(c.items()))


def logits(w, cfgj: dict, tokens, score_pos, precision: str = "f32"):
    """Logits (len(score_pos), vocab) of one sequence ``tokens`` at the
    positions ``score_pos``, causal over the whole sequence."""
    with jax.default_matmul_precision("highest"):
        return _logits(w, jnp.asarray(tokens, jnp.int32),
                       jnp.asarray(score_pos, jnp.int32),
                       cfg_items=run_values(cfgj), precision=precision)


def held_picks(w, cfgj: dict, tokens):
    """Per token of one sequence, the routed picks that land on the held
    experts, summed over the MoE layers."""
    with jax.default_matmul_precision("highest"):
        return _held_picks(w, jnp.asarray(tokens, jnp.int32),
                           cfg_items=run_values(cfgj))
