"""Plain float32 reference of the decoder-only transformers the benchmark
runs: GQA attention (optional per-head RMSNorm on q and k), RoPE, SwiGLU
feed-forward or a top-k routed mixture of experts, tied embeddings.

It imports nothing of the program.  It follows the published
architecture and takes its numbers from the configuration file, so a
departure of the program from a published scalar (granite's multipliers)
shows up as the file's run value, listed under ``reduced`` and
``assumed`` there.  One sequence at a time, layer by layer under
``lax.scan``; every matmul at ``Precision.HIGHEST``.

``precision="fp8"`` is the control: every matmul operand is rounded to
float8 e4m3 with one scale per tensor before the same float32 matmul —
the step below the bfloat16 the configurations state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _q8(x):
    """Round to float8 e4m3 at one scale per tensor."""
    s = (jnp.max(jnp.abs(x)) + 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _dot(spec, a, b, precision):
    if precision == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """Rotate-half RoPE over the last axis of (S, heads, hd)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _ffn(c, lw, x, precision):
    if "router" in lw:
        E, k = c["num_local_experts"], c["num_experts_per_tok"]
        probs = jax.nn.softmax(_dot("sd,de->se", x, lw["router"], precision))
        top_p, top_e = jax.lax.top_k(probs, k)
        top_p = top_p / top_p.sum(-1, keepdims=True)
        gate = (jax.nn.one_hot(top_e, E) * top_p[..., None]).sum(-2)
        h = jax.nn.silu(_dot("sd,edf->sef", x, lw["e_gate"], precision)) \
            * _dot("sd,edf->sef", x, lw["e_up"], precision)
        y = _dot("sef,efd->sed", h, lw["e_down"], precision)
        return jnp.einsum("sed,se->sd", y, gate, precision=HIGHEST)
    h = jax.nn.silu(_dot("sd,df->sf", x, lw["w_gate"], precision)) \
        * _dot("sd,df->sf", x, lw["w_up"], precision)
    return _dot("sf,fd->sd", h, lw["w_down"], precision)


def _layer(c, precision, h, lw):
    S = h.shape[0]
    eps = c["rms_norm_eps"]
    H, K = c["num_attention_heads"], c["num_key_value_heads"]
    pos = jnp.arange(S)
    x = _rms(h, lw["attn_norm"], eps)
    q = _dot("sd,dhk->shk", x, lw["wq"], precision)
    k = _dot("sd,dhk->shk", x, lw["wk"], precision)
    v = _dot("sd,dhk->shk", x, lw["wv"], precision)
    if "q_norm" in lw:
        q, k = _rms(q, lw["q_norm"], eps), _rms(k, lw["k_norm"], eps)
    q, k = _rope(q, pos, c["rope_theta"]), _rope(k, pos, c["rope_theta"])
    # query head h reads kv head h // (H // K)
    k, v = jnp.repeat(k, H // K, axis=1), jnp.repeat(v, H // K, axis=1)
    s = _dot("qhd,khd->hqk", q, k, precision) * c["attention_multiplier"]
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = _dot("hqk,khd->qhd", jax.nn.softmax(s, -1), v, precision)
    h = h + c["residual_multiplier"] * _dot("qhd,hdm->qm", o, lw["wo"],
                                            precision)
    x = _rms(h, lw["ffn_norm"], eps)
    return h + c["residual_multiplier"] * _ffn(c, lw, x, precision), None


def _hidden(w, tokens, c, precision):
    """The final-normed hidden states of one sequence, (S, D)."""
    h = w["embed"][tokens].astype(jnp.float32) * c["embedding_multiplier"]
    layer = functools.partial(_layer, c, precision)
    h, _ = jax.lax.scan(layer, h, w["layers"])
    return _rms(h, w["final_norm"], c["rms_norm_eps"])


def _unembed(w, hs, c, precision):
    table = w["lm_head"].T if "lm_head" in w else w["embed"]
    return _dot("nd,vd->nv", hs, table, precision) / c["logits_scaling"]


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _logits(w, tokens, score_pos, *, cfg_items, precision):
    c = dict(cfg_items)
    hs = _hidden(w, tokens, c, precision)[score_pos]
    return _unembed(w, hs, c, precision)


CONFIG_KEYS = ("num_attention_heads", "num_key_value_heads", "rms_norm_eps",
               "rope_theta", "attention_multiplier", "residual_multiplier",
               "embedding_multiplier", "logits_scaling", "num_local_experts",
               "num_experts_per_tok")


def run_values(cfgj: dict) -> tuple:
    """The scalars the reference computes with, from a configuration
    file (defaults are the published Qwen3/Llama convention)."""
    c = {"attention_multiplier": cfgj["head_dim"] ** -0.5,
         "residual_multiplier": 1.0, "embedding_multiplier": 1.0,
         "logits_scaling": 1.0, "num_local_experts": 0,
         "num_experts_per_tok": 0}
    c.update({k: cfgj[k] for k in CONFIG_KEYS if k in cfgj})
    return tuple(sorted(c.items()))


def logits(w, cfgj: dict, tokens, score_pos, precision: str = "f32"):
    """Logits (len(score_pos), vocab) of one sequence ``tokens`` at the
    positions ``score_pos``, causal over the whole sequence."""
    with jax.default_matmul_precision("highest"):
        return _logits(w, jnp.asarray(tokens, jnp.int32),
                       jnp.asarray(score_pos, jnp.int32),
                       cfg_items=run_values(cfgj), precision=precision)
