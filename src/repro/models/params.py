"""Abstract parameter trees: one source of truth for shape/axes/init.

``abstract_params(cfg)`` returns a nested dict whose leaves are
:class:`ParamAb` — (shape, dtype, logical_axes, init spec).  Everything else
derives from it:

* ``init_params``        — concrete tree (PRNG init, per-leaf fold_in)
* ``tree_shardings``     — NamedSharding tree (via repro.dist)
* ``shape_dtype_tree``   — ShapeDtypeStruct tree for the dry-run
* ``count_params``       — analytic parameter count (6ND roofline term)

Layer stacks that repeat (the scan groups) carry a leading ``layers`` dim.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.sharding import AxisName

from repro.configs.base import (
    GLOBAL_ATTN,
    LOCAL_ATTN,
    RECURRENT,
    RWKV,
    ModelConfig,
)

Tree = Dict[str, object]


@dataclass(frozen=True)
class ParamAb:
    """Abstract parameter: shape + logical axes + init recipe."""

    shape: Tuple[int, ...]
    logical_axes: Tuple[AxisName, ...]
    init: str = "fan_in"          # fan_in | zeros | ones | normal:<s> | rglru_lambda | uniform:<lo>:<hi>
    dtype: str = "float32"

    def __post_init__(self):
        assert len(self.shape) == len(self.logical_axes), (self.shape, self.logical_axes)

    @property
    def size(self) -> int:
        return int(np.prod(self.shape))

    def shape_dtype(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(self.shape, jnp.dtype(self.dtype))

    def materialize(self, key: jax.Array) -> jax.Array:
        dt = jnp.dtype(self.dtype)
        if self.init == "zeros":
            return jnp.zeros(self.shape, dt)
        if self.init == "ones":
            return jnp.ones(self.shape, dt)
        if self.init == "fan_in":
            fan_in = self.shape[0] if len(self.shape) == 1 else int(np.prod(self.shape[:-1]))
            s = 1.0 / math.sqrt(max(fan_in, 1))
            return (jax.random.normal(key, self.shape, jnp.float32) * s).astype(dt)
        if self.init.startswith("normal:"):
            s = float(self.init.split(":")[1])
            return (jax.random.normal(key, self.shape, jnp.float32) * s).astype(dt)
        if self.init == "rglru_lambda":
            # Λ such that a = sigmoid(Λ) ∈ [0.9, 0.999]  (Griffin §2.4)
            u = jax.random.uniform(key, self.shape, jnp.float32, 0.9, 0.999)
            return jnp.log(u / (1.0 - u)).astype(dt)
        if self.init.startswith("uniform:"):
            _, lo, hi = self.init.split(":")
            return jax.random.uniform(key, self.shape, jnp.float32, float(lo), float(hi)).astype(dt)
        raise ValueError(f"unknown init {self.init!r}")


def _norm(d: int) -> ParamAb:
    return ParamAb((d,), ("embed",), "ones")


# ---------------------------------------------------------------------------
# Per-block param builders.  Dict keys are stable — the forward pass and the
# tests index them by name.
# ---------------------------------------------------------------------------
def _attention_params(cfg: ModelConfig) -> Tree:
    """Projections kept 3-D (D, heads, head_dim) so the kv_heads dim
    replicates cleanly (auto-drop) when it doesn't divide the model axis,
    instead of silently splitting head_dim."""
    D, H, K, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p: Tree = {
        "q": ParamAb((D, H, hd), ("embed", "heads", "head_dim")),
        "k": ParamAb((D, K, hd), ("embed", "kv_heads", "head_dim")),
        "v": ParamAb((D, K, hd), ("embed", "kv_heads", "head_dim")),
        "o": ParamAb((H, hd, D), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        p["qb"] = ParamAb((H, hd), ("heads", "head_dim"), "zeros")
        p["kb"] = ParamAb((K, hd), ("kv_heads", "head_dim"), "zeros")
        p["vb"] = ParamAb((K, hd), ("kv_heads", "head_dim"), "zeros")
    if cfg.qk_norm:
        p["q_norm"] = ParamAb((hd,), ("head_dim",), "ones")
        p["k_norm"] = ParamAb((hd,), ("head_dim",), "ones")
    return p


def _mla_params(cfg: ModelConfig) -> Tree:
    """DeepSeek-V2 multi-head latent attention."""
    D, H = cfg.d_model, cfg.num_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    p: Tree = {
        "kv_a": ParamAb((D, cfg.kv_lora_rank + cfg.qk_rope_head_dim), ("embed", "lora")),
        "kv_norm": ParamAb((cfg.kv_lora_rank,), ("lora",), "ones"),
        "kv_b": ParamAb(
            (cfg.kv_lora_rank, H, cfg.qk_nope_head_dim + cfg.v_head_dim),
            ("lora", "heads", "head_dim"),
        ),
        "o": ParamAb((H, cfg.v_head_dim, D), ("heads", "head_dim", "embed")),
    }
    if cfg.q_lora_rank:
        p["q_a"] = ParamAb((D, cfg.q_lora_rank), ("embed", "lora"))
        p["q_norm"] = ParamAb((cfg.q_lora_rank,), ("lora",), "ones")
        p["q_b"] = ParamAb((cfg.q_lora_rank, H, qk), ("lora", "heads", "head_dim"))
    else:
        p["q"] = ParamAb((D, H, qk), ("embed", "heads", "head_dim"))
    return p


def _dense_ffn_params(cfg: ModelConfig, d_ff: Optional[int] = None) -> Tree:
    D, F = cfg.d_model, d_ff or cfg.d_ff
    return {
        "wg": ParamAb((D, F), ("embed", "ffn")),
        "wu": ParamAb((D, F), ("embed", "ffn")),
        "wd": ParamAb((F, D), ("ffn", "embed")),
    }


def _moe_ffn_params(cfg: ModelConfig) -> Tree:
    """The router scores all E experts; the layer holds the weights of
    ``cfg.experts_held`` of them."""
    D, E, Eh, Fe = cfg.d_model, cfg.num_experts, cfg.experts_held, cfg.moe_d_ff
    p: Tree = {
        "router": ParamAb((D, E), ("embed", "experts"), "normal:0.02"),
        "we_g": ParamAb((Eh, D, Fe), ("experts", "embed", "expert_ffn")),
        "we_u": ParamAb((Eh, D, Fe), ("experts", "embed", "expert_ffn")),
        "we_d": ParamAb((Eh, Fe, D), ("experts", "expert_ffn", "embed")),
    }
    if cfg.num_shared_experts:
        Fs = Fe * cfg.num_shared_experts
        p["ws_g"] = ParamAb((D, Fs), ("embed", "ffn"))
        p["ws_u"] = ParamAb((D, Fs), ("embed", "ffn"))
        p["ws_d"] = ParamAb((Fs, D), ("ffn", "embed"))
    return p


def _rglru_params(cfg: ModelConfig) -> Tree:
    """Griffin/RecurrentGemma recurrent block (linear y-gate ⊙ RG-LRU(x))."""
    D, R, CW = cfg.d_model, cfg.rnn_width, cfg.conv1d_width
    return {
        "wx": ParamAb((D, R), ("embed", "rnn")),
        "wy": ParamAb((D, R), ("embed", "rnn")),
        "conv_w": ParamAb((CW, R), ("conv", "rnn"), "normal:0.02"),
        "conv_b": ParamAb((R,), ("rnn",), "zeros"),
        "gate_i": ParamAb((R, R), (None, "rnn")),   # input gate  σ(x W)
        "gate_r": ParamAb((R, R), (None, "rnn")),   # recurrence gate
        "rglru_lambda": ParamAb((R,), ("rnn",), "rglru_lambda"),
        "wo": ParamAb((R, D), ("rnn", "embed")),
    }


def _rwkv_time_mix_params(cfg: ModelConfig) -> Tree:
    """RWKV6 ("Finch") time-mix with ddlerp token shift + data-dep decay."""
    D = cfg.d_model
    H = D // cfg.rwkv_head_dim
    hd = cfg.rwkv_head_dim
    rk, rw = cfg.rwkv_ddlerp_rank, cfg.rwkv_decay_rank
    return {
        # token-shift: 5 lerp targets (r,k,v,w,g) + 1 for the ddlerp input x
        "tm_mu": ParamAb((6, D), (None, "embed"), "uniform:0:1"),
        "tm_A": ParamAb((D, 5 * rk), ("embed", "lora"), "normal:0.02"),
        "tm_B": ParamAb((5, rk, D), (None, "lora", "embed"), "normal:0.02"),
        "wr": ParamAb((D, D), ("embed", "heads")),
        "wk": ParamAb((D, D), ("embed", "heads")),
        "wv": ParamAb((D, D), ("embed", "heads")),
        "wg": ParamAb((D, D), ("embed", "heads")),
        "w_base": ParamAb((D,), ("heads",), "uniform:-7:-5"),  # decay bias (pre-softplus-ish)
        "ww_A": ParamAb((D, rw), ("embed", "lora"), "normal:0.02"),
        "ww_B": ParamAb((rw, D), ("lora", "heads"), "normal:0.02"),
        "u": ParamAb((H, hd), ("heads", "head_dim"), "normal:0.02"),  # bonus
        "ln_x": ParamAb((D,), ("heads",), "ones"),                    # per-head groupnorm scale
        "wo": ParamAb((D, D), ("heads", "embed")),
    }


def _rwkv_channel_mix_params(cfg: ModelConfig) -> Tree:
    D, F = cfg.d_model, cfg.d_ff
    return {
        "cm_mu_k": ParamAb((D,), ("embed",), "uniform:0:1"),
        "cm_mu_r": ParamAb((D,), ("embed",), "uniform:0:1"),
        "wk_c": ParamAb((D, F), ("embed", "ffn")),
        "wv_c": ParamAb((F, D), ("ffn", "embed")),
        "wr_c": ParamAb((D, D), ("embed", None)),
    }


def _layer_params(cfg: ModelConfig, kind: str, *, dense_ffn: bool = False,
                  cross_attn: bool = False, causal_attn: bool = True) -> Tree:
    """One full block = temporal mixer + channel mixer (+norms)."""
    D = cfg.d_model
    p: Tree = {"pre_norm": _norm(D)}
    if kind in (GLOBAL_ATTN, LOCAL_ATTN):
        p["attn"] = _mla_params(cfg) if cfg.use_mla else _attention_params(cfg)
    elif kind == RECURRENT:
        p["rec"] = _rglru_params(cfg)
    elif kind == RWKV:
        p["tm"] = _rwkv_time_mix_params(cfg)
    else:
        raise ValueError(kind)
    if cfg.use_post_block_norm:
        p["post_norm"] = _norm(D)
    if cross_attn:
        p["cross_norm"] = _norm(D)
        p["cross"] = _attention_params(cfg)
        if cfg.use_post_block_norm:
            p["post_cross_norm"] = _norm(D)
    # channel mixer
    if kind == RWKV:
        p["cm_norm"] = _norm(D)
        p["cm"] = _rwkv_channel_mix_params(cfg)
    else:
        p["ffn_norm"] = _norm(D)
        if cfg.is_moe and not dense_ffn:
            p["moe"] = _moe_ffn_params(cfg)
        else:
            p["ffn"] = _dense_ffn_params(cfg)
    if cfg.use_post_block_norm:
        p["post_ffn_norm"] = _norm(D)
    return p


def _stack(tree: Tree, n: int) -> Tree:
    """Prepend a scan ``layers`` dim of length ``n`` to every leaf."""
    return jax.tree.map(
        lambda ab: ParamAb((n,) + ab.shape, ("layers",) + ab.logical_axes,
                           ab.init, ab.dtype),
        tree,
        is_leaf=lambda x: isinstance(x, ParamAb),
    )


def _stack_of_layers(cfg: ModelConfig, *, cross_attn: bool = False,
                     num_layers: Optional[int] = None) -> Tree:
    """groups (scanned, stacked) + prefix (first-k-dense) + tail (remainder)."""
    kinds = cfg.layer_kinds(num_layers)
    prefix_n = cfg.first_k_dense if num_layers is None else 0
    pat = cfg.block_pattern
    body = kinds[prefix_n:]
    n_groups, tail_n = divmod(len(body), len(pat))
    out: Tree = {}
    if prefix_n:
        out["prefix"] = {
            str(i): _layer_params(cfg, kinds[i], dense_ffn=True, cross_attn=cross_attn)
            for i in range(prefix_n)
        }
    if n_groups:
        group = {str(i): _layer_params(cfg, pat[i], cross_attn=cross_attn)
                 for i in range(len(pat))}
        out["groups"] = _stack(group, n_groups)
    if tail_n:
        out["tail"] = {str(i): _layer_params(cfg, pat[i], cross_attn=cross_attn)
                       for i in range(tail_n)}
    return out


# ---------------------------------------------------------------------------
# Whole-model abstract tree
# ---------------------------------------------------------------------------
def abstract_params(cfg: ModelConfig) -> Tree:
    D, V = cfg.d_model, cfg.padded_vocab
    p: Tree = {
        "embed": ParamAb((V, D), ("vocab", "embed"), "normal:0.02"),
        "decoder": _stack_of_layers(cfg, cross_attn=cfg.is_encoder_decoder),
        "final_norm": _norm(D),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = ParamAb((D, V), ("embed", "vocab"))
    if cfg.is_encoder_decoder:
        p["encoder"] = _stack_of_layers(cfg, num_layers=cfg.num_encoder_layers)
        p["encoder_norm"] = _norm(D)
    return p


def shape_dtype_tree(tree: Tree):
    return jax.tree.map(lambda ab: ab.shape_dtype(), tree,
                        is_leaf=lambda x: isinstance(x, ParamAb))


def tree_logical_axes(tree: Tree, drop_leading: int = 0) -> Tree:
    """Per-leaf logical-axis tuples (``drop_leading=1`` strips the scan
    ``layers`` dim — what the in-loop weight slices actually carry)."""
    return jax.tree.map(lambda ab: ab.logical_axes[drop_leading:], tree,
                        is_leaf=lambda x: isinstance(x, ParamAb))


def param_shardings(cfg: ModelConfig, mesh, rules=None) -> Tree:
    """NamedSharding tree for the whole model, inferred from the abstract
    tree by repro.dist (no arrays allocated)."""
    from repro.dist.sharding import DEFAULT_RULES, tree_shardings
    return tree_shardings(abstract_params(cfg), mesh,
                          DEFAULT_RULES if rules is None else rules)


# one compiled program per config: run eagerly, every distinct leaf shape
# compiles its own random and scaling ops, which costs a TPU minutes
@functools.partial(jax.jit, static_argnums=0)
def init_params(cfg: ModelConfig, key: jax.Array) -> Tree:
    """Concrete init.  Each leaf gets a key folded from its tree path, so
    adding/removing an unrelated leaf never reshuffles other leaves."""
    ab = abstract_params(cfg)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        ab, is_leaf=lambda x: isinstance(x, ParamAb))

    def leaf_key(path) -> jax.Array:
        k = key
        for p in path:
            name = getattr(p, "key", getattr(p, "idx", None))
            k = jax.random.fold_in(k, _stable_hash(str(name)))
        return k

    vals = [leaf.materialize(leaf_key(path)) for path, leaf in leaves]
    return jax.tree.unflatten(treedef, vals)


def _stable_hash(s: str) -> int:
    h = 2166136261
    for c in s.encode():
        h = ((h ^ c) * 16777619) & 0x7FFFFFFF
    return h


# ---------------------------------------------------------------------------
# Counting
# ---------------------------------------------------------------------------
_EXPERT_KEYS = ("we_g", "we_u", "we_d")


def count_params(cfg: ModelConfig, active_only: bool = False,
                 include_embed: bool = False) -> int:
    """Analytic parameter count from the abstract tree.

    ``active_only`` scales routed-expert weights by top_k/E (MoE 6·N_active·D).
    ``include_embed=False`` excludes embedding + lm_head (standard 6ND
    convention counts matmul-participating non-embedding params)."""
    ab = abstract_params(cfg)
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            ab, is_leaf=lambda x: isinstance(x, ParamAb))[0]:
        names = [str(getattr(p, "key", "")) for p in path]
        if not include_embed and (names[0] in ("embed", "lm_head")):
            continue
        n = leaf.size
        if active_only and names[-1] in _EXPERT_KEYS and cfg.num_experts:
            n = n * cfg.num_experts_per_tok // cfg.num_experts
        total += n
    return total
