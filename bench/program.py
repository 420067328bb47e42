"""What the benchmark knows of the system under test: how a configuration
file maps onto the program's ``ModelConfig``, and how the program lays
out its parameter tree.

Weights are the benchmark's own.  ``make_params`` draws them from the
seed, on the device, in one jitted call, into the tree the program
serves from (float32 masters; the program casts to bfloat16 inside its
steps).  ``reference_weights`` hands the same arrays to the plain
reference in its own layout, so the reference takes nothing that the
program has made.
"""
from __future__ import annotations

import dataclasses

import numpy as np

# configuration-file key -> ModelConfig field
_FIELDS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "qk_norm": "qk_norm",
    "num_local_experts": "num_experts",
    "num_experts_per_tok": "num_experts_per_tok",
}

# the program has no field for these; the configuration file must state
# the value the program computes with (its departure from the source is
# listed under ``reduced`` and ``assumed`` there)
_FIXED = {"embedding_multiplier": 1.0, "residual_multiplier": 1.0,
          "logits_scaling": 1.0}


def jax_seed(seed: int) -> int:
    """A 31-bit seed for ``jax.random`` from any whole number."""
    return int(np.random.default_rng([seed, 0]).integers(0, 2**31 - 1))


def model_config(cfgj: dict, page_size: int):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs import get_config

    for key, want in _FIXED.items():
        if float(cfgj.get(key, want)) != want:
            raise ValueError(f"{cfgj['name']}: the program computes with "
                             f"{key}={want}, the file states {cfgj[key]}")
    if "attention_multiplier" in cfgj and abs(
            cfgj["attention_multiplier"] * cfgj["head_dim"] ** 0.5 - 1) > 1e-6:
        raise ValueError(f"{cfgj['name']}: the program scales attention "
                         f"by head_dim ** -0.5")
    fields = {f: cfgj[k] for k, f in _FIELDS.items() if k in cfgj}
    width = cfgj["intermediate_size"]
    if cfgj.get("num_local_experts"):
        fields.update(moe_d_ff=width, d_ff=width)
    else:
        fields.update(d_ff=width)
    return dataclasses.replace(
        get_config(cfgj["registry"]), **fields, cache_layout="paged",
        page_size=page_size, dtype="bfloat16", param_dtype="float32")


def _leaf_rule(key: str, cfg) -> tuple:
    """(kind, scale) of the benchmark's draw for one parameter leaf."""
    D, H, hd = cfg.d_model, cfg.num_heads, cfg.head_dim
    fan_in = {"q": D, "k": D, "v": D, "o": H * hd, "wg": D, "wu": D,
              "wd": cfg.d_ff, "we_g": D, "we_u": D, "we_d": cfg.moe_d_ff,
              "lm_head": D}
    if key in ("embed", "router"):
        return "normal", 0.02
    if key.endswith("norm"):
        return "gain", 0.1
    if key in fan_in:
        return "normal", fan_in[key] ** -0.5
    raise KeyError(f"no draw for parameter leaf {key!r}: the program's "
                   f"tree has a leaf the benchmark does not know")


def make_params(cfg, seed: int):
    """The program-layout parameter tree, drawn from ``seed`` on the
    device in one jitted call (float32, as the program stores them)."""
    import jax
    import jax.numpy as jnp

    from repro.models.params import init_params

    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    rules = [_leaf_rule(str(getattr(p[-1], "key", "")), cfg)
             for p, _ in paths]

    @jax.jit
    def draw(key):
        out = []
        for i, ((_, sd), (kind, s)) in enumerate(zip(paths, rules)):
            z = jax.random.normal(jax.random.fold_in(key, i), sd.shape,
                                  jnp.float32)
            out.append(1.0 + s * z if kind == "gain" else s * z)
        return jax.tree.unflatten(treedef, out)

    return draw(jax.random.key(jax_seed(seed), impl="rbg"))


def reference_weights(params, cfgj: dict) -> dict:
    """The reference's view of the program-layout tree: one stacked
    entry per weight, the vocabulary cut to its real rows."""
    dec = params["decoder"]
    if set(dec) != {"groups"} or set(dec["groups"]) != {"0"}:
        raise ValueError("expected one scanned group of identical layers")
    lay = dec["groups"]["0"]
    att = lay["attn"]
    out = {"attn_norm": lay["pre_norm"], "wq": att["q"], "wk": att["k"],
           "wv": att["v"], "wo": att["o"], "ffn_norm": lay["ffn_norm"]}
    if "q_norm" in att:
        out.update(q_norm=att["q_norm"], k_norm=att["k_norm"])
    if "moe" in lay:
        m = lay["moe"]
        out.update(router=m["router"], e_gate=m["we_g"], e_up=m["we_u"],
                   e_down=m["we_d"])
    else:
        f = lay["ffn"]
        out.update(w_gate=f["wg"], w_up=f["wu"], w_down=f["wd"])
    V = cfgj["vocab_size"]
    ref = {"embed": params["embed"][:V], "final_norm": params["final_norm"],
           "layers": out}
    if "lm_head" in params:
        ref["lm_head"] = params["lm_head"][:, :V]
    return ref
