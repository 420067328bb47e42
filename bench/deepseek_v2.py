"""What the benchmark knows of the program for DeepSeek-V2: how its
configuration file maps onto the program's ``ModelConfig``, how the
program lays out the parameter tree of an MLA + shared-expert stack with
a dense first layer, and how that tree is handed to the plain reference
(``bench/reference/deepseek_v2.py``).

Weights are the benchmark's own, drawn from the seed on the device in
one jitted call and stored in bfloat16 as the configuration states.
"""
from __future__ import annotations

import dataclasses

from bench.program import jax_seed

# configuration-file key -> ModelConfig field
_FIELDS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "intermediate_size": "d_ff",
    "moe_intermediate_size": "moe_d_ff",
    "num_experts_per_tok": "num_experts_per_tok",
    "n_shared_experts": "num_shared_experts",
    "first_k_dense_replace": "first_k_dense",
    "norm_topk_prob": "norm_topk_prob",
    "routed_scaling_factor": "routed_scaling_factor",
    "n_group": "n_group",
    "topk_group": "topk_group",
    "n_routed_experts": "num_experts_held",
    "first_held_expert": "first_held_expert",
}

# what the program computes and the file must state
_REQUIRED = {"topk_method": "group_limited_greedy", "scoring_func": "softmax",
             "hidden_act": "silu", "moe_layer_freq": 1,
             "attention_bias": False}


def router_experts(cfgj: dict) -> int:
    """The router's outputs: the published expert count (the file's
    ``n_routed_experts`` counts the experts held here)."""
    return cfgj.get("published", {}).get("n_routed_experts",
                                         cfgj["n_routed_experts"])


def model_config(cfgj: dict, page_size: int):
    """The program's ``ModelConfig`` for a DeepSeek-V2 configuration file.
    A program without the held-expert, routing and YaRN fields fails
    here, before anything is drawn."""
    from repro.configs import get_config

    for key, want in _REQUIRED.items():
        if cfgj.get(key) != want:
            raise ValueError(f"{cfgj['name']}: the program computes with "
                             f"{key}={want!r}, the file states "
                             f"{cfgj.get(key)!r}")
    rs = cfgj["rope_scaling"]
    if rs.get("type") != "yarn" or rs["mscale"] != rs["mscale_all_dim"]:
        # the program's rope carries no cos/sin factor mscale/mscale_all_dim
        raise ValueError(f"{cfgj['name']}: the program computes YaRN with "
                         f"mscale = mscale_all_dim, the file states "
                         f"{rs!r}")
    fields = {f: cfgj[k] for k, f in _FIELDS.items() if k in cfgj}
    fields.update(
        num_experts=router_experts(cfgj),
        rope_scaling_factor=float(rs["factor"]),
        rope_original_max_pos=int(rs["original_max_position_embeddings"]),
        yarn_beta_fast=float(rs["beta_fast"]),
        yarn_beta_slow=float(rs["beta_slow"]),
        yarn_mscale=float(rs["mscale_all_dim"]))
    return dataclasses.replace(
        get_config(cfgj["registry"]), **fields, cache_layout="paged",
        page_size=page_size, dtype=cfgj["compute_dtype"],
        param_dtype=cfgj["param_dtype"])


def _leaf_rule(key: str, cfg) -> tuple:
    """(kind, scale) of the benchmark's draw for one parameter leaf."""
    D, H = cfg.d_model, cfg.num_heads
    fan_in = {"q_a": D, "q_b": cfg.q_lora_rank, "kv_a": D,
              "kv_b": cfg.kv_lora_rank, "o": H * cfg.v_head_dim,
              "wg": D, "wu": D, "wd": cfg.d_ff, "we_g": D, "we_u": D,
              "we_d": cfg.moe_d_ff, "ws_g": D, "ws_u": D,
              "ws_d": cfg.moe_d_ff * cfg.num_shared_experts, "lm_head": D}
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
    device in one jitted call, stored in ``cfg.param_dtype``; norm gains
    stay float32."""
    import jax
    import jax.numpy as jnp

    from repro.models.params import init_params

    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    rules = [_leaf_rule(str(getattr(p[-1], "key", "")), cfg)
             for p, _ in paths]
    store = jnp.dtype(cfg.param_dtype)

    @jax.jit
    def draw(key):
        out = []
        for i, ((_, sd), (kind, s)) in enumerate(zip(paths, rules)):
            z = jax.random.normal(jax.random.fold_in(key, i), sd.shape,
                                  jnp.float32)
            if kind == "gain":
                out.append(1.0 + s * z)
            else:
                out.append((s * z).astype(store))
        return jax.tree.unflatten(treedef, out)

    return draw(jax.random.key(jax_seed(seed), impl="rbg"))


def _attn(lay: dict) -> dict:
    a = lay["attn"]
    return {"attn_norm": lay["pre_norm"], "q_a": a["q_a"],
            "q_norm": a["q_norm"], "q_b": a["q_b"], "kv_a": a["kv_a"],
            "kv_norm": a["kv_norm"], "kv_b": a["kv_b"], "wo": a["o"],
            "ffn_norm": lay["ffn_norm"]}


def reference_weights(params, cfgj: dict) -> dict:
    """The reference's view of the program-layout tree: the dense first
    layer, the MoE layers stacked, the vocabulary cut to its real rows.
    The arrays stay as stored (bfloat16); the reference upcasts them."""
    dec = params["decoder"]
    if set(dec) != {"prefix", "groups"} or set(dec["groups"]) != {"0"} \
            or set(dec["prefix"]) != {"0"}:
        raise ValueError("expected one dense layer and one scanned group")
    first = dec["prefix"]["0"]
    lay = dec["groups"]["0"]
    f, m = first["ffn"], lay["moe"]
    V = cfgj["vocab_size"]
    return {"embed": params["embed"][:V], "final_norm": params["final_norm"],
            "lm_head": params["lm_head"][:, :V],
            "dense": {**_attn(first), "w_gate": f["wg"], "w_up": f["wu"],
                      "w_down": f["wd"]},
            "moe": {**_attn(lay), "router": m["router"], "e_gate": m["we_g"],
                    "e_up": m["we_u"], "e_down": m["we_d"],
                    "s_gate": m["ws_g"], "s_up": m["ws_u"],
                    "s_down": m["ws_d"]}}
