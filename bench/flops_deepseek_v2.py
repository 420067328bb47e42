"""Operations and bytes DeepSeek-V2's decode requires on this chip,
computed from the configuration file, never from the program.

"Required" is the chip's share as the configuration states it: MLA in
its absorbed decode form (scores and values in the latent space), the
router and the shared experts for every token, the routed experts at
their expected share here (``num_experts_per_tok`` x held / published
experts, 0.75 of an expert a token), the vocabulary slice.
``forward_flops`` has the signature of ``bench/flops.py``'s, so a
serving driver can count with it in that module's place.
"""
from __future__ import annotations


def _mla(c: dict) -> tuple:
    return (c["num_attention_heads"], c["kv_lora_rank"],
            c["qk_rope_head_dim"], c["qk_nope_head_dim"], c["v_head_dim"])


def attention_flops(c: dict, ctx: int) -> float:
    """One token's absorbed MLA in one layer, ``ctx`` keys visible."""
    D = c["hidden_size"]
    H, lora, rd, nope, vd = _mla(c)
    proj = 2 * D * c["q_lora_rank"] + 2 * c["q_lora_rank"] * H * (nope + rd) \
        + 2 * D * (lora + rd) + 2 * H * nope * lora + 2 * H * lora * vd \
        + 2 * H * vd * D
    return proj + (2 * H * (lora + rd) + 2 * H * lora) * ctx


def forward_flops(c: dict, ctx: int) -> float:
    """FLOPs of one token's forward pass with ``ctx`` keys visible (itself
    included): MLA, the dense first layer(s), the MoE layers' router,
    shared experts and expected routed share, the head."""
    D, L = c["hidden_size"], c["num_hidden_layers"]
    dense = c["first_k_dense_replace"]
    E = c.get("published", {}).get("n_routed_experts", c["n_routed_experts"])
    Fe = c["moe_intermediate_size"]
    routed = c["num_experts_per_tok"] * c["n_routed_experts"] / E
    moe = 2 * D * E + 6 * D * Fe * (c["n_shared_experts"] + routed)
    return L * attention_flops(c, ctx) + dense * 6 * D * c["intermediate_size"] \
        + (L - dense) * moe + 2 * D * c["vocab_size"]


def mla_decode_cost(c: dict, positions, page_size: int,
                    itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of one ``mla_paged_decode`` kernel call (one layer)
    over the active rows at ``positions`` (each row's newest position).

    FLOPs per visible position are the latent scores (lora+rope wide) and
    the latent read (lora wide) for every head; bytes are the resident
    latent pages the row's keys live in, plus its query row (lora+rope per
    head) and output row (lora per head)."""
    H, lora, rd, _, _ = _mla(c)
    fl, by = 0.0, 0.0
    for p in positions:
        pages = p // page_size + 1
        fl += (2 * H * (lora + rd) + 2 * H * lora) * (p + 1)
        by += pages * page_size * (lora + rd) * itemsize \
            + H * (2 * lora + rd) * itemsize
    return fl, by
