"""Operations and bytes the algorithm needs, computed from shapes.

Counted from the configuration file, never from the program: a later
change to the program cannot change what a step is worth.  "Required"
means what the mathematics needs: routed experts only (top-k of E), the
real vocabulary, attention over the keys a query can see.
"""
from __future__ import annotations


def _dims(c: dict):
    return (c["hidden_size"], c["num_hidden_layers"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["intermediate_size"],
            c["vocab_size"])


def forward_flops(c: dict, ctx: int) -> float:
    """FLOPs of one token's forward pass with ``ctx`` keys visible
    (itself included): projections, attention, feed-forward, unembed."""
    D, L, H, K, hd, F, V = _dims(c)
    proj = 2 * D * (H + 2 * K) * hd + 2 * H * hd * D
    attn = 4 * H * hd * ctx                      # q.k and p.v
    E = c.get("num_local_experts", 0)
    if E:
        ffn = 2 * D * E + c["num_experts_per_tok"] * 6 * D * F
    else:
        ffn = 6 * D * F
    return L * (proj + attn + ffn) + 2 * D * V


def paged_decode_cost(c: dict, positions, page_size: int,
                      itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of one paged-decode kernel call (one layer) over
    the active rows at ``positions`` (each row's newest position).

    Bytes are the resident pages the row's keys live in, K and V, plus
    the query and output rows; FLOPs are q.k and p.v over the keys each
    row can see."""
    H, K, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    fl, by = 0.0, 0.0
    for p in positions:
        pages = p // page_size + 1
        fl += 4 * H * hd * (p + 1)
        by += pages * page_size * K * hd * 2 * itemsize + 2 * H * hd * itemsize
    return fl, by
