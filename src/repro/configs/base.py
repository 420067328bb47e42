"""Model / shape / run configuration for the repro framework.

Every assigned architecture is expressed as a :class:`ModelConfig`.  The
platform layer (``repro.core``) treats an architecture the way the paper's
DLaaS treats a *framework*: an opaque, selectable learner payload.  The
training substrate (``repro.models`` / ``repro.train``) consumes the config
directly.

Design notes
------------
* Configs are frozen dataclasses — hashable, usable as jit static args.
* ``reduced()`` returns a tiny config of the *same family* for CPU smoke
  tests (same code paths: same block pattern, MoE/MLA/recurrence flags).
* ``padded_vocab`` rounds the vocabulary up to a multiple of
  ``pad_vocab_multiple`` so the embedding/vocab dims shard cleanly over the
  fixed production mesh (and align with the 128-lane MXU).  Logits for the
  padding ids are masked to ``-inf`` in the loss/serve paths.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

# ---------------------------------------------------------------------------
# Block kinds — the per-layer pattern vocabulary.
# ---------------------------------------------------------------------------
GLOBAL_ATTN = "global"      # full causal attention
LOCAL_ATTN = "local"        # sliding-window causal attention
RECURRENT = "recurrent"     # RG-LRU recurrent block (Griffin/RecurrentGemma)
RWKV = "rwkv"               # RWKV6 time-mix block (attention-free)

BLOCK_KINDS = (GLOBAL_ATTN, LOCAL_ATTN, RECURRENT, RWKV)


@dataclass(frozen=True)
class ModelConfig:
    """Architecture hyper-parameters for one model family instance."""

    name: str
    family: str                       # dense | hybrid | ssm | moe | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int

    # --- block pattern ----------------------------------------------------
    # The repeating tuple of block kinds; tiled (and truncated) to
    # ``num_layers``.  E.g. gemma-2 = ("local", "global"),
    # recurrentgemma = ("recurrent", "recurrent", "local").
    block_pattern: Tuple[str, ...] = (GLOBAL_ATTN,)
    window_size: int = 0              # local-attention window (tokens)

    # --- attention features -------------------------------------------------
    qk_norm: bool = False             # per-head RMSNorm on q,k (qwen3)
    qkv_bias: bool = False            # bias on q,k,v projections (qwen2.5)
    attn_logit_softcap: float = 0.0   # tanh softcap on attention logits (gemma2)
    final_logit_softcap: float = 0.0  # tanh softcap on output logits (gemma2)
    query_pre_attn_scalar: float = 0.0  # overrides 1/sqrt(head_dim) when > 0
    rope_theta: float = 10_000.0
    tie_embeddings: bool = False
    # YaRN RoPE scaling (DeepSeek-V2's ``rope_scaling``); factor 1 = plain
    # RoPE.  Applied on MLA's rope dims; ``yarn_mscale`` is its
    # ``mscale_all_dim``, folded into the softmax scale as mscale^2 (its
    # ``mscale`` equals it, so the rope's cos/sin factor is 1).
    rope_scaling_factor: float = 1.0
    rope_original_max_pos: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 0.0

    # --- MLA (DeepSeek-V2 multi-head latent attention) ----------------------
    use_mla: bool = False
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # --- MoE ----------------------------------------------------------------
    num_experts: int = 0              # routed experts (0 = dense FFN)
    num_experts_per_tok: int = 0
    num_shared_experts: int = 0
    moe_d_ff: int = 0                 # per-expert hidden dim
    first_k_dense: int = 0            # leading layers that keep a dense FFN
    capacity_factor: float = 1.25
    router_aux_loss_coef: float = 0.001
    moe_group_size: int = 1024        # tokens per dispatch group (§Perf knob)
    # routing: softmax over all E, then (with n_group > 1) DeepSeek-V2's
    # group_limited_greedy — keep the topk_group groups whose best expert
    # scores highest, top-k inside them; weights renormalised to sum 1
    # (norm_topk_prob) and multiplied by routed_scaling_factor
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    n_group: int = 1
    topk_group: int = 1
    # the experts this layer holds: ``num_experts_held`` (0 = all E) from
    # ``first_held_expert`` on — one chip's share under expert parallelism.
    # The router still scores all E; the layer adds only its experts' part.
    num_experts_held: int = 0
    first_held_expert: int = 0

    # --- recurrence (RG-LRU) ------------------------------------------------
    rnn_width: int = 0
    conv1d_width: int = 4

    # --- RWKV6 ---------------------------------------------------------------
    rwkv_head_dim: int = 64
    rwkv_ddlerp_rank: int = 32        # token-shift LoRA rank
    rwkv_decay_rank: int = 64         # data-dependent decay LoRA rank

    # --- encoder/decoder -----------------------------------------------------
    is_encoder_decoder: bool = False
    num_encoder_layers: int = 0

    # --- modality frontend (STUB per the brief) ------------------------------
    frontend: str = "none"            # none | audio | vision
    frontend_tokens: int = 0          # precomputed embeddings prepended (vision)

    # --- serving KV-cache layout ---------------------------------------------
    # ``dense``: one (B, K, S_max, hd) buffer per layer (the fallback).
    # ``paged``: global-attention layers keep a shared pool of fixed-size
    # pages plus per-sequence page tables (vLLM-style); ring-buffer (local)
    # and MLA-latent caches stay dense — they are already bounded.  Decode
    # logits are identical between the two layouts (tested).
    cache_layout: str = "dense"       # dense | paged
    page_size: int = 128              # tokens per KV page (paged layout)

    # --- numerics / misc ------------------------------------------------------
    norm_eps: float = 1e-6
    act: str = "silu"                 # silu | gelu_tanh
    embed_scale_by_sqrt_dim: bool = False  # gemma-style sqrt(d) input scaling
    use_post_block_norm: bool = False      # gemma2 sandwich norms
    pad_vocab_multiple: int = 128
    dtype: str = "bfloat16"           # activation/compute dtype
    param_dtype: str = "float32"      # storage dtype (cast to `dtype` in fwd)

    # ------------------------------------------------------------------
    @property
    def padded_vocab(self) -> int:
        m = self.pad_vocab_multiple
        return ((self.vocab_size + m - 1) // m) * m

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def experts_held(self) -> int:
        return self.num_experts_held or self.num_experts

    @property
    def rope_yarn(self) -> Optional[Tuple[float, int, float, float]]:
        """``(factor, original_max_pos, beta_fast, beta_slow)`` where YaRN
        scales the rope, else None."""
        if self.rope_scaling_factor == 1.0:
            return None
        return (self.rope_scaling_factor, self.rope_original_max_pos,
                self.yarn_beta_fast, self.yarn_beta_slow)

    @property
    def uses_attention(self) -> bool:
        return any(k in (GLOBAL_ATTN, LOCAL_ATTN) for k in self.layer_kinds())

    @property
    def sub_quadratic(self) -> bool:
        """True when no layer does *full* (global) attention — required for
        the ``long_500k`` shape."""
        return GLOBAL_ATTN not in self.layer_kinds()

    def layer_kinds(self, num_layers: Optional[int] = None) -> Tuple[str, ...]:
        """The per-layer block kinds, pattern tiled to ``num_layers``."""
        n = self.num_layers if num_layers is None else num_layers
        pat = self.block_pattern
        reps = (n + len(pat) - 1) // len(pat)
        return tuple((pat * reps)[:n])

    def scan_groups(self) -> Tuple[int, Tuple[str, ...], int]:
        """Decompose the layer stack into (n_full_groups, pattern, n_tail).

        The model scans ``n_full_groups`` repetitions of ``pattern`` with
        stacked weights and applies the remaining ``n_tail`` layers
        (``pattern[:n_tail]``) unrolled.  MoE first-k-dense layers are also
        peeled off into the tail-equivalent prefix (handled in the model).
        """
        pat = self.block_pattern
        n = self.num_layers - self.first_k_dense
        groups, tail = divmod(n, len(pat))
        return groups, pat, tail

    # ------------------------------------------------------------------
    def reduced(self) -> "ModelConfig":
        """A tiny same-family config for CPU smoke tests.

        Preserves: block pattern, MoE/MLA/recurrence/enc-dec flags, GQA
        ratio feel, activation/norm choices.  Shrinks: layers, widths,
        expert count, vocab.
        """
        few_layers = max(len(self.block_pattern) + 1, 3)
        if self.first_k_dense:
            few_layers = max(few_layers, self.first_k_dense + 2)
        kv = min(self.num_kv_heads, 2) or 1
        heads = max(4, kv * 2)
        return dataclasses.replace(
            self,
            name=self.name + "-reduced",
            num_layers=few_layers,
            d_model=64,
            num_heads=heads,
            num_kv_heads=kv,
            head_dim=16,
            d_ff=128,
            vocab_size=503,                   # deliberately odd: exercises padding
            window_size=min(self.window_size, 16) if self.window_size else 0,
            q_lora_rank=24 if self.q_lora_rank else 0,
            kv_lora_rank=16 if self.kv_lora_rank else 0,
            qk_nope_head_dim=16 if self.qk_nope_head_dim else 0,
            qk_rope_head_dim=8 if self.qk_rope_head_dim else 0,
            v_head_dim=16 if self.v_head_dim else 0,
            num_experts=4 if self.num_experts else 0,
            num_experts_per_tok=min(self.num_experts_per_tok, 2),
            num_shared_experts=min(self.num_shared_experts, 1),
            moe_d_ff=32 if self.moe_d_ff else 0,
            first_k_dense=min(self.first_k_dense, 1),
            n_group=min(self.n_group, 2),
            topk_group=min(self.topk_group, 1),
            num_experts_held=0,
            first_held_expert=0,
            rope_original_max_pos=min(self.rope_original_max_pos, 16),
            rnn_width=64 if self.rnn_width else 0,
            rwkv_ddlerp_rank=8,
            rwkv_decay_rank=8,
            num_encoder_layers=2 if self.is_encoder_decoder else 0,
            frontend_tokens=min(self.frontend_tokens, 4),
            pad_vocab_multiple=32,
            page_size=8,                      # page on tiny CPU sequences too
        )

    # ------------------------------------------------------------------
    def param_count(self) -> int:
        """Approximate parameter count (used for 6ND model-FLOPs)."""
        from repro.models.model import count_params  # local import: avoid cycle
        return count_params(self)

    def active_param_count(self) -> int:
        """Active params per token (MoE: shared + top-k routed only)."""
        from repro.models.model import count_params
        return count_params(self, active_only=True)


# ---------------------------------------------------------------------------
# Input shapes — each architecture is paired with the same 4-shape grid; the
# launcher skips cells per the applicability rules (DESIGN.md §4).
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                 # train | prefill | decode

    @property
    def tokens(self) -> int:
        return self.seq_len * self.global_batch


SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


def shape_applicable(cfg: ModelConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """(applicable?, reason).  Mirrors DESIGN.md §4."""
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return False, "long_500k requires sub-quadratic attention; " \
                      f"{cfg.name} has full global attention"
    return True, ""


# ---------------------------------------------------------------------------
# Per-(arch, shape) run knobs: grad-accum microbatches and remat policy are a
# memory-fit decision, recorded here so the dry-run is reproducible.
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class RunConfig:
    num_microbatches: int = 1
    remat_policy: str = "none"       # none | dots | full
    learning_rate: float = 3e-4
    weight_decay: float = 0.1
    grad_clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    # Memory/precision knobs for the largest models (e.g. 236B on 256 chips):
    # bf16 master + bf16 moments is the documented trade-off in DESIGN.md.
    master_dtype: str = "float32"
    opt_dtype: str = "float32"
    # Per-cell sharding-rule overrides: ((logical_axis, (mesh_axes...)), ...)
    # — the §Perf hillclimbing knob (e.g. (("resid_seq", ("model",)),) turns
    # on sequence-parallel residuals for this arch × shape).
    sharding_overrides: Tuple[Tuple[str, Tuple[str, ...]], ...] = ()
    # Gradient compression on the learner all-reduce ("none" | "int8" |
    # "topk") — the paper's efficiency-vs-dependability tradeoff, resolved
    # by repro.dist.compression.resolve_compression.
    grad_compression: str = "none"
    # Expected mean KV-cache occupancy for *paged* decode cells.  Continuous
    # batching keeps the pool near a target utilization instead of reserving
    # worst-case S for every sequence; the scheduler admits a cell by this
    # allocated-page budget (launch.specs.decode_page_budget), not by S_max.
    page_occupancy: float = 1.0
    # Expected fraction of each sequence's resident pages that are prefix
    # pages SHARED across the batch (system prompts / few-shot templates,
    # deduplicated by the engine's hash-addressed prefix cache).  Shared
    # pages are physically resident once, so bandwidth and admission
    # pricing count them once (launch.specs "kernel_unique" path).
    prefix_share_frac: float = 0.0


# Registry -------------------------------------------------------------------
_REGISTRY: Dict[str, ModelConfig] = {}
_RUN_OVERRIDES: Dict[Tuple[str, str], RunConfig] = {}


def register(cfg: ModelConfig) -> ModelConfig:
    if cfg.name in _REGISTRY:
        raise ValueError(f"duplicate config {cfg.name!r}")
    _REGISTRY[cfg.name] = cfg
    return cfg


def register_run(arch: str, shape: str, run: RunConfig) -> None:
    _RUN_OVERRIDES[(arch, shape)] = run


def get_config(name: str) -> ModelConfig:
    _ensure_loaded()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown architecture {name!r}; known: {sorted(_REGISTRY)}"
        ) from None


def get_run_config(arch: str, shape: str) -> RunConfig:
    _ensure_loaded()
    if (arch, shape) in _RUN_OVERRIDES:
        return _RUN_OVERRIDES[(arch, shape)]
    # training at production shapes always activation-checkpoints by default
    if SHAPES.get(shape) is not None and SHAPES[shape].kind == "train":
        return RunConfig(remat_policy="full")
    return RunConfig()


def list_configs() -> Tuple[str, ...]:
    _ensure_loaded()
    return tuple(sorted(_REGISTRY))


_LOADED = False


def _ensure_loaded() -> None:
    """Import every config module exactly once (they self-register)."""
    global _LOADED
    if _LOADED:
        return
    _LOADED = True
    from repro.configs import (  # noqa: F401
        recurrentgemma_9b,
        rwkv6_7b,
        qwen3_0_6b,
        gemma2_9b,
        mistral_large_123b,
        qwen2_5_32b,
        seamless_m4t_medium,
        internvl2_76b,
        deepseek_v2_236b,
        granite_moe_1b_a400m,
        paper_overhead,
    )
