"""DeepSeek-V2 (236B, 21B active): MLA (kv_lora=512) + MoE 160 routed top-6
with 2 shared experts; first layer dense; group-limited greedy routing (8
groups, top-3) with unnormalised weights scaled by 16; YaRN RoPE (factor
40 over 4096 positions). [arXiv:2405.04434, hf:deepseek-ai/DeepSeek-V2]"""
from repro.configs.base import GLOBAL_ATTN, ModelConfig, RunConfig, register, register_run

CONFIG = register(ModelConfig(
    name="deepseek-v2-236b",
    family="moe",
    num_layers=60,
    d_model=5120,
    num_heads=128,
    num_kv_heads=128,             # MLA: per-head K/V expanded from the latent
    head_dim=128,
    d_ff=12288,                   # dense FFN of the first layer
    vocab_size=102_400,
    block_pattern=(GLOBAL_ATTN,),
    use_mla=True,
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    num_experts=160,
    num_experts_per_tok=6,
    num_shared_experts=2,
    moe_d_ff=1536,
    first_k_dense=1,
    norm_topk_prob=False,
    routed_scaling_factor=16.0,
    n_group=8,
    topk_group=3,
    rope_theta=10_000.0,
    rope_scaling_factor=40.0,
    rope_original_max_pos=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
))

# 236B params on 256 × 16 GB chips: fp32 master + fp32 moments alone would be
# 11 GB/chip.  bf16 master + bf16 moments is the deployable configuration
# (DESIGN.md §memory); fp32 is restored when running on a larger mesh.
register_run("deepseek-v2-236b", "train_4k",
             RunConfig(num_microbatches=16, remat_policy="full",
                       master_dtype="bfloat16", opt_dtype="bfloat16",
                       sharding_overrides=(("resid_seq", ("model",)),)))
