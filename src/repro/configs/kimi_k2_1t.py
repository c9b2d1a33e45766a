"""kimi-k2-1t-a32b — Kimi-K2-Instruct, a 1.04T-parameter DeepSeek-V3-style
decoder with 32B active [hf:moonshotai/Kimi-K2-Instruct, config.json]:
latent attention (MLA), 384 sigmoid-routed experts (top 8, correction
bias, scaling 2.827) plus one shared expert, one leading dense layer and
YaRN rotary. The framework's capacity headline case: optimizer states
live in the host pool (the paper's 671B-in-CXL story)."""

from repro.models.mla_moe import MLAMoEConfig

ARCH_ID = "kimi-k2-1t-a32b"

FULL = MLAMoEConfig(
    name=ARCH_ID,
    num_layers=61, d_model=7168, num_heads=64,
    q_lora_rank=1536, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    d_ff=18432, moe_d_ff=2048, n_routed_experts=384, n_shared_experts=1,
    top_k=8, first_k_dense=1, routed_scaling_factor=2.827,
    norm_topk_prob=True, vocab=163840,
    rope_theta=50_000.0, rope_factor=32.0, original_max_position=4096,
    beta_fast=1.0, beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0,
    norm_eps=1e-6,
)

# Reduced same-family config for CPU smoke tests: every mechanism (MLA
# with YaRN, a dense first layer, sigmoid routing with bias, a shared
# expert) at toy widths.
SMOKE = MLAMoEConfig(
    name=ARCH_ID + "-smoke",
    num_layers=3, d_model=64, num_heads=4,
    q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    d_ff=128, moe_d_ff=32, n_routed_experts=16, n_shared_experts=1,
    top_k=4, first_k_dense=1, routed_scaling_factor=2.827,
    norm_topk_prob=True, vocab=256,
    rope_theta=50_000.0, rope_factor=32.0, original_max_position=16,
    beta_fast=1.0, beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0,
)
