"""DeepSeek-V2-Lite: MLA attention + fine-grained MoE.

[arXiv:2405.04434; HF deepseek-ai/DeepSeek-V2-Lite config.json] 27L,
d_model=2048, 16H, MLA kv_lora_rank=512 (no q LoRA; qk_nope=128, qk_rope=64,
v=128), YaRN rope (factor 40 over 4096 original positions, beta_fast 32,
beta_slow 1, mscale = mscale_all_dim = 0.707, theta 1e4), vocab=102400,
RMSNorm eps 1e-6. Layer 0 is dense (first_k_dense_replace=1, SwiGLU width
10944); the other 26 are MoE: 64 routed experts of width 1408, softmax
scores, greedy top-6 without renormalisation (norm_topk_prob false,
routed_scaling_factor 1), 2 shared experts, and the sequence-wise balance
loss (seq_aux; its alpha, 0.001, is the reference code's default: the
config leaves it out).
"""
from repro.models.config import ArchConfig, MLAConfig, MoEConfig, YarnConfig

ARCH = ArchConfig(
    name="deepseek-v2-lite-16b",
    n_layers=27,
    n_dense_layers=1,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10944,
    vocab=102400,
    attn_type="mla",
    mla=MLAConfig(kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128),
    ffn_pattern=("moe",),
    moe=MoEConfig(n_experts=64, top_k=6, n_shared=2, d_expert=1408, norm_topk=False,
                  aux="seq", router_aux_weight=0.001),
    rope_theta=1e4,
    yarn=YarnConfig(factor=40.0, original_max_positions=4096, beta_fast=32.0, beta_slow=1.0,
                    mscale=0.707, mscale_all_dim=0.707),
    norm_eps=1e-6,
    citation="arXiv:2405.04434",
)

SMOKE = ArchConfig(
    name="deepseek-v2-lite-smoke",
    n_layers=3,
    n_dense_layers=1,
    d_model=256,
    n_heads=4,
    n_kv_heads=4,
    d_ff=512,
    vocab=512,
    attn_type="mla",
    mla=MLAConfig(kv_lora_rank=64, qk_nope_dim=32, qk_rope_dim=16, v_head_dim=32),
    ffn_pattern=("moe",),
    moe=MoEConfig(n_experts=4, top_k=2, n_shared=1, d_expert=128, norm_topk=False,
                  aux="seq", router_aux_weight=0.001),
    rope_theta=1e4,
    yarn=YarnConfig(factor=40.0, original_max_positions=4096, beta_fast=32.0, beta_slow=1.0,
                    mscale=0.707, mscale_all_dim=0.707),
    norm_eps=1e-6,
    citation="arXiv:2405.04434 (reduced)",
)
