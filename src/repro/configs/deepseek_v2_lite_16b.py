"""DeepSeek-V2-Lite 16B [arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite].

MoE decoder with Multi-head Latent Attention: 27L, d_model=2048, 16 heads,
MLA kv_lora_rank=512 (qk_nope=128, qk_rope=64, v=128, no q LoRA), YaRN rope
(factor 40 over 4096 positions, beta 32/1, mscale = mscale_all_dim =
0.707). Layer 0 is dense (SwiGLU width 10944, ``first_k_dense_replace``
1); layers 1-26 hold 64 routed experts of width 1408 (softmax scores,
greedy top-6, weights not renormalised) plus 2 shared experts.
rms_norm_eps 1e-6, vocab 102400, untied head.
"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    arch_type="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,  # MLA: all heads read the shared latent; kept for spec
    head_dim=128,
    d_ff=10944,  # the leading dense layer's width
    vocab_size=102400,
    moe=MoEConfig(num_experts=64, top_k=6, d_ff_expert=1408,
                  num_shared_experts=2, norm_topk_prob=False,
                  routed_scaling_factor=1.0),
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    first_dense_layers=1,
    norm_eps=1e-6,
    rope_theta=10_000.0,
    rope_scaling=YarnConfig(factor=40.0, original_max_position_embeddings=4096,
                            beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                            mscale_all_dim=0.707),
)
