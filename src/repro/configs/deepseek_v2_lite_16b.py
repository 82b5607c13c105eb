"""DeepSeek-V2-Lite — 16B MoE with Multi-head Latent Attention.

[arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite config.json]  27L
d_model=2048 16H, vocab=102400 (untied).  MLA kv_lora_rank=512, no q
compression, YaRN rope scaling (factor 40 over 4096 positions).  Layer 0
is a dense SwiGLU FFN (d_ff=10944); layers 1-26 route each token to 6 of
64 experts (d_ff=1408) by a softmax router whose top-6 gates are used as
they are (``norm_topk_prob`` false, ``routed_scaling_factor`` 1), plus 2
shared experts.
"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,                 # MLA: latent-shared; kept for bookkeeping
    head_dim=128,                    # v_head_dim
    d_ff=1408,                       # routed-expert intermediate
    vocab_size=102400,
    moe=MoEConfig(
        num_experts=64,
        top_k=6,
        expert_d_ff=1408,
        num_shared_experts=2,
        shared_d_ff=2816,            # 2 shared experts fused: 2 x 1408
        norm_topk_prob=False,
    ),
    mla=MLAConfig(
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        q_lora_rank=None,
        yarn=YarnConfig(factor=40.0, original_max_position_embeddings=4096,
                        beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                        mscale_all_dim=0.707),
    ),
    first_dense_layers=1,
    first_dense_d_ff=10944,
    activation="swiglu",
    norm_type="rmsnorm",
    pos_embed="rope",
    rope_theta=10000.0,
)
