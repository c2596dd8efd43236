"""DeepSeek-V3 671B. [arXiv:2412.19437; hf deepseek-ai/DeepSeek-V3]

61 layers, d_model 7168, 128 heads of multi-head latent attention (q rank
1536, kv rank 512, head dims 128 nope + 64 rope, v 128), vocab 129280,
untied head.  The first 3 layers have a dense FFN of 18432; the other 58
route each token to 8 of 256 experts of width 2048 (sigmoid scores with an
aux-free correction bias, 8 groups of which the best 4 are kept, weights
normalised and scaled by 2.5) plus 1 shared expert.  YaRN rotary scaling,
factor 40 over 4096 original positions; 1 multi-token-prediction layer.
"""
from .base import MLAConfig, ModelConfig, MoEConfig, YaRNConfig

CONFIG = ModelConfig(
    name="deepseek-v3-671b",
    family="moe",
    n_layers=61,
    d_model=7168,
    n_heads=128,
    n_kv_heads=128,
    d_ff=18432,            # dense-prefix layers
    vocab_size=129280,
    mla=MLAConfig(q_lora_rank=1536, kv_lora_rank=512,
                  qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(n_experts=256, top_k=8, d_expert=2048, n_shared=1,
                  capacity_factor=1.25, router_aux_free=True,
                  n_group=8, topk_group=4, routed_scale=2.5),
    dense_prefix=3,
    mtp_heads=1,
    rope_theta=1e4,
    rope_scaling=YaRNConfig(factor=40.0, original_max_position_embeddings=4096,
                            beta_fast=32.0, beta_slow=1.0, mscale=1.0,
                            mscale_all_dim=1.0),
    max_seq_len=163840,
    source="arXiv:2412.19437; hf",
)

SMOKE = ModelConfig(
    name="deepseek-v3-671b-smoke",
    family="moe",
    n_layers=4,
    d_model=64,
    n_heads=4,
    n_kv_heads=4,
    d_ff=128,
    vocab_size=256,
    mla=MLAConfig(q_lora_rank=32, kv_lora_rank=16,
                  qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16),
    moe=MoEConfig(n_experts=8, top_k=2, d_expert=32, n_shared=1,
                  capacity_factor=1.5, router_aux_free=True,
                  n_group=4, topk_group=2, routed_scale=2.5),
    dense_prefix=3,
    mtp_heads=1,
    rope_scaling=CONFIG.rope_scaling,
    max_seq_len=128,
    source="smoke",
)
