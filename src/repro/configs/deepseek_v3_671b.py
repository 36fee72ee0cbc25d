"""deepseek-v3-671b: MLA + 3 leading dense layers + 1 shared and 256 routed
top-8 experts under sigmoid, group-limited routing + MTP
[arXiv:2412.19437; hf deepseek-ai/DeepSeek-V3 config.json].

61 layers, d_model 7168, 128 MLA heads (q_lora 1536, kv_lora 512, qk_nope
128, qk_rope 64, v 128) with YaRN rope (factor 40 over 4096 positions); the
first 3 layers have a dense SwiGLU MLP of width 18432, the other 58 an MoE
layer of 256 routed experts of width 2048 (top-8; sigmoid scores, a
selection-only correction bias, 8 groups of which the top 4 survive,
normalized weights x 2.5) and one shared expert; vocab 129280;
rms_norm_eps 1e-6.
"""
import dataclasses

from repro.configs.base import MLAConfig, ModelConfig, YarnConfig

CONFIG = ModelConfig(
    name="deepseek-v3-671b", family="moe",
    num_layers=61, d_model=7168, num_heads=128, num_kv_heads=128,
    d_ff=18432, vocab_size=129280, head_dim=128,
    num_experts=256, experts_per_token=8, moe_d_ff=2048,
    num_shared_experts=1, first_k_dense_replace=3,
    router_scoring="sigmoid", router_bias=True, n_group=8, topk_group=4,
    norm_topk_prob=True, routed_scaling_factor=2.5,
    mla=MLAConfig(), rope_scaling=YarnConfig(), mtp=True,
    rope_theta=10000.0, norm_eps=1e-6,
)


def reduced() -> ModelConfig:
    return dataclasses.replace(
        CONFIG, name="deepseek-v3-671b-reduced", num_layers=3, d_model=64,
        num_heads=4, num_kv_heads=4, head_dim=16, d_ff=96, vocab_size=256,
        num_experts=8, experts_per_token=2, moe_d_ff=64, num_shared_experts=1,
        first_k_dense_replace=1, n_group=4, topk_group=2,
        mla=MLAConfig(q_lora_rank=32, kv_lora_rank=16,
                      qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16),
        rope_scaling=YarnConfig(factor=4.0, original_max_position_embeddings=16))
