"""deepseek-v2-lite-16b [moe] — DeepSeek-V2-Lite as published
(https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json;
arXiv:2405.04434): 27 layers, d_model 2048, 16 heads; MLA without q-LoRA
(kv_lora_rank 512, qk_nope 128, qk_rope 64, v_head 128); layer 0 a dense
SwiGLU FFN of width 10944 (first_k_dense_replace 1), layers 1–26 MoE with
64 routed experts of width 1408, top-6 by softmax scores (greedy, the top-k
weights not renormalised: norm_topk_prob false, routed_scaling_factor 1)
plus 2 shared experts; YaRN rope (factor 40, mscale = mscale_all_dim =
0.707, beta_fast 32, beta_slow 1, original context 4096, rope_theta 1e4);
vocabulary 102400, untied head, RMSNorm eps 1e-6.  The rotary pairing is
the repo's half-split one, not the published interleaved one: a fixed
permutation of the rope columns."""

from .base import ModelConfig, RopeScaling

#: the published ``rope_scaling``
YARN = RopeScaling(factor=40.0, original_max_position_embeddings=4096,
                   beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                   mscale_all_dim=0.707)

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, kv_heads=16,
    d_ff=10944, vocab=102400, rope_theta=1e4, rope_scaling=YARN,
    n_experts=64, top_k=6, n_shared_experts=2, moe_d_ff=1408,
    first_dense_layers=1, capacity_factor=1.25, norm_topk_prob=False,
    use_mla=True, kv_lora=512, qk_nope_dim=128, qk_rope_dim=64,
    v_head_dim=128,
)


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite-smoke", family="moe",
        n_layers=3, d_model=64, n_heads=4, kv_heads=4,
        d_ff=160, vocab=256, rope_theta=1e4, rope_scaling=YARN,
        n_experts=8, top_k=2, n_shared_experts=1, moe_d_ff=48,
        first_dense_layers=1, capacity_factor=1.25, norm_topk_prob=False,
        use_mla=True, kv_lora=32, qk_nope_dim=16, qk_rope_dim=8,
        v_head_dim=16,
        attn_q_chunk=32, attn_k_chunk=32, remat="none",
    )
