"""DeepSeek-V2-Lite: 27 layers, hidden 2048, the first dense (SwiGLU 10944),
then 26 MoE layers of 64 routed experts (width 1408, softmax, greedy top-6,
no top-k renormalization) and 2 shared experts; latent attention (MLA)
with 16 heads, kv_lora_rank 512, q/k heads of 128 + 64 rotary dims, V 128;
YaRN rope (factor 40 over 4096 positions).  vocab 102400, untied head.
[arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite config.json]
"""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=10944, vocab_size=102400,
    n_experts=64, n_shared_experts=2, top_k=6, moe_d_ff=1408, first_k_dense=1,
    norm_topk_prob=False, routed_scaling_factor=1.0, moe_aux_coef=0.001,
    moe_seq_aux=True,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=10000.0, rope_scaling_factor=40.0, rope_original_max=4096,
    yarn_beta_fast=32.0, yarn_beta_slow=1.0, yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    norm="rmsnorm", gated_mlp=True, tie_embeddings=False,
    max_position=163840,
)
