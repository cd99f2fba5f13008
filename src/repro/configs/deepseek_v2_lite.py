"""deepseek-v2-lite [moe]: 27L d_model=2048 16H, multi-head latent attention
(kv_lora_rank=512, q·k width 128+64, v width 128, no q LoRA, YaRN rope
factor 40 over 4096 positions), one leading dense layer (MLP 10944), then
26 MoE layers of 64 routed experts (width 1408, top-6 by softmax, not
renormalised) and 2 shared experts; vocab=102400, head untied
[arXiv:2405.04434; huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json]."""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=1408,
    vocab=102_400,
    n_experts=64,
    top_k=6,
    moe_every=1,
    n_shared_experts=2,
    norm_topk_prob=False,
    routed_scaling_factor=1.0,
    first_k_dense=1,
    dense_d_ff=10_944,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=10_000.0,
    rope_factor=40.0,
    rope_original_max_len=4096,
    rope_beta_fast=32.0,
    rope_beta_slow=1.0,
    rope_mscale=0.707,
    rope_mscale_all_dim=0.707,
    norm_eps=1e-6,
    source="arXiv:2405.04434",
)
