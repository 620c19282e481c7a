"""deepseek-v2-236b — MLA + 2 shared / 160 routed top-6 MoE, group-limited
routing, YaRN RoPE [arXiv:2405.04434;
https://huggingface.co/deepseek-ai/DeepSeek-V2/blob/main/config.json].

Published: 60 layers (the first dense), d_model 5120, 128 heads; MLA with
q_lora 1536, kv_lora 512, qk_nope = v_head = 128, qk_rope 64; 160 routed
experts of width 1536, 6 a token, and 2 shared; softmax scores,
``group_limited_greedy`` over n_group 8 keeping topk_group 3,
norm_topk_prob false, routed_scaling_factor 16; YaRN factor 40 over 4096
original positions, beta_fast 32, beta_slow 1, mscale = mscale_all_dim =
0.707; vocabulary 102400, untied.

One departure: RoPE rotates halves of the 64 rope dims, where the published
model rotates interleaved pairs.  On weights drawn at random that is a
fixed permutation of ``wq_b``'s and ``wkv_a``'s rope columns, the same
model class."""
from repro.configs.base import ModelConfig, Yarn

CONFIG = ModelConfig(
    name="deepseek-v2-236b",
    family="moe",
    num_layers=60,
    d_model=5120,
    num_heads=128, num_kv_heads=128, head_dim=128,
    d_ff=12288,                      # dense FFN of the first layer
    vocab_size=102400,
    use_mla=True, kv_lora_rank=512, q_lora_rank=1536, rope_head_dim=64,
    rope_theta=1e4,
    rope_yarn=Yarn(factor=40.0, original_max_pos=4096, beta_fast=32.0,
                   beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    num_experts=160, experts_per_tok=6, num_shared_experts=2,
    moe_d_ff=1536, first_k_dense=1,
    n_group=8, topk_group=3, norm_topk_prob=False,
    routed_scaling_factor=16.0,
    norm="rmsnorm",
    source="https://huggingface.co/deepseek-ai/DeepSeek-V2/blob/main/config.json",
)
