"""deepseek-v2-lite-16b [moe]: 27L d_model=2048 16H vocab=102400 — MLA
(kv_lora 512, no query compression, YaRN x40 on the 64 rope dims), layer 0 a
dense SwiGLU 10944 wide, then 26 MoE layers of 64 routed experts (1408 wide,
top-6 softmax gates, not renormalised) + 2 shared [arXiv:2405.04434;
huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json].

Assignment-line discrepancy (recorded in DESIGN.md §4): the inline spec says
"MoE 64e top-6" while the prose says "2 shared+160 routed"; the published
V2-Lite has 64 routed experts — we follow the bracketed spec (64 routed).
"""

from repro.configs.base import ModelConfig, register

CONFIG = register(ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,              # unused under MLA (latent cache)
    d_ff=10944,                 # the dense layer 0 (intermediate_size)
    vocab_size=102400,
    n_routed_experts=64,
    n_shared_experts=2,
    moe_top_k=6,
    d_expert=1408,
    first_layer_dense=True,
    norm_topk_prob=False,
    use_mla=True,
    kv_lora_rank=512,
    rope_head_dim=64,
    nope_head_dim=128,
    v_head_dim=128,
    yarn_factor=40.0,
    yarn_original_max_len=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
))
