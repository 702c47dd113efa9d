"""DeepSeek-V2-Lite 15.7B: MLA without query compression (kv_lora=512), one
leading dense layer, then 26 layers of 64 routed experts top-6 (softmax,
not renormalised) + 2 shared, YaRN rope x40 over 4096 positions.
[arXiv:2405.04434; huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json]"""

from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=192,            # qk_nope + qk_rope
    d_ff=10944,              # the dense layer's FFN
    moe_d_ff=1408,
    vocab_size=102400,
    num_experts=64,
    experts_per_token=6,
    num_shared_experts=2,
    norm_topk=False,
    first_k_dense=1,
    use_mla=True,
    kv_lora_rank=512,
    q_lora_rank=0,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    rope_theta=10000.0,
    yarn_factor=40.0,
    yarn_original_max_pos=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    source="arXiv:2405.04434; huggingface.co/deepseek-ai/DeepSeek-V2-Lite",
)
