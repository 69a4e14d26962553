"""deepseek-v2-lite — MLA attention without a query low-rank projection,
YaRN rope, 64 routed experts top-6 (softmax, no renormalisation) + 2
shared, layer 0 dense.
[arXiv:2405.04434; https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json]"""
from repro.configs.base import MLACfg, ModelConfig, MoECfg, YaRNCfg

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,           # MLA: latent cache, kv heads = q heads post-expand
    head_dim=192,            # qk_nope (128) + qk_rope (64)
    d_ff=1408,               # expert hidden dim
    vocab_size=102400,
    tie_embeddings=False,
    rope_theta=10000.0,
    rope_scaling=YaRNCfg(factor=40.0, original_max_position_embeddings=4096,
                         beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                         mscale_all_dim=0.707),
    mla=MLACfg(kv_lora_rank=512, q_lora_rank=None,
               qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128),
    moe=MoECfg(n_experts=64, top_k=6, n_shared=2, d_ff_expert=1408,
               first_dense_layers=1, d_ff_dense=10944,
               norm_topk_prob=False, routed_scaling_factor=1.0),
    source="https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite",
)
