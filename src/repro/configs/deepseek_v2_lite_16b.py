"""deepseek-v2-lite-16b — MLA (kv_lora 512, no q-LoRA, YaRN ×40), 1 dense
layer then 26 MoE layers of 2 shared + 64 routed experts, top-6.
[https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite; arXiv:2405.04434]"""
from ..nn.config import MLAConfig, ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b", family="moe", n_layers=27, d_model=2048,
    n_heads=16, n_kv_heads=16, d_head=128, d_ff=10_944, vocab_size=102_400,
    norm_kind="rmsnorm", norm_eps=1e-6, attn_kind="mla",
    rope_theta=10_000.0,
    rope_scaling=YarnConfig(factor=40.0,
                            original_max_position_embeddings=4096,
                            beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                            mscale_all_dim=0.707),
    mla=MLAConfig(kv_lora_rank=512, rope_head_dim=64, nope_head_dim=128,
                  v_head_dim=128),
    moe=MoEConfig(n_experts=64, top_k=6, n_shared=2, d_expert=1408,
                  first_dense_layers=1, norm_topk_prob=False,
                  balance_coef=0.001),
)
