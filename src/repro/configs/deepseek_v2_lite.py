"""deepseek-v2-lite [moe] — latent attention (MLA, no q-LoRA), one dense
layer, then 26 MoE layers of 64 routed experts (top-6, gates not
renormalised) and 2 shared ones; YaRN rotary scaling.
[hf:deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434]"""
from repro.configs.base import ModelConfig, Yarn, reduced

CONFIG = ModelConfig(
    name="deepseek-v2-lite", family="moe",
    source="hf:deepseek-ai/DeepSeek-V2-Lite (config.json); arXiv:2405.04434",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=1408, vocab_size=102400,  # d_ff = per-expert (moe_intermediate_size)
    rope_theta=10_000.0,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
    rope_yarn=Yarn(factor=40.0, original_max_position=4096, beta_fast=32.0,
                   beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    n_experts=64, top_k=6, moe_dropless=True, n_shared_experts=2,
    # The balance loss's weight is not in config.json; the paper gives
    # 0.001 for the expert-level balance loss of DeepSeek-V2-Lite.
    aux_loss_coef=0.001,
    first_dense_layers=1, dense_d_ff=10944,
)


def smoke_config():
    return reduced(CONFIG, n_layers=3, kv_lora_rank=32, qk_nope_head_dim=16,
                   qk_rope_head_dim=8, v_head_dim=16, d_ff=64,
                   dense_d_ff=256)
