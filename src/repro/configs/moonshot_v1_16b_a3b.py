"""Moonlight-16B-A3B (``model_type`` deepseek_v3) — 27 layers at d_model
2048: the first a dense SwiGLU MLP of 11264, the other 26 MoE layers of 64
routed experts (SwiGLU 1408) and 2 shared experts, routed by sigmoid scores
through a correction bias (``noaux_tc``, one group) to the top 6, weights
normalised and scaled by 2.446; multi-head latent attention with 16 heads,
no query LoRA, KV latent 512, rope 64 + nope 128, values 128, rope theta
50000; RMSNorm eps 1e-5; untied vocabulary 163840.
[hf:moonshotai/Moonlight-16B-A3B config.json]"""
import dataclasses

from repro.configs.base import MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b", family="moe",
    num_layers=27, d_model=2048, num_heads=16, num_kv_heads=16,
    d_ff=11264, vocab_size=163840, first_k_dense=1,
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(num_experts=64, top_k=6, d_ff_expert=1408,
                  num_shared_experts=2, scoring="sigmoid",
                  routed_scale=2.446, grouped=True),
    rope_theta=50_000.0, norm_eps=1e-5,
    citation="hf:moonshotai/Moonlight-16B-A3B",
)


def smoke_config() -> ModelConfig:
    """One dense and one MLA/MoE layer."""
    return dataclasses.replace(
        CONFIG, name="moonlight-smoke", num_layers=2, d_model=128,
        num_heads=4, num_kv_heads=4, d_ff=256, vocab_size=256,
        mla=MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16,
                      qk_rope_head_dim=8, v_head_dim=16),
        moe=dataclasses.replace(CONFIG.moe, num_experts=4, top_k=2,
                                d_ff_expert=64, num_shared_experts=1))
