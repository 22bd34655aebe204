"""deepseek-v2-lite-16b [moe]: 27L, d=2048, 16H, MLA (kv_lora=512, rope 64,
nope 128, v 128, YaRN x40 over 4096 positions), vocab=102400; MoE: 2
shared + 64 routed top-6, d_ff_expert=1408, softmax gate with raw top-6
weights, sequence-wise balance loss; first layer dense (d_ff=10944).
[arXiv:2405.04434; huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json]

``config_ep8`` is one chip's share of the model with its MoE layers
divided over 8 chips by experts: 8 of the 64 routed experts held, the
router still scoring all 64, an eighth of the vocabulary, and the dense
layer plus 4 MoE layers (the layers left out lie on further pipeline
stages). Every width is as published.
"""
import dataclasses

from repro.configs.base import (LayerSpec, MLACfg, MoECfg, ModelConfig,
                                YaRNCfg)


def config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite-16b", family="moe",
        d_model=2048, n_layers=27, n_heads=16, n_kv_heads=16,
        d_ff=10944, vocab_size=102400,
        prologue=(LayerSpec("attn", "dense"),),       # first_k_dense = 1
        pattern=(LayerSpec("attn", "moe"),),          # 26 MoE layers
        attn_kind="mla",
        mla=MLACfg(kv_lora_rank=512, q_lora_rank=0, qk_nope_head_dim=128,
                   qk_rope_head_dim=64, v_head_dim=128),
        moe=MoECfg(n_experts=64, top_k=6, d_ff_expert=1408,
                   n_shared_experts=2, norm_topk_prob=False,
                   balance_loss="seq",
                   router_aux_weight=0.001),
        rope_scaling=YaRNCfg(factor=40.0, original_max_position=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
        tie_embeddings=False, rope_theta=1e4,
    )


def config_ep8() -> ModelConfig:
    full = config()
    return full.replace(
        name="deepseek-v2-lite-16b-ep8", n_layers=5,
        vocab_size=full.vocab_size // 8,
        moe=dataclasses.replace(full.moe, n_held=8))
