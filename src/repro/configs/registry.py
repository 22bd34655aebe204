"""Architecture registry: ``--arch <id>`` lookup, the shape cells each
architecture is sized for, and reduced configs for CPU smoke tests.

The 4 shape cells (assignment):
    train_4k:    seq 4096,   global_batch 256  -> CPSL train_step
    prefill_32k: seq 32768,  global_batch 32   -> prefill_step
    decode_32k:  seq 32768,  global_batch 128  -> serve_step (1 new token)
    long_500k:   seq 524288, global_batch 1    -> serve_step; only for
                 sub-quadratic archs (mamba2, jamba; ``LONG_CTX_ARCHS``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import (chameleon_34b, deepseek_v2_lite_16b, gemma2_2b,
                           jamba_v01_52b, mamba2_2p7b, phi35_moe_42b,
                           qwen2_05b, qwen25_14b, qwen3_32b, whisper_small)
from repro.configs.base import LayerSpec, MLACfg, ModelConfig, MoECfg, SSMCfg

ARCHS = {
    "whisper-small": whisper_small.config,
    "chameleon-34b": chameleon_34b.config,
    "deepseek-v2-lite-16b": deepseek_v2_lite_16b.config,
    "deepseek-v2-lite-16b-ep8": deepseek_v2_lite_16b.config_ep8,
    "phi3.5-moe-42b-a6.6b": phi35_moe_42b.config,
    "mamba2-2.7b": mamba2_2p7b.config,
    "jamba-v0.1-52b": jamba_v01_52b.config,
    "gemma2-2b": gemma2_2b.config,
    "qwen2.5-14b": qwen25_14b.config,
    "qwen3-32b": qwen3_32b.config,
    "qwen2-0.5b": qwen2_05b.config,
}

# archs eligible for the long_500k cell (sub-quadratic sequence mixing)
LONG_CTX_ARCHS = {"mamba2-2.7b", "jamba-v0.1-52b"}


def get(name: str) -> ModelConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(ARCHS)}")
    return ARCHS[name]()


def list_archs():
    return sorted(ARCHS)


def cells(arch: str):
    """Shape cells applicable to this arch."""
    out = ["train_4k", "prefill_32k", "decode_32k"]
    if arch in LONG_CTX_ARCHS:
        out.append("long_500k")
    return out


# --------------------------------------------------------------------------
# reduced configs for CPU smoke tests
# --------------------------------------------------------------------------

def reduce_for_smoke(cfg: ModelConfig) -> ModelConfig:
    """Same family/features, tiny dims: runs a forward + train step on CPU."""
    kw = dict(
        d_model=64,
        n_heads=4 if cfg.n_heads else 0,
        n_kv_heads=min(cfg.n_kv_heads, 2) if cfg.n_kv_heads else 0,
        head_dim=16 if cfg.head_dim else 0,
        d_ff=128 if cfg.d_ff else 0,
        vocab_size=211,
        n_layers=len(cfg.prologue) + 2 * len(cfg.pattern),
        remat=False,
        q_chunk=8, kv_chunk=8,
    )
    if cfg.moe is not None:
        # a held share stays a share: 2 of 8 experts here
        share = cfg.moe.held < cfg.moe.n_experts
        kw["moe"] = dataclasses.replace(cfg.moe, n_experts=8 if share else 4,
                                        top_k=2, d_ff_expert=32,
                                        n_held=2 if share else 0)
    if cfg.mla is not None:
        kw["mla"] = MLACfg(kv_lora_rank=32, q_lora_rank=0,
                           qk_nope_head_dim=16, qk_rope_head_dim=8,
                           v_head_dim=16)
    if cfg.ssm is not None:
        kw["ssm"] = dataclasses.replace(cfg.ssm, d_state=16, headdim=16,
                                        chunk_size=8)
    if cfg.encdec:
        kw["n_enc_layers"] = 2
        kw["n_layers"] = 4
        kw["enc_seq"] = 24
    return cfg.replace(**kw)


def concrete_batch(key, cfg: ModelConfig, *, batch: int, seq: int) -> Dict:
    """Small concrete batch for smoke tests."""
    ks = jax.random.split(key, 3)
    out = {
        "tokens": jax.random.randint(ks[0], (batch, seq), 0, cfg.vocab_size),
        "labels": jax.random.randint(ks[1], (batch, seq), 0, cfg.vocab_size),
    }
    if cfg.encdec:
        out["frames"] = jax.random.normal(
            ks[2], (batch, cfg.enc_seq, cfg.d_model), jnp.float32
        ).astype(jnp.dtype(cfg.dtype))
    return out
