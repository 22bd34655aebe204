"""Whisper-small backbone (enc-dec transformer).

The audio frontend (log-mel + 2x conv) is a STUB per the assignment:
``registry.concrete_batch`` supplies precomputed frame embeddings
(B, S_enc, D).
LayerNorm everywhere, absolute sinusoidal positions (no rope), GELU MLPs.

CPSL split point: the *encoder* stack (the device holds the microphone);
device-side = frames + enc blocks[:v], server-side = enc blocks[v:] + the
full decoder + head.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import common as cm
from repro.models.common import Params


def sinusoid_pos(S: int, d: int) -> jnp.ndarray:
    pos = jnp.arange(S, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    inv = jnp.exp(-math.log(10000.0) * dim / max(d // 2 - 1, 1))
    ang = pos * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _enc_block_init(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 2)
    dt = cm.pdtype(cfg)
    return {
        "pre_norm": cm.norm_init(cfg.d_model, "layernorm", dt),
        "attn": cm.gqa_init(ks[0], cfg),
        "mlp_norm": cm.norm_init(cfg.d_model, "layernorm", dt),
        "mlp": cm.mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg, bias=True),
    }


def _dec_block_init(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 3)
    dt = cm.pdtype(cfg)
    return {
        "pre_norm": cm.norm_init(cfg.d_model, "layernorm", dt),
        "attn": cm.gqa_init(ks[0], cfg),
        "x_norm": cm.norm_init(cfg.d_model, "layernorm", dt),
        "x_attn": cm.gqa_init(ks[1], cfg),
        "mlp_norm": cm.norm_init(cfg.d_model, "layernorm", dt),
        "mlp": cm.mlp_init(ks[2], cfg.d_model, cfg.d_ff, cfg, bias=True),
    }


def enc_block_apply(p: Params, x, cfg: ModelConfig):
    h = cm.apply_norm(p["pre_norm"], x, "layernorm", cfg.norm_eps)
    x = x + cm.gqa_apply(p["attn"], h, cfg, causal=False, use_rope=False)
    h = cm.apply_norm(p["mlp_norm"], x, "layernorm", cfg.norm_eps)
    return x + cm.mlp_apply(p["mlp"], h, cfg)


def dec_block_apply(p: Params, x, memory, cfg: ModelConfig,
                    positions, mem_kv=None, kv_valid_len=None,
                    self_kv=None):
    h = cm.apply_norm(p["pre_norm"], x, "layernorm", cfg.norm_eps)
    x = x + cm.gqa_apply(p["attn"], h, cfg, causal=self_kv is None,
                         use_rope=False, positions=positions, kv=self_kv,
                         kv_valid_len=kv_valid_len)
    h = cm.apply_norm(p["x_norm"], x, "layernorm", cfg.norm_eps)
    if mem_kv is None:
        mem_kv = cm.gqa_project_kv(p["x_attn"], memory, cfg,
                                   jnp.arange(memory.shape[1]),
                                   use_rope=False)
    x = x + cm.gqa_apply(p["x_attn"], h, cfg, causal=False, use_rope=False,
                         positions=positions, kv=mem_kv)
    h = cm.apply_norm(p["mlp_norm"], x, "layernorm", cfg.norm_eps)
    return x + cm.mlp_apply(p["mlp"], h, cfg)


def init(key, cfg: ModelConfig) -> Params:
    n_enc, n_dec = cfg.n_enc_layers, cfg.n_layers - cfg.n_enc_layers
    ks = jax.random.split(key, 4)
    enc_keys = jax.random.split(ks[0], n_enc)
    dec_keys = jax.random.split(ks[1], n_dec)
    return {
        "embed": cm.embed_init(ks[2], cfg),
        "enc_stack": jax.vmap(lambda k: _enc_block_init(k, cfg))(enc_keys),
        "enc_norm": cm.norm_init(cfg.d_model, "layernorm", cm.pdtype(cfg)),
        "dec_stack": jax.vmap(lambda k: _dec_block_init(k, cfg))(dec_keys),
        "dec_norm": cm.norm_init(cfg.d_model, "layernorm", cm.pdtype(cfg)),
    }


def encode(params: Params, frames: jnp.ndarray, cfg: ModelConfig,
           start_layer: int = 0, end_layer: Optional[int] = None):
    """frames: (B, S_enc, D) precomputed embeddings (frontend stub)."""
    x = frames.astype(cm.cdtype(cfg))
    if start_layer == 0:
        x = x + sinusoid_pos(x.shape[1], cfg.d_model).astype(x.dtype)
    n_enc = cfg.n_enc_layers
    end_layer = n_enc if end_layer is None else end_layer

    def body(x, p):
        return enc_block_apply(p, x, cfg), None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    sl = jax.tree.map(lambda t: t[start_layer:end_layer],
                      params["enc_stack"])
    x, _ = lax.scan(body_fn, x, sl)
    if end_layer == n_enc:
        x = cm.apply_norm(params["enc_norm"], x, "layernorm", cfg.norm_eps)
    return x


def decode_hidden(params: Params, tokens: jnp.ndarray, memory: jnp.ndarray,
                  cfg: ModelConfig):
    B, S = tokens.shape
    positions = jnp.arange(S)
    x = cm.embed_apply(params["embed"], tokens, cfg)
    x = x + sinusoid_pos(S, cfg.d_model).astype(x.dtype)

    def body(x, p):
        return dec_block_apply(p, x, memory, cfg, positions), None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    x, _ = lax.scan(body_fn, x, params["dec_stack"])
    return cm.apply_norm(params["dec_norm"], x, "layernorm", cfg.norm_eps)


def decode(params: Params, tokens: jnp.ndarray, memory: jnp.ndarray,
           cfg: ModelConfig):
    x = decode_hidden(params, tokens, memory, cfg)
    return cm.logits_apply(params["embed"], x, cfg)


def forward(params: Params, batch: dict, cfg: ModelConfig):
    memory = encode(params, batch["frames"], cfg)
    return (decode(params, batch["tokens"], memory, cfg),
            jnp.zeros((), jnp.float32))


def loss_fn(params: Params, batch: dict, cfg: ModelConfig) -> jnp.ndarray:
    memory = encode(params, batch["frames"], cfg)
    x = decode_hidden(params, batch["tokens"], memory, cfg)
    head = (params["embed"]["tok"].T if cfg.tie_embeddings
            else params["embed"]["head"])
    return cm.lm_head_loss(head, x, batch["labels"], cfg,
                           batch.get("mask"))


# -- serving ---------------------------------------------------------------

def prefill(params: Params, batch: dict, cfg: ModelConfig,
            cap: Optional[int] = None):
    """Encode frames + prefill decoder self-attn caches with ``tokens``.

    Returns (last logits (B,V), cache). Cross-attn K/V are precomputed once.
    """
    tokens = batch["tokens"]
    B, S = tokens.shape
    cap = cap or S
    memory = encode(params, batch["frames"], cfg)
    positions = jnp.arange(S)
    x = cm.embed_apply(params["embed"], tokens, cfg)
    x = x + sinusoid_pos(S, cfg.d_model).astype(x.dtype)

    def body(x, p):
        h = cm.apply_norm(p["pre_norm"], x, "layernorm", cfg.norm_eps)
        k, v = cm.gqa_project_kv(p["attn"], h, cfg, positions,
                                 use_rope=False)
        kc = jnp.zeros((B, cap) + k.shape[2:], k.dtype)
        kc = lax.dynamic_update_slice_in_dim(kc, k, 0, 1)
        vc = jnp.zeros((B, cap) + v.shape[2:], v.dtype)
        vc = lax.dynamic_update_slice_in_dim(vc, v, 0, 1)
        mem_kv = cm.gqa_project_kv(p["x_attn"], memory, cfg,
                                   jnp.arange(memory.shape[1]),
                                   use_rope=False)
        x = dec_block_apply(p, x, memory, cfg, positions, mem_kv=mem_kv)
        return x, {"k": kc, "v": vc, "mk": mem_kv[0], "mv": mem_kv[1]}

    x, caches = lax.scan(body, x, params["dec_stack"])
    x = cm.apply_norm(params["dec_norm"], x, "layernorm", cfg.norm_eps)
    logits = cm.logits_apply(params["embed"], x[:, -1:, :], cfg)
    return logits[:, 0], caches


def decode_step(params: Params, cache: dict, tokens: jnp.ndarray, pos,
                cfg: ModelConfig):
    """tokens: (B,) -> (logits (B,V), cache)."""
    x = cm.embed_apply(params["embed"], tokens[:, None], cfg)
    # position embedding for the new token
    pe = sinusoid_pos_at(pos, cfg.d_model).astype(x.dtype)
    x = x + pe[None, None, :]
    positions = jnp.full((1,), pos)

    def body(x, inp):
        p, c = inp
        h = cm.apply_norm(p["pre_norm"], x, "layernorm", cfg.norm_eps)
        k_new, v_new = cm.gqa_project_kv(p["attn"], h, cfg, positions,
                                         use_rope=False)
        kc = lax.dynamic_update_slice_in_dim(c["k"], k_new, pos, 1)
        vc = lax.dynamic_update_slice_in_dim(c["v"], v_new, pos, 1)
        x = x + cm.gqa_apply(p["attn"], h, cfg, causal=False,
                             use_rope=False, positions=positions,
                             kv=(kc, vc), kv_valid_len=pos + 1)
        h = cm.apply_norm(p["x_norm"], x, "layernorm", cfg.norm_eps)
        x = x + cm.gqa_apply(p["x_attn"], h, cfg, causal=False,
                             use_rope=False, positions=positions,
                             kv=(c["mk"], c["mv"]))
        h = cm.apply_norm(p["mlp_norm"], x, "layernorm", cfg.norm_eps)
        x = x + cm.mlp_apply(p["mlp"], h, cfg)
        return x, {"k": kc, "v": vc, "mk": c["mk"], "mv": c["mv"]}

    x, new_cache = lax.scan(body, x, (params["dec_stack"], cache))
    x = cm.apply_norm(params["dec_norm"], x, "layernorm", cfg.norm_eps)
    logits = cm.logits_apply(params["embed"], x, cfg)
    return logits[:, 0], new_cache


def sinusoid_pos_at(pos, d: int) -> jnp.ndarray:
    dim = jnp.arange(d // 2, dtype=jnp.float32)
    inv = jnp.exp(-math.log(10000.0) * dim / max(d // 2 - 1, 1))
    ang = pos * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
