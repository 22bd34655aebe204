"""Unified model API dispatching by config family.

    init(key, cfg)                 -> params
    loss_fn(params, batch, cfg)    -> scalar
    forward(params, batch, cfg)    -> (logits, aux)
    prefill(params, batch, cfg)    -> (last logits, cache)
    decode_step(params, cache, tokens, pos, cfg) -> (logits, cache)
"""
from __future__ import annotations

from repro.configs.base import ModelConfig
from repro.models import transformer as tfm
from repro.models import whisper as whp


def is_encdec(cfg: ModelConfig) -> bool:
    return cfg.encdec


def init(key, cfg: ModelConfig):
    return whp.init(key, cfg) if cfg.encdec else tfm.init(key, cfg)


def loss_fn(params, batch: dict, cfg: ModelConfig):
    if cfg.encdec:
        return whp.loss_fn(params, batch, cfg)
    return tfm.loss_fn(params, batch, cfg)


def forward(params, batch: dict, cfg: ModelConfig):
    if cfg.encdec:
        return whp.forward(params, batch, cfg)
    return tfm.forward(params, batch["tokens"], cfg)


def prefill(params, batch: dict, cfg: ModelConfig, cap=None):
    if cfg.encdec:
        return whp.prefill(params, batch, cfg, cap=cap)
    return tfm.prefill(params, batch["tokens"], cfg, cap=cap)


def decode_step(params, cache, tokens, pos, cfg: ModelConfig):
    if cfg.encdec:
        return whp.decode_step(params, cache, tokens, pos, cfg)
    return tfm.decode_step(params, cache, tokens, pos, cfg)
