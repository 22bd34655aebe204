"""Batched serving engine: prefill + decode with KV/SSM caches.

``serve_step`` (one token for the whole batch) is the decode unit.
``generate`` drives greedy/temperature sampling over a
fixed batch of requests (static shapes — continuous batching would swap
finished rows; here rows finishing early keep decoding into padding, which
is the shape-stable TPU-friendly variant).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import api


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, cap: int = 2048):
        self.cfg, self.params, self.cap = cfg, params, cap
        self._prefill = jax.jit(functools.partial(api.prefill, cfg=cfg,
                                                  cap=cap))
        self._step = jax.jit(functools.partial(api.decode_step, cfg=cfg))

    def prefill(self, batch):
        return self._prefill(self.params, batch)

    def decode(self, cache, tokens, pos):
        return self._step(self.params, cache, tokens, pos)

    def generate(self, batch, steps: int, temperature: float = 0.0,
                 key=None):
        """batch: {"tokens": (B, S_prompt)} (+frames for enc-dec).
        Returns (B, steps) generated tokens."""
        logits, cache = self.prefill(batch)
        S = batch["tokens"].shape[1]
        outs = []
        tok = self._sample(logits, temperature, key, 0)
        outs.append(tok)
        for i in range(steps - 1):
            logits, cache = self.decode(cache, tok, S + i)
            tok = self._sample(logits, temperature, key, i + 1)
            outs.append(tok)
        return jnp.stack(outs, axis=1)

    @staticmethod
    def _sample(logits, temperature, key, i):
        if temperature <= 0 or key is None:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        k = jax.random.fold_in(key, i)
        return jax.random.categorical(k, logits / temperature).astype(
            jnp.int32)
