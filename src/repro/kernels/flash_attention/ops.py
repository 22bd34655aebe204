"""Jit-ready wrapper: grouped-layout flash attention with custom VJP.

Forward = Pallas kernel (interpret mode on the CPU); backward re-computes
through the chunked-jnp reference (identical math, flash-style memory) —
the standard recompute-in-backward flash pattern without hand-writing the
dq/dk/dv kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import kernels
from repro.kernels.flash_attention.kernel import flash_attention_flat


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal=True, window=0, softcap=0.0,
                    q_offset=0):
    """Grouped layout: q (B, Sq, G, R, D); k, v (B, Skv, G, D)."""
    B, Sq, G, R, D = q.shape
    Skv = k.shape[1]
    qf = q.transpose(0, 2, 3, 1, 4).reshape(B * G * R, Sq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * G, Skv, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * G, Skv, D)
    of = flash_attention_flat(qf, kf, vf, causal=causal, window=window,
                              softcap=softcap, q_offset=q_offset,
                              kv_repeat=R, interpret=kernels.interpret())
    return of.reshape(B, G, R, Sq, D).transpose(0, 3, 1, 2, 4)


def _fwd(q, k, v, causal, window, softcap, q_offset):
    out = flash_attention(q, k, v, causal, window, softcap, q_offset)
    return out, (q, k, v)


def _bwd(causal, window, softcap, q_offset, res, g):
    # chunked_attention carries the flash backward (custom_vjp) — memory
    # stays O(chunk^2), matching what the dq/dk/dv kernels would do.
    from repro.models.common import chunked_attention
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: chunked_attention(q_, k_, v_, causal, window,
                                             softcap, q_offset), q, k, v)
    return vjp(g)


flash_attention.defvjp(_fwd, _bwd)
