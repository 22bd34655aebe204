"""The precisions the plain references run in.

``REF`` is the reference proper: float32 everywhere, every dot at
``Precision.HIGHEST`` (a float32 dot on the TPU otherwise rounds its
operands to bfloat16). The controls are the same references computed one
precision below what a configuration states:

- ``BF16X3`` for float32 at ``highest``: every dot, forward and the two
  of its backward, as the three bfloat16 passes of ``Precision.HIGH``,
  a.b ~ hi(a).hi(b) + hi(a).lo(b) + lo(a).hi(b), written out so that it
  computes the same on any backend.
- ``BF16`` for other float32: parameters, activations, gradients and the
  SGD update all in bfloat16.
- ``FP8`` for a bfloat16 configuration: every dot takes its operands
  rounded to float8 with one absmax scale per tensor (e4m3 forward, e5m2
  for the incoming gradient, the usual fp8 training recipe), forward and
  backward; everything else as ``REF``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def _fake_quant(x, dtype):
    """Round ``x`` to ``dtype`` (a float8 type) under one absmax scale."""
    top = float(jnp.finfo(dtype).max)
    amax = lax.stop_gradient(jnp.max(jnp.abs(x)))
    s = jnp.where(amax > 0, amax / top, 1.0)
    return (x / s).astype(dtype).astype(jnp.float32) * s


def _hi_lo(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _lowered(mode, f, a, b):
    """Bilinear ``f`` (computed at HIGHEST) of ``a`` and ``b`` in ``mode``:
    "x3" three bfloat16 passes, "fp8" / "fp8g" float8 operands (e4m3, or
    e5m2 for the first operand, an incoming gradient)."""
    if mode == "x3":
        (ah, al), (bh, bl) = _hi_lo(a), _hi_lo(b)
        return f(ah, bh) + (f(ah, bl) + f(al, bh))
    first = jnp.float8_e5m2 if mode == "fp8g" else jnp.float8_e4m3fn
    return f(_fake_quant(a, first), _fake_quant(b, jnp.float8_e4m3fn))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _dot(mode, f, a, b):
    return _lowered(mode, f, a, b)


def _dot_fwd(mode, f, a, b):
    return _lowered(mode, f, a, b), (a, b)


def _dot_bwd(mode, f, res, g):
    """Each cotangent is itself a bilinear form, of the output cotangent
    and the other operand: take it in the same precision."""
    a, b = res
    gmode = "fp8g" if mode == "fp8" else mode

    def da(u, v):
        return jax.vjp(lambda x: f(x, v), a)[1](u)[0]

    def db(u, v):
        return jax.vjp(lambda y: f(v, y), b)[1](u)[0]

    return _lowered(gmode, da, g, b), _lowered(gmode, db, g, a)


_dot.defvjp(_dot_fwd, _dot_bwd)


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _conv(pad, x, w, precision=HIGHEST):
    return lax.conv_general_dilated(
        x, w.astype(x.dtype), window_strides=(1, 1), padding=pad,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)


@dataclass(frozen=True)
class Numerics:
    name: str
    dtype: object          # parameters and activations
    mode: str = ""         # how dots are lowered: "", "x3" or "fp8"

    def einsum(self, spec, a, b):
        if self.mode:
            return _dot(self.mode, functools.partial(_einsum, spec),
                        a.astype(jnp.float32), b.astype(jnp.float32))
        if self.dtype == jnp.bfloat16:
            return jnp.einsum(spec, a.astype(jnp.bfloat16),
                              b.astype(jnp.bfloat16))
        return _einsum(spec, a, b)

    def conv(self, x, w, pad):
        """3x3 convolution, NHWC activations and HWIO kernels."""
        if self.mode:
            return _dot(self.mode, functools.partial(_conv, pad),
                        x.astype(jnp.float32), w.astype(jnp.float32))
        if self.dtype == jnp.bfloat16:
            return _conv(pad, x, w, precision=None)
        return _conv(pad, x, w)


REF = Numerics("ref", jnp.float32)
BF16X3 = Numerics("bf16x3", jnp.float32, "x3")
BF16 = Numerics("bf16", jnp.bfloat16)
FP8 = Numerics("fp8", jnp.float32, "fp8")
BY_NAME = {n.name: n for n in (REF, BF16X3, BF16, FP8)}
