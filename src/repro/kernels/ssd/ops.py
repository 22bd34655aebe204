"""Jit-ready SSD wrapper in the model's (B, S, H, P) layout, with custom
VJP (backward recomputes through the chunked-jnp reference)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import kernels
from repro.kernels.ssd.kernel import ssd_flat


def _to_flat(x, dt, A, Bm, Cm):
    B_, S, H, P = x.shape
    N = Bm.shape[-1]
    xf = x.transpose(0, 2, 1, 3).reshape(B_ * H, S, P)
    dtf = dt.transpose(0, 2, 1).reshape(B_ * H, S)
    Af = jnp.broadcast_to(A[None, :], (B_, H)).reshape(B_ * H)
    Bf = Bm.transpose(0, 2, 1, 3).reshape(B_ * H, S, N)
    Cf = Cm.transpose(0, 2, 1, 3).reshape(B_ * H, S, N)
    return xf, dtf, Af, Bf, Cf


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def ssd(x, dt, A, Bm, Cm, chunk: int = 128, h0=None):
    """Model layout: x (B,S,H,P), dt (B,S,H), A (H,), Bm/Cm (B,S,H,N).
    Returns (y, final state (B,H,N,P)). h0 must be None for the kernel
    path (prefill-from-scratch); decode handoff uses the jnp path."""
    assert h0 is None, "kernel path starts from zero state"
    B_, S, H, P = x.shape
    xf, dtf, Af, Bf, Cf = _to_flat(x, dt, A, Bm, Cm)
    y, hT = ssd_flat(xf, dtf, Af, Bf, Cf, chunk=chunk,
                     interpret=kernels.interpret())
    y = y.reshape(B_, H, S, P).transpose(0, 2, 1, 3)
    hT = hT.reshape(B_, H, *hT.shape[1:])
    return y, hT


def _fwd(x, dt, A, Bm, Cm, chunk, h0=None):
    out = ssd(x, dt, A, Bm, Cm, chunk, h0)
    return out, (x, dt, A, Bm, Cm)


def _bwd(chunk, res, g):
    from repro.models.mamba2 import ssd_chunked
    x, dt, A, Bm, Cm = res
    _, vjp = jax.vjp(
        lambda x_, dt_, A_, B_, C_: ssd_chunked(x_, dt_, A_, B_, C_,
                                                chunk=chunk), x, dt, A, Bm,
        Cm)
    grads = vjp(g)
    return grads + (None,)


ssd.defvjp(_fwd, _bwd)
