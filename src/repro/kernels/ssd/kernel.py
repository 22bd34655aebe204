"""Mamba-2 SSD Pallas TPU kernel.

Chunked state-space scan: grid = (BH, n_chunks) with the chunk axis
innermost — TPU grids iterate sequentially, so the inter-chunk SSM state
lives in a VMEM scratch buffer carried across chunk iterations (the same
role the flash kernel's (m, l, acc) scratch plays).

Per chunk (length Q):
  intra-chunk: (C B^T ⊙ decay-tril) (dt x)   — two (Q,Q)x(Q,{N,P}) MXU
               matmuls; Q defaults to 128 for full systolic tiles,
  inter-chunk: y += exp(cum) * (C h_prev);  h = exp(cum_Q) h_prev + B^T(dt x)

Layout is flat per-head: x (BH, S, P), dt (BH, S, 1), A (BH, 1, 1),
B/C (BH, S, N) — dt and A carry a trailing unit lane dim so that every
block's last two dims are (8k, full) or (full, full), as Mosaic requires.
The (N, P) state tile (128x64 for mamba2-2.7b) stays resident in VMEM for
the whole sequence — the core TPU adaptation vs. the CUDA SSD kernel, which
re-materializes state through shared memory per block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, hT_ref, h_sc, *,
                Q: int, n_chunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_sc[...] = jnp.zeros_like(h_sc)

    x = x_ref[0].astype(jnp.float32)           # (Q, P)
    dt = dt_ref[0].astype(jnp.float32)         # (Q, 1) column
    A = a_ref[0].astype(jnp.float32)           # (1, 1)
    B = b_ref[0].astype(jnp.float32)           # (Q, N)
    C = c_ref[0].astype(jnp.float32)           # (Q, N)

    # Mosaic has no cumsum and no (Q, 1) <-> (1, Q) relayout, so the
    # inclusive prefix sum and the row views come from masked reductions
    # over (Q, Q) tiles (exact f32 sums of the same terms).
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    dA = dt * A                                # (Q, 1) <= 0
    dA_sq = jnp.broadcast_to(dA, (Q, Q))       # [i, j] = dA[i]
    cum_row = jnp.sum(jnp.where(ii <= jj, dA_sq, 0.0), axis=0,
                      keepdims=True)           # (1, Q) inclusive cumsum
    cum = jnp.sum(jnp.where(ii == jj, jnp.broadcast_to(cum_row, (Q, Q)),
                            0.0), axis=1, keepdims=True)      # (Q, 1)
    dt_row = jnp.sum(jnp.where(ii == jj, jnp.broadcast_to(dt, (Q, Q)),
                               0.0), axis=0, keepdims=True)   # (1, Q)
    total = jnp.sum(dA, axis=0, keepdims=True)                # (1, 1)
    # intra-chunk
    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    decay = jnp.where(ii >= jj, jnp.exp(cum - cum_row), 0.0)
    M = scores * decay * dt_row
    y = jax.lax.dot_general(M, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # inter-chunk: contribution of the carried state
    h = h_sc[...]                              # (N, P)
    Ch = jax.lax.dot_general(C, h, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    y = y + jnp.exp(cum) * Ch
    # state update
    Bw = B * (jnp.exp(total - cum) * dt)       # (Q, N)
    dh = jax.lax.dot_general(Bw, x, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    h_sc[...] = jnp.exp(total) * h + dh
    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        hT_ref[0] = h_sc[...]


def ssd_flat(x, dt, A, Bm, Cm, *, chunk: int = 128,
             interpret: bool = False):
    """x: (BH, S, P); dt: (BH, S); A: (BH,); Bm, Cm: (BH, S, N).
    Returns (y (BH, S, P), hT (BH, N, P))."""
    BH, S, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    while S % Q:
        Q //= 2
    nc = S // Q
    grid = (BH, nc)
    kernel = functools.partial(_ssd_kernel, Q=Q, n_chunks=nc)
    y, hT = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Q, P), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, Q, 1), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, ci: (b, 0, 0)),
            pl.BlockSpec((1, Q, N), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, Q, N), lambda b, ci: (b, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, P), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, N, P), lambda b, ci: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, P), x.dtype),
            jax.ShapeDtypeStruct((BH, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(x, dt[:, :, None], A[:, None, None], Bm, Cm)
    return y, hT
