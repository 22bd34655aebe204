"""Blocked causal flash attention in jnp, at MLA's own widths.

The CPU's path, and wherever the TPU kernel does not apply (a length no
kernel block divides). Layout (B, S, H, D):
q and k carry the query-key width, v its own (DeepSeek-V2: 192 and 128).
Semantics ``softmax(scale q k^T) v``, the scale applied to the float32
scores, causal: key j is masked from query i when j > i.

Only the (q block, kv block) pairs that hold an unmasked entry are
computed, in the forward and in both backward passes: the dq pass
accumulates one q block over its kv blocks, the dkv pass one kv block
over its q blocks, so no pass rewrites a full-length array. Dots take
their operands' dtype with float32 accumulation (the probabilities enter
the value dots in v's dtype); softmax statistics are float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30
F32 = jnp.float32


def kv_blocks_for(i, bq: int, bkv: int, nk: int):
    """How many kv blocks q block ``i`` reads: those with a key at or
    before its last query. ``i`` may be traced."""
    return jnp.minimum(nk, ((i + 1) * bq - 1) // bkv + 1)


def first_q_block(j, bq: int, bkv: int):
    """The first q block that reads kv block ``j``: the one holding the
    query at the block's first key."""
    return (j * bkv) // bq


def _probs(qi, kj, i, j, scale, bq, bkv, lse=None):
    """Scores of one tile (B, H, bq, bkv), float32, masked; with ``lse``
    the normalised probabilities."""
    s = jnp.einsum("bqhd,bkhd->bhqk", qi, kj,
                   preferred_element_type=F32) * scale
    qpos = i * bq + jnp.arange(bq)
    kpos = j * bkv + jnp.arange(bkv)
    s = jnp.where(qpos[:, None] >= kpos[None, :], s, NEG_INF)
    if lse is None:
        return s
    return jnp.exp(s - lse[..., None])


def _fwd_impl(q, k, v, scale, bq, bkv):
    B, S, H, _ = q.shape
    Dv = v.shape[-1]
    nq, nk = S // bq, S // bkv

    def q_step(_, i):
        qi = lax.dynamic_slice_in_dim(q, i * bq, bq, 1)

        def kv_step(j, carry):
            m, l, acc = carry
            kj = lax.dynamic_slice_in_dim(k, j * bkv, bkv, 1)
            vj = lax.dynamic_slice_in_dim(v, j * bkv, bkv, 1)
            s = _probs(qi, kj, i, j, scale, bq, bkv)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), vj,
                            preferred_element_type=F32)
            acc = acc * jnp.swapaxes(alpha, 1, 2)[..., None] + pv
            return m_new, l * alpha + p.sum(-1), acc

        init = (jnp.full((B, H, bq), NEG_INF, F32), jnp.zeros((B, H, bq), F32),
                jnp.zeros((B, bq, H, Dv), F32))
        m, l, acc = lax.fori_loop(0, kv_blocks_for(i, bq, bkv, nk), kv_step,
                                  init)
        o = acc / jnp.swapaxes(l, 1, 2)[..., None]
        return None, (o.astype(q.dtype), m + jnp.log(l))

    _, (o, lse) = lax.scan(q_step, None, jnp.arange(nq))
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, H, Dv)
    lse = jnp.moveaxis(lse, 0, 2).reshape(B, H, S)
    return o, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash(q, k, v, scale: float, bq: int, bkv: int):
    """q, k (B, S, H, D); v (B, S, H, Dv) -> (B, S, H, Dv) in q's dtype.
    ``bq`` and ``bkv`` divide S."""
    return _fwd_impl(q, k, v, scale, bq, bkv)[0]


def _fwd_rule(q, k, v, scale, bq, bkv):
    o, lse = _fwd_impl(q, k, v, scale, bq, bkv)
    return o, (q, k, v, o, lse)


def _bwd_rule(scale, bq, bkv, res, do):
    q, k, v, o, lse = res
    S = q.shape[1]
    nq, nk = S // bq, S // bkv
    delta = jnp.swapaxes((o.astype(F32) * do.astype(F32)).sum(-1), 1, 2)
    do = do.astype(v.dtype)

    def q_rows(i):
        return (lax.dynamic_slice_in_dim(q, i * bq, bq, 1),
                lax.dynamic_slice_in_dim(do, i * bq, bq, 1),
                lax.dynamic_slice_in_dim(lse, i * bq, bq, 2),
                lax.dynamic_slice_in_dim(delta, i * bq, bq, 2))

    def kv_rows(j):
        return (lax.dynamic_slice_in_dim(k, j * bkv, bkv, 1),
                lax.dynamic_slice_in_dim(v, j * bkv, bkv, 1))

    def tile(i, j, qi, doi, lse_i, delta_i, kj, vj):
        p = _probs(qi, kj, i, j, scale, bq, bkv, lse_i)
        dp = jnp.einsum("bqhd,bkhd->bhqk", doi, vj, preferred_element_type=F32)
        return p, p * (dp - delta_i[..., None])

    def dq_step(_, i):
        qi, doi, lse_i, delta_i = q_rows(i)

        def kv_step(j, dq):
            kj, vj = kv_rows(j)
            _, ds = tile(i, j, qi, doi, lse_i, delta_i, kj, vj)
            return dq + jnp.einsum("bhqk,bkhd->bqhd", ds.astype(k.dtype), kj,
                                   preferred_element_type=F32)

        dq = lax.fori_loop(0, kv_blocks_for(i, bq, bkv, nk), kv_step,
                           jnp.zeros(qi.shape, F32))
        return None, (dq * scale).astype(q.dtype)

    def dkv_step(_, j):
        kj, vj = kv_rows(j)

        def q_step(i, carry):
            dk, dv = carry
            qi, doi, lse_i, delta_i = q_rows(i)
            p, ds = tile(i, j, qi, doi, lse_i, delta_i, kj, vj)
            dv = dv + jnp.einsum("bhqk,bqhd->bkhd", p.astype(do.dtype), doi,
                                 preferred_element_type=F32)
            dk = dk + jnp.einsum("bhqk,bqhd->bkhd", ds.astype(q.dtype), qi,
                                 preferred_element_type=F32)
            return dk, dv

        dk, dv = lax.fori_loop(first_q_block(j, bq, bkv), nq, q_step,
                               (jnp.zeros(kj.shape, F32),
                                jnp.zeros(vj.shape, F32)))
        return None, ((dk * scale).astype(k.dtype), dv.astype(v.dtype))

    def unblock(x):
        return jnp.moveaxis(x, 0, 1).reshape(x.shape[1], S, *x.shape[3:])

    _, dq = lax.scan(dq_step, None, jnp.arange(nq))
    _, (dk, dv) = lax.scan(dkv_step, None, jnp.arange(nk))
    return unblock(dq), unblock(dk), unblock(dv)


flash.defvjp(_fwd_rule, _bwd_rule)
