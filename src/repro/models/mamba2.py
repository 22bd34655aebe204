"""Mamba-2 (state-space duality) block in pure JAX.

SSD semantics (Dao & Gu 2024): per head h with state size N, head dim P:
    h_t = exp(dt_t * A) * h_{t-1} + dt_t * x_t B_t^T
    y_t = C_t h_t + D * x_t
Three implementations:
  - ``scan``:     exact sequential recurrence (oracle, O(S) steps)
  - ``chunked``:  block decomposition (intra-chunk quadratic + inter-chunk
                  state passing) — the math the Pallas kernel implements
  - ``pallas``:   TPU kernel (kernels/ssd), validated in interpret mode
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig, SSMCfg
from repro.models.common import (Params, dense, dense_init, norm_init,
                                 apply_norm, _normal, pdtype, cdtype)


# --------------------------------------------------------------------------
# SSD cores. x:(B,S,H,P) dt:(B,S,H) A:(H,) Bm,Cm:(B,S,H,N)  (groups already
# broadcast to heads). Returns y:(B,S,H,P) and final state (B,H,N,P).
# --------------------------------------------------------------------------

def ssd_scan(x, dt, A, Bm, Cm, h0=None):
    B_, S, H, P = x.shape
    N = Bm.shape[-1]
    h0 = h0 if h0 is not None else jnp.zeros((B_, H, N, P), jnp.float32)

    def step(h, inp):
        x_t, dt_t, B_t, C_t = inp
        a = jnp.exp(dt_t.astype(jnp.float32) * A)              # (B,H)
        u = jnp.einsum("bhn,bhp,bh->bhnp", B_t.astype(jnp.float32),
                       x_t.astype(jnp.float32), dt_t.astype(jnp.float32))
        h = a[..., None, None] * h + u
        y = jnp.einsum("bhn,bhnp->bhp", C_t.astype(jnp.float32), h)
        return h, y

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, Bm, Cm))
    h, ys = lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), h


def ssd_chunked(x, dt, A, Bm, Cm, h0=None, chunk: int = 256):
    """Block-decomposed SSD as a single rematted scan over chunks.

    The per-chunk (Q,Q) decay/score tiles are the SSD analogue of
    attention probabilities: letting AD stash them for every chunk costs
    O(S*Q) per layer (tens of GB at production shapes). The chunk body is
    jax.checkpoint-ed, so the backward recomputes each tile from the
    chunk inputs + carried state — the same residual policy as the
    flash-attention backward and the Pallas kernel.
    """
    B_, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = chunk
    while S % Q:
        Q //= 2
    nc = S // Q
    f32 = jnp.float32
    mask = jnp.tril(jnp.ones((Q, Q), bool))

    def body(h, inp):
        xc, dtc, Bc, Cc = inp               # (B,Q,H,P), (B,Q,H), (B,Q,H,N)
        xc = xc.astype(f32)
        dtc = dtc.astype(f32)
        Bc = Bc.astype(f32)
        Cc = Cc.astype(f32)
        dA = dtc * A                        # (B,Q,H) <= 0
        cum = jnp.cumsum(dA, axis=1)        # inclusive
        # intra-chunk quadratic term
        scores = jnp.einsum("bqhd,bkhd->bhqk", Cc, Bc)
        ci = jnp.moveaxis(cum, 2, 1)        # (B,H,Q)
        decay = jnp.exp(ci[..., :, None] - ci[..., None, :])
        decay = jnp.where(mask, decay, 0.0)
        M = scores * decay * jnp.moveaxis(dtc, 2, 1)[..., None, :]
        y = jnp.einsum("bhqk,bkhp->bqhp", M, xc)
        # carried-state contribution
        y = y + jnp.einsum("bqhd,bhdp,bqh->bqhp", Cc, h, jnp.exp(cum))
        # state update
        sdecay = jnp.exp(cum[:, -1:, :] - cum) * dtc
        Sc = jnp.einsum("bqhd,bqh,bqhp->bhdp", Bc, sdecay, xc)
        h_new = jnp.exp(cum[:, -1, :])[..., None, None] * h + Sc
        return h_new, y.astype(x.dtype)

    h0 = h0 if h0 is not None else jnp.zeros((B_, H, N, P), f32)
    chunks = (jnp.moveaxis(x.reshape(B_, nc, Q, H, P), 1, 0),
              jnp.moveaxis(dt.reshape(B_, nc, Q, H), 1, 0),
              jnp.moveaxis(Bm.reshape(B_, nc, Q, H, N), 1, 0),
              jnp.moveaxis(Cm.reshape(B_, nc, Q, H, N), 1, 0))
    hT, ys = lax.scan(jax.checkpoint(body), h0, chunks)
    y = jnp.moveaxis(ys, 0, 1).reshape(B_, S, H, P)
    return y, hT


def ssd_decode_step(h, x, dt, A, Bm, Cm):
    """One-token recurrence. x:(B,H,P) dt:(B,H) Bm,Cm:(B,H,N) h:(B,H,N,P)."""
    a = jnp.exp(dt.astype(jnp.float32) * A)
    u = jnp.einsum("bhn,bhp,bh->bhnp", Bm.astype(jnp.float32),
                   x.astype(jnp.float32), dt.astype(jnp.float32))
    h = a[..., None, None] * h + u
    y = jnp.einsum("bhn,bhnp->bhp", Cm.astype(jnp.float32), h)
    return y.astype(x.dtype), h


def ssd(x, dt, A, Bm, Cm, *, impl: str, chunk: int = 256, h0=None):
    if impl == "scan":
        return ssd_scan(x, dt, A, Bm, Cm, h0)
    if impl == "chunked":
        return ssd_chunked(x, dt, A, Bm, Cm, h0, chunk=chunk)
    if impl == "pallas":
        from repro.kernels.ssd import ops as ssd_ops
        return ssd_ops.ssd(x, dt, A, Bm, Cm, chunk=chunk, h0=h0)
    raise ValueError(impl)


# --------------------------------------------------------------------------
# causal depthwise conv1d
# --------------------------------------------------------------------------

def causal_conv(x, w, b):
    """x: (B,S,C), w: (K,C), b: (C,) — causal depthwise conv."""
    K = w.shape[0]
    S = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    y = sum(xp[:, k:k + S, :] * w[k].astype(x.dtype) for k in range(K))
    return y + b.astype(x.dtype)


def causal_conv_step(state, x_new, w, b):
    """state: (B,K-1,C), x_new: (B,C) -> (y (B,C), new state)."""
    window = jnp.concatenate([state, x_new[:, None, :]], axis=1)   # (B,K,C)
    y = jnp.einsum("bkc,kc->bc", window.astype(jnp.float32),
                   w.astype(jnp.float32)) + b
    return y.astype(x_new.dtype), window[:, 1:, :]


# --------------------------------------------------------------------------
# Mamba-2 block
# --------------------------------------------------------------------------

def mamba_dims(cfg: ModelConfig):
    s: SSMCfg = cfg.ssm
    d_inner = s.expand * cfg.d_model
    H = d_inner // s.headdim
    conv_dim = d_inner + 2 * s.ngroups * s.d_state
    return d_inner, H, conv_dim


def mamba_init(key, cfg: ModelConfig) -> Params:
    s: SSMCfg = cfg.ssm
    d = cfg.d_model
    d_inner, H, conv_dim = mamba_dims(cfg)
    dt = pdtype(cfg)
    ks = jax.random.split(key, 6)
    # packed in_proj: [z, x, B, C, dt]
    d_in_proj = 2 * d_inner + 2 * s.ngroups * s.d_state + H
    # dt bias: inverse softplus of uniform [dt_min, dt_max]
    u = jax.random.uniform(ks[3], (H,), jnp.float32)
    dt0 = jnp.exp(u * (math.log(s.dt_max) - math.log(s.dt_min))
                  + math.log(s.dt_min))
    dt_bias = dt0 + jnp.log(-jnp.expm1(-dt0))                  # inv softplus
    A = jax.random.uniform(ks[4], (H,), jnp.float32, 1.0, 16.0)
    return {
        "in_proj": dense_init(ks[0], d, d_in_proj, dtype=dt),
        "conv_w": _normal(ks[1], (s.d_conv, conv_dim),
                          1.0 / math.sqrt(s.d_conv * conv_dim), dt),
        "conv_b": jnp.zeros((conv_dim,), dt),
        "dt_bias": dt_bias.astype(dt),
        "A_log": jnp.log(A).astype(dt),
        "D": jnp.ones((H,), dt),
        "norm": norm_init(d_inner, "rmsnorm", dt),
        "out_proj": dense_init(ks[2], d_inner, d, dtype=dt,
                               scale=1.0 / math.sqrt(d_inner)),
    }


def _split_proj(zxbcdt, cfg: ModelConfig):
    s: SSMCfg = cfg.ssm
    d_inner, H, _ = mamba_dims(cfg)
    gn = s.ngroups * s.d_state
    z, xin, B_, C_, dt = jnp.split(
        zxbcdt, [d_inner, 2 * d_inner, 2 * d_inner + gn, 2 * d_inner + 2 * gn],
        axis=-1)
    return z, xin, B_, C_, dt


def _broadcast_groups(t, cfg: ModelConfig):
    """(B,S,G*N) -> (B,S,H,N) broadcasting groups over heads."""
    s: SSMCfg = cfg.ssm
    _, H, _ = mamba_dims(cfg)
    B_, S = t.shape[:2]
    t = t.reshape(B_, S, s.ngroups, s.d_state)
    R = H // s.ngroups
    return jnp.broadcast_to(t[:, :, :, None, :],
                            (B_, S, s.ngroups, R, s.d_state)
                            ).reshape(B_, S, H, s.d_state)


def mamba_apply(p: Params, x: jnp.ndarray, cfg: ModelConfig,
                h0=None, conv0=None, return_state: bool = False):
    """Full-sequence mamba2 mixer. x: (B,S,D)."""
    s: SSMCfg = cfg.ssm
    B_, S, _ = x.shape
    d_inner, H, conv_dim = mamba_dims(cfg)
    zxbcdt = dense(p["in_proj"], x)
    z, xin, B_r, C_r, dtr = _split_proj(zxbcdt, cfg)

    xbc = jnp.concatenate([xin, B_r, C_r], axis=-1)
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"]))
    xin, B_r, C_r = jnp.split(xbc, [d_inner, d_inner + s.ngroups * s.d_state],
                              axis=-1)
    xh = xin.reshape(B_, S, H, s.headdim)
    Bh = _broadcast_groups(B_r, cfg)
    Ch = _broadcast_groups(C_r, cfg)
    dt = jax.nn.softplus(dtr.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    y, hT = ssd(xh, dt, A, Bh, Ch, impl=cfg.ssd_impl, chunk=s.chunk_size,
                h0=h0)
    y = y + xh * p["D"].astype(y.dtype)[:, None]
    y = y.reshape(B_, S, d_inner)
    y = apply_norm(p["norm"], y * jax.nn.silu(z), "rmsnorm", cfg.norm_eps)
    out = dense(p["out_proj"], y)
    if return_state:
        # final conv window for decode continuation
        xbc_pre = jnp.concatenate(_split_proj(zxbcdt, cfg)[1:4], axis=-1)
        conv_state = xbc_pre[:, -(s.d_conv - 1):, :]
        return out, (conv_state, hT)
    return out


def mamba_init_cache(cfg: ModelConfig, batch: int, dtype) -> Tuple:
    s: SSMCfg = cfg.ssm
    d_inner, H, conv_dim = mamba_dims(cfg)
    conv = jnp.zeros((batch, s.d_conv - 1, conv_dim), dtype)
    h = jnp.zeros((batch, H, s.d_state, s.headdim), jnp.float32)
    return {"conv": conv, "ssm": h}


def mamba_decode_step(p: Params, x: jnp.ndarray, cache, cfg: ModelConfig):
    """x: (B,1,D) -> (y (B,1,D), new cache)."""
    s: SSMCfg = cfg.ssm
    B_ = x.shape[0]
    d_inner, H, conv_dim = mamba_dims(cfg)
    zxbcdt = dense(p["in_proj"], x[:, 0, :])
    gn = s.ngroups * s.d_state
    z, xin, B_r, C_r, dtr = jnp.split(
        zxbcdt, [d_inner, 2 * d_inner, 2 * d_inner + gn, 2 * d_inner + 2 * gn],
        axis=-1)
    xbc = jnp.concatenate([xin, B_r, C_r], axis=-1)
    y_conv, conv_new = causal_conv_step(cache["conv"], xbc,
                                        p["conv_w"], p["conv_b"])
    xbc = jax.nn.silu(y_conv)
    xin, B_r, C_r = jnp.split(xbc, [d_inner, d_inner + gn], axis=-1)
    xh = xin.reshape(B_, H, s.headdim)
    R = H // s.ngroups
    Bh = jnp.broadcast_to(B_r.reshape(B_, s.ngroups, 1, s.d_state),
                          (B_, s.ngroups, R, s.d_state)).reshape(B_, H, s.d_state)
    Ch = jnp.broadcast_to(C_r.reshape(B_, s.ngroups, 1, s.d_state),
                          (B_, s.ngroups, R, s.d_state)).reshape(B_, H, s.d_state)
    dt = jax.nn.softplus(dtr.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    y, h_new = ssd_decode_step(cache["ssm"], xh, dt, A, Bh, Ch)
    y = y + xh * p["D"].astype(y.dtype)[:, None]
    y = y.reshape(B_, d_inner)
    y = apply_norm(p["norm"], y * jax.nn.silu(z), "rmsnorm", cfg.norm_eps)
    out = dense(p["out_proj"], y)[:, None, :]
    return out, {"conv": conv_new, "ssm": h_new}
