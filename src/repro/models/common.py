"""Common pure-JAX model components: norms, rope (with YaRN), attention
(GQA/MLA, naive/chunked flash-equivalent), MLPs, dropless MoE over the
experts held here.

Everything is functional: ``*_init(key, ...) -> params`` (nested dicts of
f32 arrays) and ``*_apply(params, x, ...) -> y``. Compute runs in the
config's compute dtype (bf16 by default); softmax statistics in f32.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig, MoECfg, MLACfg, YaRNCfg
from repro.kernels.mla_attention import ops as mla_ops

Params = dict

NEG_INF = -1e30


def cdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def pdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


# --------------------------------------------------------------------------
# initializers
# --------------------------------------------------------------------------

def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def dense_init(key, d_in: int, d_out: int, *, bias: bool = False,
               dtype=jnp.float32, scale: Optional[float] = None) -> Params:
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    p = {"w": _normal(key, (d_in, d_out), scale, dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------

def norm_init(d: int, kind: str, dtype=jnp.float32) -> Params:
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d,), dtype)}
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def apply_norm(p: Params, x: jnp.ndarray, kind: str, eps: float = 1e-6,
               gemma_style: bool = False) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * lax.rsqrt(var + eps)
        scale = p["scale"].astype(jnp.float32)
        # gemma parameterizes the scale as (1 + w)
        y = y * (1.0 + scale) if gemma_style else y * scale
    else:  # layernorm
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mean) * lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# --------------------------------------------------------------------------
# rotary position embeddings (NeoX half-rotation convention)
# --------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_freqs(dim: int, theta: float,
               scaling: Optional[YaRNCfg] = None) -> jnp.ndarray:
    """Rotary frequencies; with ``scaling``, YaRN's: frequencies below the
    ``beta_slow`` correction dimension are interpolated by ``factor``,
    those above ``beta_fast``'s kept, a linear ramp between."""
    freqs = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    if scaling is None:
        return freqs

    def corr(rotations):
        return dim * math.log(scaling.original_max_position
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(corr(scaling.beta_fast)), 0)
    high = min(math.ceil(corr(scaling.beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low if high > low else 0.001), 0.0, 1.0)
    return freqs * (1.0 - ramp) + (freqs / scaling.factor) * ramp


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               scaling: Optional[YaRNCfg] = None) -> jnp.ndarray:
    """x: (..., S, H, D); positions: (S,) or broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta, scaling)              # (d/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, d/2)
    cos = jnp.cos(angles)[..., :, None, :]             # (..., S, 1, d/2)
    sin = jnp.sin(angles)[..., :, None, :]
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


# --------------------------------------------------------------------------
# attention cores (grouped-query layout throughout)
#   q: (B, Sq, G, R, D)   k, v: (B, Skv, G, D)
# where G = n_kv_heads, R = n_heads // n_kv_heads.
# --------------------------------------------------------------------------

def _soft_cap(s: jnp.ndarray, cap: float) -> jnp.ndarray:
    return cap * jnp.tanh(s / cap) if cap > 0 else s


def _mask_bias(qpos, kpos, *, causal: bool, window: int,
               kv_valid_len=None) -> jnp.ndarray:
    """Additive f32 bias (..., Sq, Skv) — 0 where allowed, NEG_INF elsewhere."""
    ok = jnp.ones((qpos.shape[-1], kpos.shape[-1]), jnp.bool_)
    dq = qpos[:, None]
    dk = kpos[None, :]
    if causal:
        ok &= dq >= dk
    if window > 0:
        ok &= (dq - dk) < window
    if kv_valid_len is not None:
        ok &= dk < kv_valid_len
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def naive_attention(q, k, v, *, causal: bool, window: int = 0,
                    softcap: float = 0.0, q_offset=0,
                    kv_valid_len=None) -> jnp.ndarray:
    """Reference full-materialization attention. Grouped layout."""
    B, Sq, G, R, D = q.shape
    Skv = k.shape[1]
    scale = 1.0 / math.sqrt(D)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = _soft_cap(s, softcap)
    qpos = q_offset + jnp.arange(Sq)
    kpos = jnp.arange(Skv)
    s = s + _mask_bias(qpos, kpos, causal=causal, window=window,
                       kv_valid_len=kv_valid_len)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.maximum(l, 1e-30)
    o = jnp.einsum("bgrqk,bkgd->bqgrd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def _largest_divisor(n: int, target: int) -> int:
    c = min(target, n)
    while n % c:
        c -= 1
    return c


def _flash_fwd_impl(q, k, v, causal, window, softcap, q_offset, q_chunk,
                    kv_chunk):
    """Online-softmax forward. Returns (out, lse) with lse: (B,G,R,Sq)."""
    B, Sq, G, R, D = q.shape
    Skv = k.shape[1]
    q_chunk = _largest_divisor(Sq, q_chunk)
    kv_chunk = _largest_divisor(Skv, kv_chunk)
    nq, nk = Sq // q_chunk, Skv // kv_chunk
    scale = 1.0 / math.sqrt(D)
    qr = q.reshape(B, nq, q_chunk, G, R, D)

    def q_step(_, inputs):
        qi, qc = inputs                                  # qc: (B, qcw, G, R, D)
        qpos = q_offset + qi * q_chunk + jnp.arange(q_chunk)
        m0 = jnp.full((B, G, R, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, G, R, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, q_chunk, G, R, D), jnp.float32)

        def kv_step(carry, ki):
            m, l, acc = carry
            kc = lax.dynamic_slice_in_dim(k, ki * kv_chunk, kv_chunk, 1)
            vc = lax.dynamic_slice_in_dim(v, ki * kv_chunk, kv_chunk, 1)
            s = jnp.einsum("bqgrd,bkgd->bgrqk", qc.astype(jnp.float32),
                           kc.astype(jnp.float32)) * scale
            s = _soft_cap(s, softcap)
            kpos = ki * kv_chunk + jnp.arange(kv_chunk)
            s = s + _mask_bias(qpos, kpos, causal=causal, window=window)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bgrqk,bkgd->bqgrd", p, vc.astype(jnp.float32))
            acc_new = acc * jnp.moveaxis(alpha, 3, 1)[..., None] + pv
            return (m_new, l_new, acc_new), None

        (m, l, acc), _ = lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk))
        l = jnp.maximum(l, 1e-30)
        lse = m + jnp.log(l)
        out_c = (acc / jnp.moveaxis(l, 3, 1)[..., None]).astype(q.dtype)
        return None, (out_c, lse)

    _, (out, lse) = lax.scan(q_step, None,
                             (jnp.arange(nq), jnp.moveaxis(qr, 1, 0)))
    out = jnp.moveaxis(out, 0, 1).reshape(B, Sq, G, R, D)
    lse = jnp.moveaxis(lse, 0, 3).reshape(B, G, R, Sq)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def chunked_attention(q, k, v, causal=True, window=0, softcap=0.0,
                      q_offset=0, q_chunk=512, kv_chunk=1024) -> jnp.ndarray:
    """Flash attention in pure jnp with a FLASH BACKWARD (custom_vjp).

    Plain AD through the chunk scans would stash the (q_chunk, kv_chunk)
    probability tiles for every iteration — O(Sq*Skv) residuals, the exact
    memory blow-up flash attention exists to avoid. Instead we save only
    (out, lse) and recompute each tile in the backward, the standard
    flash-attention gradient. This is also the exact math of the Pallas
    kernel (kernels/flash_attention) and serves as its oracle.
    """
    return _flash_fwd_impl(q, k, v, causal, window, softcap, q_offset,
                           q_chunk, kv_chunk)[0]


def _flash_fwd_rule(q, k, v, causal, window, softcap, q_offset, q_chunk,
                    kv_chunk):
    out, lse = _flash_fwd_impl(q, k, v, causal, window, softcap, q_offset,
                               q_chunk, kv_chunk)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, window, softcap, q_offset, q_chunk, kv_chunk,
                    res, g):
    q, k, v, out, lse = res
    B, Sq, G, R, D = q.shape
    Skv = k.shape[1]
    q_chunk = _largest_divisor(Sq, q_chunk)
    kv_chunk = _largest_divisor(Skv, kv_chunk)
    nq, nk = Sq // q_chunk, Skv // kv_chunk
    scale = 1.0 / math.sqrt(D)
    f32 = jnp.float32
    # delta_i = sum_d dO_i * O_i   (B,G,R,Sq)
    delta = jnp.einsum("bqgrd,bqgrd->bgrq", g.astype(f32), out.astype(f32))
    qr = jnp.moveaxis(q.reshape(B, nq, q_chunk, G, R, D), 1, 0)
    gr = jnp.moveaxis(g.reshape(B, nq, q_chunk, G, R, D), 1, 0)
    lser = jnp.moveaxis(lse.reshape(B, G, R, nq, q_chunk), 3, 0)
    deltar = jnp.moveaxis(delta.reshape(B, G, R, nq, q_chunk), 3, 0)

    def q_step(carry, inputs):
        dk, dv = carry
        qi, qc, gc, lse_c, delta_c = inputs
        qpos = q_offset + qi * q_chunk + jnp.arange(q_chunk)

        def kv_step(inner, ki):
            dk, dv, dq_c = inner
            kc = lax.dynamic_slice_in_dim(k, ki * kv_chunk, kv_chunk, 1)
            vc = lax.dynamic_slice_in_dim(v, ki * kv_chunk, kv_chunk, 1)
            s_pre = jnp.einsum("bqgrd,bkgd->bgrqk", qc.astype(f32),
                               kc.astype(f32)) * scale
            s = _soft_cap(s_pre, softcap)
            kpos = ki * kv_chunk + jnp.arange(kv_chunk)
            bias = _mask_bias(qpos, kpos, causal=causal, window=window)
            p = jnp.exp(s + bias - lse_c[..., None])     # exact softmax tile
            dp = jnp.einsum("bqgrd,bkgd->bgrqk", gc.astype(f32),
                            vc.astype(f32))
            ds = p * (dp - delta_c[..., None])
            if softcap > 0:
                ds = ds * (1.0 - jnp.square(jnp.tanh(s_pre / softcap)))
            dq_c = dq_c + jnp.einsum("bgrqk,bkgd->bqgrd", ds,
                                     kc.astype(f32)) * scale
            dk_c = jnp.einsum("bgrqk,bqgrd->bkgd", ds,
                              qc.astype(f32)) * scale
            dv_c = jnp.einsum("bgrqk,bqgrd->bkgd", p, gc.astype(f32))
            dk = lax.dynamic_update_slice_in_dim(
                dk, lax.dynamic_slice_in_dim(dk, ki * kv_chunk, kv_chunk, 1)
                + dk_c, ki * kv_chunk, 1)
            dv = lax.dynamic_update_slice_in_dim(
                dv, lax.dynamic_slice_in_dim(dv, ki * kv_chunk, kv_chunk, 1)
                + dv_c, ki * kv_chunk, 1)
            return (dk, dv, dq_c), None

        dq0 = jnp.zeros((B, q_chunk, G, R, D), f32)
        (dk, dv, dq_c), _ = lax.scan(kv_step, (dk, dv, dq0),
                                     jnp.arange(nk))
        return (dk, dv), dq_c

    dk0 = jnp.zeros((B, Skv, G, D), f32)
    dv0 = jnp.zeros((B, Skv, G, D), f32)
    (dk, dv), dq = lax.scan(q_step, (dk0, dv0),
                            (jnp.arange(nq), qr, gr, lser, deltar))
    dq = jnp.moveaxis(dq, 0, 1).reshape(B, Sq, G, R, D)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


chunked_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def grouped_attention(q, k, v, cfg: ModelConfig, *, causal: bool,
                      window: int = 0, q_offset=0, kv_valid_len=None,
                      impl: Optional[str] = None) -> jnp.ndarray:
    impl = impl or cfg.attn_impl
    # chunked/pallas need static q_offset (custom_vjp nondiff arg); traced
    # offsets only occur on decode/cache paths, which use naive anyway.
    fast_ok = (kv_valid_len is None and q.shape[1] > 1
               and isinstance(q_offset, int))
    if impl == "chunked" and fast_ok:
        return chunked_attention(q, k, v, causal, window, cfg.attn_softcap,
                                 q_offset, cfg.q_chunk, cfg.kv_chunk)
    if impl == "pallas" and fast_ok:
        from repro.kernels.flash_attention import ops as fa_ops
        return fa_ops.flash_attention(q, k, v, causal, window,
                                      cfg.attn_softcap, q_offset)
    return naive_attention(q, k, v, causal=causal, window=window,
                           softcap=cfg.attn_softcap, q_offset=q_offset,
                           kv_valid_len=kv_valid_len)


# --------------------------------------------------------------------------
# GQA attention module
# --------------------------------------------------------------------------

def gqa_init(key, cfg: ModelConfig) -> Params:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    H, G = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    dt = pdtype(cfg)
    p = {
        "wq": dense_init(ks[0], d, H * hd, bias=cfg.qkv_bias, dtype=dt),
        "wk": dense_init(ks[1], d, G * hd, bias=cfg.qkv_bias, dtype=dt),
        "wv": dense_init(ks[2], d, G * hd, bias=cfg.qkv_bias, dtype=dt),
        "wo": dense_init(ks[3], H * hd, d, dtype=dt,
                         scale=1.0 / math.sqrt(H * hd)),
    }
    if cfg.qk_norm:
        p["q_norm"] = norm_init(hd, "rmsnorm", dt)
        p["k_norm"] = norm_init(hd, "rmsnorm", dt)
    return p


def gqa_project_kv(p: Params, x: jnp.ndarray, cfg: ModelConfig,
                   positions: jnp.ndarray, *, use_rope: bool = True):
    """Project and rope k/v for caching. x: (B, S, D) -> k, v: (B, S, G, hd)."""
    B, S, _ = x.shape
    hd, G = cfg.resolved_head_dim, cfg.n_kv_heads
    k = dense(p["wk"], x).reshape(B, S, G, hd)
    v = dense(p["wv"], x).reshape(B, S, G, hd)
    if cfg.qk_norm:
        k = apply_norm(p["k_norm"], k, "rmsnorm", cfg.norm_eps)
    if use_rope:
        k = apply_rope(k, positions, cfg.rope_theta)
    return k, v


def gqa_apply(p: Params, x: jnp.ndarray, cfg: ModelConfig, *,
              causal: bool = True, window: int = 0,
              positions: Optional[jnp.ndarray] = None,
              kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
              kv_valid_len=None, use_rope: bool = True,
              impl: Optional[str] = None) -> jnp.ndarray:
    """Self- or cross-attention. If ``kv`` is given it is the (already
    roped/projected) key/value source (cache or encoder memory)."""
    B, S, _ = x.shape
    hd, H, G = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    R = H // G
    if positions is None:
        positions = jnp.arange(S)
    q = dense(p["wq"], x).reshape(B, S, G, R, hd)
    if cfg.qk_norm:
        q = apply_norm(p["q_norm"], q, "rmsnorm", cfg.norm_eps)
    if use_rope:
        q = apply_rope(q.reshape(B, S, G * R, hd), positions,
                       cfg.rope_theta).reshape(B, S, G, R, hd)
    if kv is None:
        k, v = gqa_project_kv(p, x, cfg, positions, use_rope=use_rope)
        q_offset = 0
    else:
        k, v = kv
        # only causal/window masking consults absolute positions
        q_offset = (positions[0] if (causal or window > 0)
                    and positions.ndim == 1 else 0)
    o = grouped_attention(q, k, v, cfg, causal=causal, window=window,
                          q_offset=q_offset, kv_valid_len=kv_valid_len,
                          impl=impl)
    return dense(p["wo"], o.reshape(B, S, H * hd))


# --------------------------------------------------------------------------
# MLA attention (DeepSeek-V2 multi-head latent attention)
# --------------------------------------------------------------------------

def mla_init(key, cfg: ModelConfig) -> Params:
    m: MLACfg = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    dt = pdtype(cfg)
    ks = jax.random.split(key, 6)
    qdim = H * (m.qk_nope_head_dim + m.qk_rope_head_dim)
    p = {
        # q projection (V2-Lite: full rank)
        "wq": dense_init(ks[0], d, qdim, dtype=dt),
        # compressed kv latent + decoupled rope key
        "w_dkv": dense_init(ks[1], d, m.kv_lora_rank + m.qk_rope_head_dim, dtype=dt),
        "kv_norm": norm_init(m.kv_lora_rank, "rmsnorm", dt),
        "w_uk": dense_init(ks[2], m.kv_lora_rank, H * m.qk_nope_head_dim, dtype=dt),
        "w_uv": dense_init(ks[3], m.kv_lora_rank, H * m.v_head_dim, dtype=dt),
        "wo": dense_init(ks[4], H * m.v_head_dim, d, dtype=dt),
    }
    return p


def mla_project_latent(p: Params, x: jnp.ndarray, cfg: ModelConfig,
                       positions: jnp.ndarray):
    """Compute the cacheable latent: c_kv (B,S,r) and roped k_rope (B,S,dr)."""
    m: MLACfg = cfg.mla
    ckv_kr = dense(p["w_dkv"], x)
    c_kv, k_rope = jnp.split(ckv_kr, [m.kv_lora_rank], axis=-1)
    c_kv = apply_norm(p["kv_norm"], c_kv, "rmsnorm", cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions,
                        cfg.rope_theta, cfg.rope_scaling)[:, :, 0, :]
    return c_kv, k_rope


def mla_softmax_gain(cfg: ModelConfig) -> float:
    """What the softmax scale 1/sqrt(qk head dim) is multiplied by: YaRN's
    mscale(factor, mscale_all_dim) squared, as DeepSeek-V2 sets it."""
    s = cfg.rope_scaling
    if s is None or not s.mscale_all_dim:
        return 1.0
    return yarn_mscale(s.factor, s.mscale_all_dim) ** 2


def mla_apply(p: Params, x: jnp.ndarray, cfg: ModelConfig, *,
              causal: bool = True, positions: Optional[jnp.ndarray] = None,
              latent: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
              kv_valid_len=None, absorbed: bool = False) -> jnp.ndarray:
    """MLA attention. ``latent`` is the (c_kv, k_rope) cache for decode.

    absorbed=True runs attention in the compressed latent space (W_UK folded
    into the query, W_UV folded into the output) — the memory-optimal decode
    path; scores/values touch only rank-r tensors.
    """
    with jax.named_scope("mla"):
        return _mla(p, x, cfg, causal, positions, latent, kv_valid_len,
                    absorbed)


def _mla(p, x, cfg, causal, positions, latent, kv_valid_len, absorbed):
    m: MLACfg = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    dn, dr, dv, r = (m.qk_nope_head_dim, m.qk_rope_head_dim,
                     m.v_head_dim, m.kv_lora_rank)
    if positions is None:
        positions = jnp.arange(S)
    q = dense(p["wq"], x).reshape(B, S, H, dn + dr)
    q_nope, q_rope = jnp.split(q, [dn], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, cfg.rope_scaling)
    if latent is None:
        c_kv, k_rope = mla_project_latent(p, x, cfg, positions)
        q_offset = 0
    else:
        c_kv, k_rope = latent
        q_offset = positions[0] if positions.ndim == 1 else 0
    Skv = c_kv.shape[1]
    gain = mla_softmax_gain(cfg)

    if absorbed:
        # fold W_UK into q: q_lat (B,S,H,r); attend over latent directly.
        w_uk = p["w_uk"]["w"].reshape(r, H, dn).astype(q_nope.dtype)
        q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, w_uk)
        qq = jnp.concatenate([q_lat, q_rope], axis=-1)     # (B,S,H,r+dr)
        kk = jnp.concatenate([c_kv, k_rope], axis=-1)      # (B,Skv,r+dr)
        # grouped layout with G=1 kv head of width r+dr, value = c_kv (r)
        qq = qq.reshape(B, S, 1, H, r + dr) * (
            gain / math.sqrt((dn + dr) / (r + dr)))
        kk = kk[:, :, None, :]
        vv = c_kv[:, :, None, :]
        o_lat = naive_attention(qq, kk, vv, causal=causal, q_offset=q_offset,
                                kv_valid_len=kv_valid_len)  # (B,S,1,H,r)
        w_uv = p["w_uv"]["w"].reshape(r, H, dv).astype(x.dtype)
        o = jnp.einsum("bshr,rhd->bshd", o_lat[:, :, 0], w_uv)
    else:
        k_nope = dense(p["w_uk"], c_kv).reshape(B, Skv, H, dn)
        v = dense(p["w_uv"], c_kv).reshape(B, Skv, H, dv)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, Skv, H, dr))],
            axis=-1)
        qq = jnp.concatenate([q_nope, q_rope], axis=-1)
        # training's full causal self-attention: MLA's own flash core, q·k
        # over dn + dr, p·v over dv; decode and cache paths: the reference
        if (causal and latent is None and kv_valid_len is None and S > 1
                and cfg.attn_impl != "naive"):
            o = mla_ops.mla_attention(
                qq, k, v, scale=gain / math.sqrt(dn + dr),
                bq=_largest_divisor(S, cfg.q_chunk),
                bkv=_largest_divisor(S, cfg.kv_chunk))
        else:
            if gain != 1.0:
                qq = qq * gain
            o = naive_attention(qq[:, :, :, None], k, v, causal=causal,
                                q_offset=q_offset, kv_valid_len=kv_valid_len)
    return dense(p["wo"], o.reshape(B, S, H * dv))


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------

def _act(x, kind: str):
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x, approximate=True)
    raise ValueError(kind)


def mlp_init(key, d: int, d_ff: int, cfg: ModelConfig, *,
             bias: bool = False) -> Params:
    dt = pdtype(cfg)
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[0], d, d_ff, bias=bias, dtype=dt),
         "w_down": dense_init(ks[1], d_ff, d, bias=bias, dtype=dt)}
    if cfg.glu:
        p["w_gate"] = dense_init(ks[2], d, d_ff, bias=bias, dtype=dt)
    return p


def mlp_apply(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    up = dense(p["w_up"], x)
    if cfg.glu:
        h = _act(dense(p["w_gate"], x), cfg.act) * up
    else:
        h = _act(up, cfg.act)
    return dense(p["w_down"], h)


# --------------------------------------------------------------------------
# auxiliary outputs of a layer stack: the auxiliary loss and counters
# --------------------------------------------------------------------------

def aux_zero(cfg: ModelConfig) -> dict:
    """A stack's auxiliary outputs before any layer: ``loss`` (added to the
    objective), and for MoE models ``moe_routed`` (token choices that land
    on the experts held here) and ``moe_load_max`` (the largest held
    expert's token choices in one layer call)."""
    z = jnp.zeros((), jnp.float32)
    if cfg.moe is None:
        return {"loss": z}
    return {"loss": z, "moe_routed": z, "moe_load_max": z}


def aux_add(a: dict, b: dict) -> dict:
    """Two layers' (or steps') auxiliary outputs together: ``*_max``
    counters by their maximum, everything else summed."""
    return {k: jnp.maximum(a[k], b[k]) if k.endswith("_max") else a[k] + b[k]
            for k in a}


def aux_over_clients(aux: dict) -> dict:
    """The K device-side models' outputs (leading axis K) as one: the loss
    averaged (each device's batch is 1/K of the server's), counters
    summed or maximised."""
    return {k: (v.max() if k.endswith("_max") else
                v.mean() if k == "loss" else v.sum()) for k, v in aux.items()}


# --------------------------------------------------------------------------
# MoE: the experts held here, dropless, through a grouped matmul
# --------------------------------------------------------------------------

def moe_init(key, cfg: ModelConfig) -> Params:
    """The router scores all ``n_experts``; weights of the ``held`` ones."""
    m: MoECfg = cfg.moe
    d, dff, E, H = cfg.d_model, m.d_ff_expert, m.n_experts, m.held
    dt = pdtype(cfg)
    ks = jax.random.split(key, 5)
    s_in, s_ff = 1.0 / math.sqrt(d), 1.0 / math.sqrt(dff)
    p = {
        "router": _normal(ks[0], (d, E), s_in, jnp.float32),
        "w_gate": _normal(ks[1], (H, d, dff), s_in, dt),
        "w_up": _normal(ks[2], (H, d, dff), s_in, dt),
        "w_down": _normal(ks[3], (H, dff, d), s_ff, dt),
    }
    if m.n_shared_experts:
        p["shared"] = mlp_init(ks[4], d, dff * m.n_shared_experts, cfg)
    return p


def moe_route(p: Params, x: jnp.ndarray, m: MoECfg):
    """x: (T, D) -> (probs (T, E), gate weights (T, k), experts (T, k)).
    Softmax over all experts, greedy top-k; the weights renormalised only
    where the config says so (DeepSeek-V2-Lite's routed scaling factor is
    1). The logits are float32 at full precision, as the published gates
    are: a float32 dot at a TPU's default precision is one bfloat16 pass,
    which flips near-tied choices."""
    logits = jnp.dot(x.astype(jnp.float32), p["router"],
                     precision=lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    return probs, w, idx


def moe_balance_loss(probs, idx, m: MoECfg, n_seq: int) -> jnp.ndarray:
    """``switch``: E * sum_e f_e p_e over the whole batch (f_e the share of
    token choices, p_e the mean probability); ``seq`` (DeepSeek-V2): per
    sequence, f_i = count_i E / (S k), P_i = mean_t s_it, the loss the
    mean over sequences of sum_i f_i P_i. Both times alpha."""
    E, k = m.n_experts, m.top_k
    probs = probs.reshape(n_seq, -1, E)
    S = probs.shape[1]
    counts = jax.nn.one_hot(idx.reshape(n_seq, S * k), E,
                            dtype=jnp.float32).sum(1)       # (n_seq, E)
    if m.balance_loss == "seq":
        f = counts * E / (S * k)
        return m.router_aux_weight * jnp.mean(
            jnp.sum(f * probs.mean(1), -1))
    f = counts.sum(0) / (n_seq * S * k)
    return m.router_aux_weight * E * jnp.sum(f * probs.mean((0, 1)))


@jax.custom_vjp
def _permute(x, order, inverse):
    """x[order], whose gradient is the gather g[inverse] (no scatter)."""
    return x[order]


_permute.defvjp(lambda x, order, inverse: (x[order], inverse),
                lambda inverse, g: (g[inverse], None, None))


def _rows_in_groups(y, sizes):
    return jnp.where((jnp.arange(y.shape[0]) < sizes.sum())[:, None], y, 0)


@jax.custom_vjp
def _gmm(lhs, rhs, sizes):
    """Grouped matmul: rows [0, sum(sizes)) of ``lhs`` times their group's
    matrix of ``rhs``; the rows after are zero, and so is their gradient.
    The TPU's ragged-dot kernel leaves rows outside every group unwritten,
    in the forward and in the backward's product for ``lhs``."""
    return _rows_in_groups(lax.ragged_dot(lhs, rhs, sizes), sizes)


def _gmm_bwd(res, g):
    lhs, rhs, sizes = res
    _, vjp = jax.vjp(lambda a, b: lax.ragged_dot(a, b, sizes), lhs, rhs)
    d_lhs, d_rhs = vjp(_rows_in_groups(g, sizes))
    return _rows_in_groups(d_lhs, sizes), d_rhs, None


_gmm.defvjp(lambda lhs, rhs, sizes: (_gmm(lhs, rhs, sizes),
                                     (lhs, rhs, sizes)), _gmm_bwd)


def moe_apply(p: Params, x: jnp.ndarray, cfg: ModelConfig
              ) -> Tuple[jnp.ndarray, dict]:
    """x: (B, S, D) -> (y, aux). Routes every token over all experts and
    computes the part of the result that the experts held here give, for
    every token choice that lands on them (none is dropped): the choices
    sorted by held expert, gate/up/down as grouped matmuls over those
    groups, unsorted and weighted; the shared experts added once."""
    m: MoECfg = cfg.moe
    B, S, D = x.shape
    T, k, H = B * S, m.top_k, m.held
    xt = x.reshape(T, D)
    with jax.named_scope("moe_router"):
        probs, w, idx = moe_route(p, xt, m)
        loss = moe_balance_loss(probs, idx, m, B)
    with jax.named_scope("moe_permute"):
        e = idx.reshape(T * k)
        held = (e >= 0) & (e < H)
        e = jnp.where(held, e, H)            # not held here: sorted last
        order = jnp.argsort(e, stable=True)
        inverse = jnp.argsort(order)
        sizes = jnp.bincount(e, length=H + 1)[:H].astype(jnp.int32)
        rows = _permute(jnp.repeat(xt, k, axis=0), order, inverse)
    with jax.named_scope("moe_experts"):
        def gmm(lhs, w_):
            return _gmm(lhs, w_.astype(lhs.dtype), sizes)
        h = _act(gmm(rows, p["w_gate"]), cfg.act) * gmm(rows, p["w_up"])
        out = gmm(h, p["w_down"])
    with jax.named_scope("moe_combine"):
        # the float32 gate weights stay float32 (a sum over k, and its
        # gradient a sum over D): the router's gradient comes through it
        out = _permute(out, inverse, order)
        wk = jnp.where(held, w.reshape(T * k), 0.0).reshape(T, k)
        y = jnp.einsum("tk,tkd->td", wk, out.reshape(T, k, D),
                       precision=lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
        y = y.astype(x.dtype).reshape(B, S, D)
    if m.n_shared_experts:
        with jax.named_scope("moe_shared"):
            y = y + mlp_apply(p["shared"], x, cfg)
    aux = {"loss": loss, "moe_routed": held.sum().astype(jnp.float32),
           "moe_load_max": sizes.max().astype(jnp.float32)}
    return y, aux


def moe_apply_naive(p: Params, x: jnp.ndarray, cfg: ModelConfig
                    ) -> jnp.ndarray:
    """Oracle: every held expert evaluated densely on every token, weighted
    by its gate where chosen (zero elsewhere), plus the shared experts.
    Used only in tests on tiny shapes."""
    m: MoECfg = cfg.moe
    B, S, D = x.shape
    _, gate_w, gate_idx = moe_route(p, x.reshape(B * S, D), m)
    h = _act(jnp.einsum("bsd,edf->bsef", x, p["w_gate"].astype(x.dtype)),
             cfg.act)
    h = h * jnp.einsum("bsd,edf->bsef", x, p["w_up"].astype(x.dtype))
    ye = jnp.einsum("bsef,efd->bsed", h, p["w_down"].astype(x.dtype))
    sel = jax.nn.one_hot(gate_idx, m.held, dtype=jnp.float32)
    w = jnp.einsum("tke,tk->te", sel, gate_w).astype(x.dtype)
    y = jnp.einsum("bse,bsed->bsd", w.reshape(B, S, -1), ye)
    if m.n_shared_experts:
        y = y + mlp_apply(p["shared"], x, cfg)
    return y


# --------------------------------------------------------------------------
# embeddings / heads
# --------------------------------------------------------------------------

def embed_init(key, cfg: ModelConfig) -> Params:
    dt = pdtype(cfg)
    p = {"tok": _normal(key, (cfg.vocab_size, cfg.d_model), 0.02, dt)}
    if not cfg.tie_embeddings:
        p["head"] = _normal(jax.random.fold_in(key, 1),
                            (cfg.d_model, cfg.vocab_size),
                            1.0 / math.sqrt(cfg.d_model), dt)
    return p


def embed_apply(p: Params, tokens: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    x = p["tok"].astype(cdtype(cfg))[tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def logits_apply(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    if cfg.tie_embeddings:
        logits = x @ p["tok"].astype(x.dtype).T
    else:
        logits = x @ p["head"].astype(x.dtype)
    logits = logits.astype(jnp.float32)
    if cfg.final_softcap > 0:
        logits = _soft_cap(logits, cfg.final_softcap)
    return logits


def lm_head_loss(head_w: jnp.ndarray, x: jnp.ndarray, labels: jnp.ndarray,
                 cfg: ModelConfig, mask: Optional[jnp.ndarray] = None
                 ) -> jnp.ndarray:
    """Cross-entropy from final hiddens WITHOUT materializing the full
    (tokens, vocab) logits when cfg.loss_chunk > 0: scan over token chunks
    with remat so peak memory is one chunk's logits. head_w: (D, V).

    At production shapes the full logits tensor is the memory monster
    (train_4k x 152k vocab = 0.6 TB global); chunking is the standard
    fused-CE production fix.
    """
    D = x.shape[-1]
    B, S = labels.shape[:2] if labels.ndim == 2 else (1, labels.shape[0])
    x = x.reshape(B, S, D)
    labels = labels.reshape(B, S)
    mask = mask.reshape(B, S) if mask is not None else None
    chunk = cfg.loss_chunk
    if chunk <= 0 or S % max(chunk, 1) or S <= chunk:
        logits = (x @ head_w.astype(x.dtype)).astype(jnp.float32)
        if cfg.final_softcap > 0:
            logits = _soft_cap(logits, cfg.final_softcap)
        return cross_entropy(logits, labels, mask)
    # chunk along SEQ
    n = S // chunk
    mask = mask if mask is not None else jnp.ones((B, S), jnp.float32)
    return _fused_ce(x, head_w, labels, mask, n,
                     float(cfg.final_softcap))


def _ce_chunk_stats(xc, head_w, lc, softcap):
    logits = (xc @ head_w.astype(xc.dtype)).astype(jnp.float32)
    raw = logits
    if softcap > 0:
        logits = _soft_cap(logits, softcap)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
    return logits, raw, lse, ll


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _fused_ce(x, head_w, labels, mask, n, softcap):
    """Fused chunked cross-entropy with a HAND-WRITTEN backward.

    AD through the chunk scan would (a) carry a full replicated f32
    (D, V) head-gradient accumulator and (b) all-gather the head per
    chunk. The custom backward recomputes each chunk's softmax, forms
    dlogits = p - onehot, and accumulates dW chunk by chunk.
    """
    return _fused_ce_fwd(x, head_w, labels, mask, n, softcap)[0]


def _fused_ce_fwd(x, head_w, labels, mask, n, softcap):
    B, S, D = x.shape
    chunk = S // n
    xr = jnp.moveaxis(x.reshape(B, n, chunk, D), 1, 0)
    lr = jnp.moveaxis(labels.reshape(B, n, chunk), 1, 0)
    mr = jnp.moveaxis(mask.reshape(B, n, chunk), 1, 0).astype(jnp.float32)

    def body(carry, inp):
        xc, lc, mc = inp
        _, _, lse, ll = _ce_chunk_stats(xc, head_w, lc, softcap)
        return (carry[0] + ((lse - ll) * mc).sum(), carry[1] + mc.sum()), None

    (tot, cnt), _ = lax.scan(jax.checkpoint(body),
                             (jnp.zeros(()), jnp.zeros(())), (xr, lr, mr))
    cnt = jnp.maximum(cnt, 1.0)
    return tot / cnt, (x, head_w, labels, mask, cnt)


def _fused_ce_bwd(n, softcap, res, g):
    x, head_w, labels, mask, cnt = res
    B, S, D = x.shape
    V = head_w.shape[1]
    chunk = S // n
    xr = jnp.moveaxis(x.reshape(B, n, chunk, D), 1, 0)
    lr = jnp.moveaxis(labels.reshape(B, n, chunk), 1, 0)
    mr = jnp.moveaxis(mask.reshape(B, n, chunk), 1, 0).astype(jnp.float32)
    scale = g / cnt

    def body(dW, inp):
        xc, lc, mc = inp
        logits, raw, lse, _ = _ce_chunk_stats(xc, head_w, lc, softcap)
        p = jnp.exp(logits - lse[..., None])
        onehot = jax.nn.one_hot(lc, V, dtype=jnp.float32)
        dlogits = (p - onehot) * (mc * scale)[..., None]
        if softcap > 0:
            dlogits = dlogits * (1.0 - jnp.square(jnp.tanh(raw / softcap)))
        dxc = (dlogits @ head_w.astype(jnp.float32).T).astype(x.dtype)
        dW = dW + jnp.einsum("bcd,bcv->dv", xc.astype(jnp.float32),
                             dlogits)
        return dW, dxc

    dW0 = jnp.zeros((D, V), jnp.float32)
    dW, dxs = lax.scan(jax.checkpoint(body), dW0, (xr, lr, mr))
    dx = jnp.moveaxis(dxs, 0, 1).reshape(B, S, D)
    import numpy as _np
    ct_labels = _np.zeros(labels.shape, jax.dtypes.float0)
    return (dx, dW.astype(head_w.dtype), ct_labels, jnp.zeros_like(mask))


_fused_ce.defvjp(lambda x, w, l, m, n, s: _fused_ce_fwd(x, w, l, m, n, s),
                 _fused_ce_bwd)


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Mean token cross-entropy; logits (..., V) f32, labels (...) int."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
