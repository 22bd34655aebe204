"""Plain reference of a Qwen2 decoder (arXiv:2407.10671) split for CPSL.

Each layer: RMSNorm -> grouped-query attention (q/k/v with bias, o
without; rotary embeddings in the half-rotation form over the whole head,
causal softmax) -> residual -> RMSNorm -> SwiGLU MLP -> residual. A final
RMSNorm and the output head give the logits; the loss is the mean token
cross-entropy. Everything is float32, one layer at a time under
``jax.checkpoint`` and the head in sequence chunks, so the reference fits
one chip at the published widths.

Split at cut ``v``: the device side holds the token table and layers
``[0, v)``; the server side the remaining layers, the final norm and an
output head of its own (a split cannot tie the head to a table that lives
on the devices, so the head is untied, as the system under test trains
it). Parameters are laid out as the system holds them: device
``{"embed": {"tok"}, "prologue": [layer, ...], "stack": []}``, server
``{"prologue": [], "final_norm": {"scale"}, "head", "stack": [layers
stacked on a leading axis]}``, a layer ``{"pre_norm", "attn": {"wq", "wk",
"wv", "wo"}, "mlp_norm", "mlp": {"w_up", "w_down", "w_gate"}}``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

LOSS_CHUNK = 128       # sequence positions per head chunk


def _sizes(cfg):
    d = cfg["hidden_size"]
    H, G = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // H
    return d, H, G, hd, cfg["intermediate_size"], cfg["vocab_size"]


def _layer_init(key, cfg):
    d, H, G, hd, ff, _ = _sizes(cfg)
    ks = jax.random.split(key, 7)

    def lin(k, i, o, bias=False):
        p = {"w": jax.random.normal(k, (i, o)) / math.sqrt(i)}
        if bias:
            p["b"] = jnp.zeros((o,))
        return p

    return {"pre_norm": {"scale": jnp.ones((d,))},
            "attn": {"wq": lin(ks[0], d, H * hd, True),
                     "wk": lin(ks[1], d, G * hd, True),
                     "wv": lin(ks[2], d, G * hd, True),
                     "wo": lin(ks[3], H * hd, d)},
            "mlp_norm": {"scale": jnp.ones((d,))},
            "mlp": {"w_up": lin(ks[4], d, ff), "w_down": lin(ks[5], ff, d),
                    "w_gate": lin(ks[6], d, ff)}}


def init(key, cfg: dict, v: int):
    """(device-side params, server-side params) for cut ``v``: token table
    N(0, 0.02^2), dense kernels N(0, 1/fan_in), biases zero, norm scales
    one."""
    d, _, _, _, _, V = _sizes(cfg)
    n = cfg["num_hidden_layers"]
    k_tok, k_head, k_dev, k_srv = jax.random.split(key, 4)
    dev = {"embed": {"tok": 0.02 * jax.random.normal(k_tok, (V, d))},
           "prologue": [_layer_init(k, cfg)
                        for k in jax.random.split(k_dev, v)],
           "stack": []}
    srv = {"prologue": [],
           "final_norm": {"scale": jnp.ones((d,))},
           "head": jax.random.normal(k_head, (d, V)) / math.sqrt(d),
           "stack": [jax.vmap(lambda k: _layer_init(k, cfg))(
               jax.random.split(k_srv, n - v))]}
    return dev, srv


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (B, S, heads, hd); half-rotation over the whole head."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(p, x, cfg, nm):
    d, H, G, hd, _, _ = _sizes(cfg)
    eps = cfg["rms_norm_eps"]
    B, S, _ = x.shape
    a = p["attn"]
    h = _rms(x, p["pre_norm"]["scale"], eps)
    q = nm.einsum("bsd,de->bse", h, a["wq"]["w"]) + a["wq"]["b"]
    k = nm.einsum("bsd,de->bse", h, a["wk"]["w"]) + a["wk"]["b"]
    vv = nm.einsum("bsd,de->bse", h, a["wv"]["w"]) + a["wv"]["b"]
    q = _rope(q.reshape(B, S, H, hd), cfg["rope_theta"])
    k = _rope(k.reshape(B, S, G, hd), cfg["rope_theta"])
    q = q.reshape(B, S, G, H // G, hd)
    vv = vv.reshape(B, S, G, hd)
    s = nm.einsum("bqgrd,bkgd->bgrqk", q, k) / math.sqrt(hd)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    o = nm.einsum("bgrqk,bkgd->bqgrd", jax.nn.softmax(s, axis=-1), vv)
    x = x + nm.einsum("bse,ed->bsd", o.reshape(B, S, H * hd), a["wo"]["w"])
    m = p["mlp"]
    h = _rms(x, p["mlp_norm"]["scale"], eps)
    g = nm.einsum("bsd,df->bsf", h, m["w_gate"]["w"])
    u = nm.einsum("bsd,df->bsf", h, m["w_up"]["w"])
    return x + nm.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, m["w_down"]["w"])


def make(cfg: dict, v: int):
    """(device_apply, server_loss) of the split at cut ``v``."""
    eps = cfg["rms_norm_eps"]

    def device_apply(dev, batch, nm):
        x = dev["embed"]["tok"][batch["tokens"]]
        for p in dev["prologue"]:
            x = jax.checkpoint(lambda p_, x_: _layer(p_, x_, cfg, nm))(p, x)
        return x

    def server_loss(srv, smashed, batch, nm):
        def body(x, p):
            return _layer(p, x, cfg, nm), None

        x, _ = lax.scan(jax.checkpoint(body), smashed, srv["stack"][0])
        x = _rms(x, srv["final_norm"]["scale"], eps)
        B, S, d = x.shape
        n = S // LOSS_CHUNK if S % LOSS_CHUNK == 0 and S > LOSS_CHUNK else 1
        xs = jnp.moveaxis(x.reshape(B, n, S // n, d), 1, 0)
        ls = jnp.moveaxis(batch["labels"].reshape(B, n, S // n), 1, 0)

        def chunk(tot, xl):
            xc, lc = xl
            logits = nm.einsum("bsd,dv->bsv", xc, srv["head"])
            logits = logits.astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, -1)
            ll = jnp.take_along_axis(logits, lc[..., None], -1)[..., 0]
            return tot + jnp.sum(lse - ll), None

        tot, _ = lax.scan(jax.checkpoint(chunk), jnp.zeros(()), (xs, ls))
        return tot / (B * S)

    return device_apply, server_loss
