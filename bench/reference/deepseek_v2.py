"""Plain reference of DeepSeek-V2-Lite (arXiv:2405.04434) split for CPSL,
as one chip's share of a deployment that divides each MoE layer's
experts over several chips.

Each layer: RMSNorm -> multi-head latent attention -> residual -> RMSNorm
-> feed-forward -> residual; a final RMSNorm and the output head give the
logits, and the loss is the mean token cross-entropy.

- Attention (MLA, no query compression): q = h Wq per head, split into a
  128-wide part without position and a 64-wide rotary part; a 512-wide
  latent c = RMSNorm(h Wdkv[:, :512]) and one shared 64-wide rotary key
  h Wdkv[:, 512:]; per head k = [c Wuk, k_rope], v = c Wuv; causal
  softmax of q.k times 192^-0.5 * mscale^2, then Wo. Rotary embeddings
  rotate halves (the layout the system uses; DeepSeek's checkpoints
  interleave, which only permutes the rotary columns of random weights)
  at YaRN's frequencies: with d = 64, base b, original length L, factor
  s, corr(r) = d ln(L / (2 pi r)) / (2 ln b), low = floor(corr(beta_fast))
  and high = ceil(corr(beta_slow)) clamped to [0, d-1], ramp_i =
  clip((i - low) / (high - low), 0, 1), inv_freq_i = b^(-2i/d) (1 - ramp_i)
  + b^(-2i/d) / s ramp_i; cos and sin times m(s, mscale) / m(s,
  mscale_all_dim), with m(s, a) = 0.1 a ln s + 1, and mscale =
  m(s, mscale_all_dim) in the softmax scale.
- Feed-forward: the first ``first_k_dense_replace`` layers a SwiGLU MLP
  of ``intermediate_size``. The others DeepSeekMoE: a softmax over the
  router's ``published.n_routed_experts`` logits (float32), the greedy
  top ``num_experts_per_tok`` weights as they are (``norm_topk_prob``
  false) times ``routed_scaling_factor``; of the routed experts only the
  first ``n_routed_experts``, those held here, are computed, each densely
  on every token and weighted by its gate (zero where it was not
  chosen), one expert at a time; plus
  ``n_shared_experts`` SwiGLU experts on every token (one MLP of their
  summed width).
- The sequence-wise balance loss (``seq_aux``): per sequence f_i =
  count_i E / (S k) over all E routed experts, P_i = mean_t s_it; alpha
  times the mean over sequences of sum_i f_i P_i, summed over the MoE
  layers. It is differentiated with the cross-entropy; the loss reported
  is the cross-entropy alone, as the system reports it.

Departures a guessed model would make are switches of the configuration
(``bench/configs``'s ``faults``): ``norm_topk_prob`` true renormalises the
top-k weights, ``rope_scaling`` null is plain rope without YaRN or mscale,
``moe_capacity_factor`` drops the choices past each expert's capacity in
groups of ``moe_group_size`` tokens (GShard's order: earlier tokens, then
earlier choices, first).

Everything is float32, one layer at a time under ``jax.checkpoint``,
attention in blocks of queries and the head in sequence chunks, so the
reference fits one chip at the published widths. Split at cut ``v``: the
device side holds the token table and layers [0, v), the server side the
rest, the final norm and an untied head, laid out as the system holds
them: device ``{"embed": {"tok"}, "prologue": [layer], "stack": []}``,
server ``{"prologue": [], "final_norm", "head", "stack": [MoE layers
stacked on a leading axis]}``; a layer ``{"pre_norm", "attn": {"wq",
"w_dkv", "kv_norm", "w_uk", "w_uv", "wo"}, "mlp_norm", "mlp" | "moe":
{"router", "w_gate", "w_up", "w_down", "shared"}}``. The cut must leave
the dense layers on the device side.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

LOSS_CHUNK = 128       # sequence positions per head chunk
Q_BLOCK = 512          # query positions per attention block


def _mscale(s, a):
    return 0.1 * a * math.log(s) + 1.0 if s > 1 else 1.0


def _rope_tables(cfg, S):
    """(cos, sin, softmax gain) for S positions, YaRN where configured."""
    d, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    i = np.arange(d // 2, dtype=np.float64)
    inv = base ** (-2.0 * i / d)
    y = cfg.get("rope_scaling")
    gain, cs = 1.0, 1.0
    if y:
        s, L = float(y["factor"]), float(y["original_max_position_embeddings"])

        def corr(r):
            return d * math.log(L / (2 * math.pi * r)) / (2 * math.log(base))

        low = max(math.floor(corr(y["beta_fast"])), 0)
        high = min(math.ceil(corr(y["beta_slow"])), d - 1)
        ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
        inv = inv * (1 - ramp) + inv / s * ramp
        cs = _mscale(s, y["mscale"]) / _mscale(s, y["mscale_all_dim"])
        if y.get("mscale_all_dim"):
            gain = _mscale(s, y["mscale_all_dim"]) ** 2
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang) * cs, jnp.float32),
            jnp.asarray(np.sin(ang) * cs, jnp.float32), gain)


def _rope(x, cos, sin):
    """x: (B, S, heads, 64); half rotation."""
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _lin(k, i, o):
    return {"w": jax.random.normal(k, (i, o)) / math.sqrt(i)}


def _swiglu_init(k, d, ff):
    ks = jax.random.split(k, 3)
    return {"w_gate": _lin(ks[0], d, ff), "w_up": _lin(ks[1], d, ff),
            "w_down": _lin(ks[2], ff, d)}


def _layer_init(key, cfg, moe: bool):
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    ks = jax.random.split(key, 7)
    p = {"pre_norm": {"scale": jnp.ones((d,))},
         "attn": {"wq": _lin(ks[0], d, H * (dn + dr)),
                  "w_dkv": _lin(ks[1], d, r + dr),
                  "kv_norm": {"scale": jnp.ones((r,))},
                  "w_uk": _lin(ks[2], r, H * dn),
                  "w_uv": _lin(ks[3], r, H * dv),
                  "wo": _lin(ks[4], H * dv, d)},
         "mlp_norm": {"scale": jnp.ones((d,))}}
    if not moe:
        p["mlp"] = _swiglu_init(ks[5], d, cfg["intermediate_size"])
        return p
    E = cfg["published"]["n_routed_experts"]
    n, ff = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    ke = jax.random.split(ks[5], 4)
    p["moe"] = {
        "router": jax.random.normal(ke[0], (d, E)) / math.sqrt(d),
        "w_gate": jax.random.normal(ke[1], (n, d, ff)) / math.sqrt(d),
        "w_up": jax.random.normal(ke[2], (n, d, ff)) / math.sqrt(d),
        "w_down": jax.random.normal(ke[3], (n, ff, d)) / math.sqrt(ff),
        "shared": _swiglu_init(ks[6], d, ff * cfg["n_shared_experts"])}
    return p


def init(key, cfg: dict, v: int):
    """(device-side params, server-side params) for cut ``v``: token table
    N(0, 0.02^2), dense kernels and the router N(0, 1/fan_in), norm scales
    one."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    n, n_dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    assert n_dense <= v < n
    k_tok, k_head, k_dev, k_srv = jax.random.split(key, 4)
    dev = {"embed": {"tok": 0.02 * jax.random.normal(k_tok, (V, d))},
           "prologue": [_layer_init(k, cfg, i >= n_dense) for i, k in
                        enumerate(jax.random.split(k_dev, v))],
           "stack": []}
    srv = {"prologue": [],
           "final_norm": {"scale": jnp.ones((d,))},
           "head": jax.random.normal(k_head, (d, V)) / math.sqrt(d),
           "stack": [jax.vmap(lambda k: _layer_init(k, cfg, True))(
               jax.random.split(k_srv, n - v))]}
    return dev, srv


def _attention(p, h, cfg, nm):
    B, S, _ = h.shape
    H = cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    cos, sin, gain = _rope_tables(cfg, S)
    q = nm.einsum("bsd,de->bse", h, p["wq"]["w"]).reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], cos, sin)
    ckr = nm.einsum("bsd,de->bse", h, p["w_dkv"]["w"])
    c = _rms(ckr[..., :r], p["kv_norm"]["scale"], cfg["rms_norm_eps"])
    k_rope = _rope(ckr[..., None, r:], cos, sin)[:, :, 0]      # (B, S, dr)
    k_nope = nm.einsum("bsr,re->bse", c, p["w_uk"]["w"]).reshape(B, S, H, dn)
    v = nm.einsum("bsr,re->bse", c, p["w_uv"]["w"]).reshape(B, S, H, dv)
    scale = gain / math.sqrt(dn + dr)
    nb = S // Q_BLOCK if S % Q_BLOCK == 0 and S > Q_BLOCK else 1
    blk = S // nb

    def block(i):
        lo = i * blk
        qn = lax.dynamic_slice_in_dim(q_nope, lo, blk, 1)
        qr = lax.dynamic_slice_in_dim(q_rope, lo, blk, 1)
        s = (nm.einsum("bqhd,bkhd->bhqk", qn, k_nope)
             + nm.einsum("bqhd,bkd->bhqk", qr, k_rope)) * scale
        causal = (lo + jnp.arange(blk))[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(causal, s, -jnp.inf)
        return nm.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    o = lax.map(jax.checkpoint(block), jnp.arange(nb))      # (nb, B, blk...)
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, H * dv)
    return nm.einsum("bse,ed->bsd", o, p["wo"]["w"])


def _swiglu(p, h, nm):
    g = nm.einsum("bsd,df->bsf", h, p["w_gate"]["w"])
    u = nm.einsum("bsd,df->bsf", h, p["w_up"]["w"])
    return nm.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, p["w_down"]["w"])


def capacity_kept(idx, E, k, group, factor):
    """(T, k) 1 where a choice fits its expert's capacity ceil(g k / E *
    factor) within its group of ``group`` tokens, in token order then
    choice order, else 0."""
    T = idx.shape[0]
    g = math.gcd(T, group)
    oh = jax.nn.one_hot(idx.reshape(T // g, g, k), E)          # (n, g, k, E)
    before = jnp.cumsum(oh.sum(2), axis=1) - oh.sum(2)          # tokens before
    within = jnp.cumsum(oh, axis=2) - oh                        # earlier choices
    pos = ((before[:, :, None, :] + within) * oh).sum(-1)
    cap = math.ceil(g * k / E * factor)
    return (pos < cap).astype(jnp.float32).reshape(T, k)


def _moe(p, h, cfg, nm):
    """(routed share + shared experts, balance loss)."""
    B, S, d = h.shape
    E = cfg["published"]["n_routed_experts"]
    k = cfg["num_experts_per_tok"]
    n = cfg["n_routed_experts"]
    x = h.reshape(B * S, d)
    probs = jax.nn.softmax(nm.einsum("td,de->te", x, p["router"]), axis=-1)
    w, idx = lax.top_k(probs, k)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * cfg["routed_scaling_factor"]
    if cfg.get("moe_capacity_factor"):
        w = w * capacity_kept(idx, E, k, cfg["moe_group_size"],
                              cfg["moe_capacity_factor"])
    gates = jnp.einsum("tk,tke->et", w, jax.nn.one_hot(idx, n))

    def expert(y, ex):
        g_e, wg, wu, wd = ex
        a = jax.nn.silu(nm.einsum("td,df->tf", x, wg)) \
            * nm.einsum("td,df->tf", x, wu)
        return y + g_e[:, None] * nm.einsum("tf,fd->td", a, wd), None

    y, _ = lax.scan(jax.checkpoint(expert), jnp.zeros_like(x),
                    (gates, p["w_gate"], p["w_up"], p["w_down"]))
    y = y.reshape(B, S, d) + _swiglu(p["shared"], h, nm)
    counts = jax.nn.one_hot(idx.reshape(B, S * k), E).sum(1)    # (B, E)
    f = counts * E / (S * k)
    aux = cfg["aux_loss_alpha"] * jnp.mean(
        jnp.sum(f * probs.reshape(B, S, E).mean(1), -1))
    return y, aux


def _layer(p, x, cfg, nm):
    eps = cfg["rms_norm_eps"]
    x = x + _attention(p["attn"], _rms(x, p["pre_norm"]["scale"], eps),
                       cfg, nm)
    h = _rms(x, p["mlp_norm"]["scale"], eps)
    if "moe" in p:
        y, aux = _moe(p["moe"], h, cfg, nm)
        return x + y, aux
    return x + _swiglu(p["mlp"], h, nm), jnp.zeros(())


def make(cfg: dict, v: int):
    """(device_apply, server_loss) of the split at cut ``v``."""
    eps = cfg["rms_norm_eps"]

    def device_apply(dev, batch, nm):
        x = dev["embed"]["tok"][batch["tokens"]]
        for p in dev["prologue"]:
            x, _ = jax.checkpoint(lambda p_, x_: _layer(p_, x_, cfg, nm))(
                p, x)
        return x

    def server_loss(srv, smashed, batch, nm):
        def body(carry, p):
            x, aux = carry
            x, a = _layer(p, x, cfg, nm)
            return (x, aux + a), None

        (x, aux), _ = lax.scan(jax.checkpoint(body),
                               (smashed, jnp.zeros(())), srv["stack"][0])
        x = _rms(x, srv["final_norm"]["scale"], eps)
        B, S, d = x.shape
        n = S // LOSS_CHUNK if S % LOSS_CHUNK == 0 and S > LOSS_CHUNK else 1
        xs = jnp.moveaxis(x.reshape(B, n, S // n, d), 1, 0)
        ls = jnp.moveaxis(batch["labels"].reshape(B, n, S // n), 1, 0)

        def chunk(tot, xl):
            xc, lc = xl
            logits = nm.einsum("bsd,dv->bsv", xc, srv["head"])
            lse = jax.nn.logsumexp(logits, -1)
            ll = jnp.take_along_axis(logits, lc[..., None], -1)[..., 0]
            return tot + jnp.sum(lse - ll), None

        tot, _ = lax.scan(jax.checkpoint(chunk), jnp.zeros(()), (xs, ls))
        # the balance loss is differentiated; its value is not reported
        return tot / (B * S) + (aux - lax.stop_gradient(aux))

    return device_apply, server_loss
