"""Decoder-only LM stack covering dense, MoE, SSM and hybrid families.

The stack = unrolled ``prologue`` blocks + ``lax.scan`` over ``n_periods``
repetitions of ``pattern`` (params stacked on a leading axis). Scanning one
*period* (e.g. gemma2's [local, global] pair or jamba's 8-layer unit) keeps
the HLO compact — one traced period regardless of depth — so compiles
stay fast.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import LayerSpec, ModelConfig
from repro.models import common as cm
from repro.models import mamba2 as mb
from repro.models.common import Params


# --------------------------------------------------------------------------
# one block
# --------------------------------------------------------------------------

def block_init(key, cfg: ModelConfig, spec: LayerSpec) -> Params:
    ks = jax.random.split(key, 4)
    dt = cm.pdtype(cfg)
    p = {"pre_norm": cm.norm_init(cfg.d_model, cfg.norm_kind, dt)}
    if spec.mixer == "attn":
        p["attn"] = (cm.mla_init(ks[0], cfg) if cfg.attn_kind == "mla"
                     else cm.gqa_init(ks[0], cfg))
    elif spec.mixer == "mamba":
        p["mamba"] = mb.mamba_init(ks[0], cfg)
    else:
        raise ValueError(spec.mixer)
    if cfg.post_norm:
        p["post_norm"] = cm.norm_init(cfg.d_model, cfg.norm_kind, dt)
    if spec.ffn != "none":
        p["mlp_norm"] = cm.norm_init(cfg.d_model, cfg.norm_kind, dt)
        if spec.ffn == "moe":
            p["moe"] = cm.moe_init(ks[1], cfg)
        else:
            p["mlp"] = cm.mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg)
        if cfg.post_norm:
            p["mlp_post_norm"] = cm.norm_init(cfg.d_model, cfg.norm_kind, dt)
    return p


def _mixer(p: Params, x, cfg: ModelConfig, spec: LayerSpec, positions):
    if spec.mixer == "attn":
        if cfg.attn_kind == "mla":
            return cm.mla_apply(p["attn"], x, cfg, causal=True,
                                positions=positions)
        return cm.gqa_apply(p["attn"], x, cfg, causal=True,
                            window=spec.window, positions=positions)
    return mb.mamba_apply(p["mamba"], x, cfg)


def block_apply(p: Params, x, cfg: ModelConfig, spec: LayerSpec,
                positions) -> Tuple[jnp.ndarray, dict]:
    """Returns (x, aux): aux as ``common.aux_zero`` lays it out."""
    aux = cm.aux_zero(cfg)
    h = cm.apply_norm(p["pre_norm"], x, cfg.norm_kind, cfg.norm_eps)
    a = _mixer(p, h, cfg, spec, positions)
    if cfg.post_norm:
        a = cm.apply_norm(p["post_norm"], a, cfg.norm_kind, cfg.norm_eps)
    x = x + a
    if spec.ffn != "none":
        h = cm.apply_norm(p["mlp_norm"], x, cfg.norm_kind, cfg.norm_eps)
        if spec.ffn == "moe":
            f, aux = cm.moe_apply(p["moe"], h, cfg)
        else:
            f = cm.mlp_apply(p["mlp"], h, cfg)
        if cfg.post_norm:
            f = cm.apply_norm(p["mlp_post_norm"], f, cfg.norm_kind,
                              cfg.norm_eps)
        x = x + f
    return x, aux


# --------------------------------------------------------------------------
# caches
# --------------------------------------------------------------------------

def _attn_cache_init(cfg: ModelConfig, batch: int, cap: int):
    dt = cm.cdtype(cfg)
    if cfg.attn_kind == "mla":
        m = cfg.mla
        return {
            "ckv": jnp.zeros((batch, cap, m.kv_lora_rank), dt),
            "kr": jnp.zeros((batch, cap, m.qk_rope_head_dim), dt),
        }
    hd, G = cfg.resolved_head_dim, cfg.n_kv_heads
    return {
        "k": jnp.zeros((batch, cap, G, hd), dt),
        "v": jnp.zeros((batch, cap, G, hd), dt),
    }


def layer_cache_init(cfg: ModelConfig, spec: LayerSpec, batch: int, cap: int):
    if spec.mixer == "attn":
        return _attn_cache_init(cfg, batch, cap)
    return mb.mamba_init_cache(cfg, batch, cm.cdtype(cfg))


def init_cache(cfg: ModelConfig, batch: int, cap: int):
    """Full-model cache: prologue list + per-pattern-position stacked."""
    pro = [layer_cache_init(cfg, s, batch, cap) for s in cfg.prologue]
    stack = []
    for s in cfg.pattern:
        one = layer_cache_init(cfg, s, batch, cap)
        stack.append(jax.tree.map(
            lambda t: jnp.broadcast_to(t[None], (cfg.n_periods,) + t.shape),
            one))
    return {"prologue": pro, "stack": stack}


def block_decode(p: Params, x, cache, cfg: ModelConfig, spec: LayerSpec,
                 pos) -> Tuple[jnp.ndarray, dict]:
    """x: (B,1,D); pos: scalar index of the new token. Returns (x, cache)."""
    h = cm.apply_norm(p["pre_norm"], x, cfg.norm_kind, cfg.norm_eps)
    positions = jnp.full((1,), pos)
    if spec.mixer == "attn":
        if cfg.attn_kind == "mla":
            ckv_new, kr_new = cm.mla_project_latent(p["attn"], h, cfg,
                                                    positions)
            cache = {
                "ckv": lax.dynamic_update_slice_in_dim(
                    cache["ckv"], ckv_new.astype(cache["ckv"].dtype), pos, 1),
                "kr": lax.dynamic_update_slice_in_dim(
                    cache["kr"], kr_new.astype(cache["kr"].dtype), pos, 1),
            }
            a = cm.mla_apply(p["attn"], h, cfg, causal=False,
                             positions=positions,
                             latent=(cache["ckv"], cache["kr"]),
                             kv_valid_len=pos + 1, absorbed=True)
        else:
            k_new, v_new = cm.gqa_project_kv(p["attn"], h, cfg, positions)
            cache = {
                "k": lax.dynamic_update_slice_in_dim(
                    cache["k"], k_new.astype(cache["k"].dtype), pos, 1),
                "v": lax.dynamic_update_slice_in_dim(
                    cache["v"], v_new.astype(cache["v"].dtype), pos, 1),
            }
            # window masking for local layers works through kv_valid_len +
            # the window term using absolute positions
            a = cm.gqa_apply(p["attn"], h, cfg, causal=False,
                             window=spec.window, positions=positions,
                             kv=(cache["k"], cache["v"]),
                             kv_valid_len=pos + 1)
    else:
        a, cache = mb.mamba_decode_step(p["mamba"], h, cache, cfg)
    if cfg.post_norm:
        a = cm.apply_norm(p["post_norm"], a, cfg.norm_kind, cfg.norm_eps)
    x = x + a
    if spec.ffn != "none":
        h = cm.apply_norm(p["mlp_norm"], x, cfg.norm_kind, cfg.norm_eps)
        if spec.ffn == "moe":
            f, _ = cm.moe_apply(p["moe"], h, cfg)
        else:
            f = cm.mlp_apply(p["mlp"], h, cfg)
        if cfg.post_norm:
            f = cm.apply_norm(p["mlp_post_norm"], f, cfg.norm_kind,
                              cfg.norm_eps)
        x = x + f
    return x, cache


def block_prefill(p: Params, x, cfg: ModelConfig, spec: LayerSpec,
                  positions, cap: int) -> Tuple[jnp.ndarray, dict, dict]:
    """Forward one block while building its decode cache. Returns
    (x, aux, cache). ``cap`` >= S is the cache capacity."""
    B, S, _ = x.shape
    aux = cm.aux_zero(cfg)
    h = cm.apply_norm(p["pre_norm"], x, cfg.norm_kind, cfg.norm_eps)
    if spec.mixer == "attn":
        cache = _attn_cache_init(cfg, B, cap)
        if cfg.attn_kind == "mla":
            ckv, kr = cm.mla_project_latent(p["attn"], h, cfg, positions)
            cache["ckv"] = lax.dynamic_update_slice_in_dim(
                cache["ckv"], ckv.astype(cache["ckv"].dtype), 0, 1)
            cache["kr"] = lax.dynamic_update_slice_in_dim(
                cache["kr"], kr.astype(cache["kr"].dtype), 0, 1)
            a = cm.mla_apply(p["attn"], h, cfg, causal=True,
                             positions=positions)
        else:
            k, v = cm.gqa_project_kv(p["attn"], h, cfg, positions)
            cache["k"] = lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), 0, 1)
            cache["v"] = lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), 0, 1)
            a = cm.gqa_apply(p["attn"], h, cfg, causal=True,
                             window=spec.window, positions=positions)
    else:
        a, (conv_state, hT) = mb.mamba_apply(p["mamba"], h, cfg,
                                             return_state=True)
        cache = {"conv": conv_state, "ssm": hT}
    if cfg.post_norm:
        a = cm.apply_norm(p["post_norm"], a, cfg.norm_kind, cfg.norm_eps)
    x = x + a
    if spec.ffn != "none":
        h = cm.apply_norm(p["mlp_norm"], x, cfg.norm_kind, cfg.norm_eps)
        if spec.ffn == "moe":
            f, aux = cm.moe_apply(p["moe"], h, cfg)
        else:
            f = cm.mlp_apply(p["mlp"], h, cfg)
        if cfg.post_norm:
            f = cm.apply_norm(p["mlp_post_norm"], f, cfg.norm_kind,
                              cfg.norm_eps)
        x = x + f
    return x, aux, cache


# --------------------------------------------------------------------------
# full model
# --------------------------------------------------------------------------

def init(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 3 + len(cfg.prologue) + len(cfg.pattern))
    params = {"embed": cm.embed_init(ks[0], cfg),
              "final_norm": cm.norm_init(cfg.d_model, cfg.norm_kind,
                                         cm.pdtype(cfg))}
    params["prologue"] = [block_init(ks[3 + i], cfg, s)
                          for i, s in enumerate(cfg.prologue)]
    stack = []
    base = 3 + len(cfg.prologue)
    for pos, s in enumerate(cfg.pattern):
        keys = jax.random.split(ks[base + pos], cfg.n_periods)
        stacked = jax.vmap(lambda k: block_init(k, cfg, s))(keys)
        stack.append(stacked)
    params["stack"] = stack
    return params


def _stack_forward(params, x, cfg: ModelConfig, positions):
    """Run prologue + scanned pattern. Returns (x, aux)."""
    aux = cm.aux_zero(cfg)
    for i, spec in enumerate(cfg.prologue):
        blk = (jax.checkpoint(functools.partial(block_apply, cfg=cfg,
                                                spec=spec))
               if cfg.remat else
               functools.partial(block_apply, cfg=cfg, spec=spec))
        x, a = blk(params["prologue"][i], x, positions=positions)
        aux = cm.aux_add(aux, a)

    def body(carry, period_params):
        x, aux = carry
        for pos, spec in enumerate(cfg.pattern):
            x, a = block_apply(period_params[pos], x, cfg, spec, positions)
            aux = cm.aux_add(aux, a)
        return (x, aux), None

    if cfg.n_periods:
        g = cfg.remat_group
        if cfg.remat and g > 1 and cfg.n_periods % g == 0:
            # two-level (sqrt) remat: the outer scan saves one residual
            # per GROUP of g periods; each group's backward recomputes
            # its g bodies (which are themselves rematted) transiently.
            n_outer = cfg.n_periods // g
            grouped = jax.tree.map(
                lambda t: t.reshape((n_outer, g) + t.shape[1:]),
                params["stack"])

            def group_body(carry, group_params):
                return lax.scan(jax.checkpoint(body), carry,
                                group_params)

            (x, aux), _ = lax.scan(jax.checkpoint(group_body), (x, aux),
                                   grouped)
        else:
            body_fn = jax.checkpoint(body) if cfg.remat else body
            (x, aux), _ = lax.scan(body_fn, (x, aux), params["stack"])
    return x, aux


def forward(params: Params, tokens: jnp.ndarray, cfg: ModelConfig,
            positions: Optional[jnp.ndarray] = None,
            inputs_embeds: Optional[jnp.ndarray] = None
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens: (B, S) int32 -> (logits (B,S,V) f32, auxiliary loss)."""
    if positions is None:
        S = tokens.shape[1] if inputs_embeds is None else inputs_embeds.shape[1]
        positions = jnp.arange(S)
    x = (cm.embed_apply(params["embed"], tokens, cfg)
         if inputs_embeds is None else inputs_embeds)
    x, aux = _stack_forward(params, x, cfg, positions)
    x = cm.apply_norm(params["final_norm"], x, cfg.norm_kind, cfg.norm_eps)
    logits = cm.logits_apply(params["embed"], x, cfg)
    return logits, aux["loss"]


def final_hidden(params: Params, tokens: jnp.ndarray, cfg: ModelConfig):
    """Backbone up to (and incl.) the final norm. Returns (x, aux dict)."""
    positions = jnp.arange(tokens.shape[1])
    x = cm.embed_apply(params["embed"], tokens, cfg)
    x, aux = _stack_forward(params, x, cfg, positions)
    x = cm.apply_norm(params["final_norm"], x, cfg.norm_kind, cfg.norm_eps)
    return x, aux


def head_matrix(params: Params, cfg: ModelConfig) -> jnp.ndarray:
    return (params["embed"]["tok"].T if cfg.tie_embeddings
            else params["embed"]["head"])


def loss_fn(params: Params, batch: dict, cfg: ModelConfig) -> jnp.ndarray:
    x, aux = final_hidden(params, batch["tokens"], cfg)
    loss = cm.lm_head_loss(head_matrix(params, cfg), x, batch["labels"],
                           cfg, batch.get("mask"))
    return loss + aux["loss"]


def prefill(params: Params, tokens: jnp.ndarray, cfg: ModelConfig,
            cap: Optional[int] = None):
    """Forward + cache build. Returns (last-position logits, cache)."""
    B, S = tokens.shape
    cap = cap or S
    positions = jnp.arange(S)
    x = cm.embed_apply(params["embed"], tokens, cfg)
    pro_caches = []
    for i, spec in enumerate(cfg.prologue):
        x, _, c = block_prefill(params["prologue"][i], x, cfg, spec,
                                positions, cap)
        pro_caches.append(c)

    def body(x, period_params):
        caches = []
        for pos, spec in enumerate(cfg.pattern):
            x, _, c = block_prefill(period_params[pos], x, cfg, spec,
                                    positions, cap)
            caches.append(c)
        return x, tuple(caches)

    stack_caches = []
    if cfg.n_periods:
        body_fn = jax.checkpoint(body) if cfg.remat else body
        x, caches = lax.scan(body_fn, x, params["stack"])
        stack_caches = list(caches)
    x = cm.apply_norm(params["final_norm"], x, cfg.norm_kind, cfg.norm_eps)
    logits = cm.logits_apply(params["embed"], x[:, -1:, :], cfg)
    return logits[:, 0], {"prologue": pro_caches, "stack": stack_caches}


def decode_step(params: Params, cache: dict, tokens: jnp.ndarray,
                pos, cfg: ModelConfig):
    """One decode step. tokens: (B,) int32; pos: scalar int (new token's
    index; attends to cache[:pos] + itself). Returns (logits (B,V), cache)."""
    x = cm.embed_apply(params["embed"], tokens[:, None], cfg)
    new_pro = []
    for i, spec in enumerate(cfg.prologue):
        x, c = block_decode(params["prologue"][i], x, cache["prologue"][i],
                            cfg, spec, pos)
        new_pro.append(c)

    def body(x, inp):
        period_params, period_cache = inp
        new_caches = []
        for ppos, spec in enumerate(cfg.pattern):
            x, c = block_decode(period_params[ppos], x, period_cache[ppos],
                                cfg, spec, pos)
            new_caches.append(c)
        return x, tuple(new_caches)

    new_stack = []
    if cfg.n_periods:
        x, caches = lax.scan(body, x, (params["stack"],
                                       tuple(cache["stack"])))
        new_stack = list(caches)
    x = cm.apply_norm(params["final_norm"], x, cfg.norm_kind, cfg.norm_eps)
    logits = cm.logits_apply(params["embed"], x, cfg)
    return logits[:, 0], {"prologue": new_pro, "stack": new_stack}
