"""Cut-layer splitting: build device-side / server-side sub-models for any
zoo architecture (paper §III/IV, generalized from the chain-topology DNN).

A ``SplitModel`` bundles:
    init_device(key) / init_server(key)
    device_apply(dev_params, batch)        -> (smashed, aux)
    server_loss(srv_params, smashed, batch)-> (loss, aux)
    (aux: a dict of the auxiliary loss ``"loss"``, added to the objective,
    and any counters, laid out as ``models.common.aux_zero``)
    export(dev_params, srv_params)         -> assembled params (+cfg) for
                                              standard serving/eval.

Cut-layer conventions per family:
  - LM (dense/moe/ssm/hybrid/vlm): device = embed + blocks[:v];
    server = blocks[v:] + final norm + (untied) head. Tied-embedding archs
    are trained with an untied server-side head under CPSL (the device owns
    the table; the server cannot share it across the wireless link).
  - enc-dec (whisper): split inside the encoder; the server owns the rest
    of the encoder + the whole decoder.
  - LeNet (paper's model): layer-granular Table III split.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import common as cm
from repro.models import lenet as ln
from repro.models import transformer as tfm
from repro.models import whisper as whp


@dataclass(frozen=True)
class SplitModel:
    kind: str
    cfg: Optional[ModelConfig]
    v: int
    n_cuts: int
    init_device: Callable
    init_server: Callable
    device_apply: Callable          # (dev_params, batch) -> (smashed, aux)
    server_loss: Callable           # (srv, smashed, batch) -> (loss, aux)
    export: Callable                # (dev, srv) -> (params, cfg)
    smashed_spec: Callable          # (batch_size, seq) -> ShapeDtypeStruct
    eval_metrics: Optional[Callable] = None
    # (dev, srv, eval_batch) -> {"acc", "loss"}; jit-safe, used by the
    # fused training curve for in-jit test-set evaluation (None = the
    # family has no packaged eval; run_training_fused then disallows
    # eval_every > 0)
    masked_loss: bool = False
    # True when server_loss implements the reserved per-sample
    # ``batch["sample_weight"]`` semantics that padded fleet layouts
    # (client_mask) rely on; fleets with masks assert it


# --------------------------------------------------------------------------
# LM split
# --------------------------------------------------------------------------

def _split_cfgs(cfg: ModelConfig, v: int):
    specs = cfg.layer_specs()
    assert 1 <= v < len(specs), f"cut {v} out of range for {cfg.name}"
    dev_cfg = cfg.replace(prologue=tuple(specs[:v]), pattern=(), n_layers=v)
    n_pro = len(cfg.prologue)
    if v < n_pro:
        srv_cfg = cfg.replace(prologue=cfg.prologue[v:],
                              n_layers=cfg.n_layers - v)
    else:
        P = len(cfg.pattern)
        off = (v - n_pro) % P
        srv_pro = cfg.pattern[off:] if off else ()
        srv_cfg = cfg.replace(prologue=tuple(srv_pro),
                              n_layers=cfg.n_layers - v)
    return dev_cfg, srv_cfg


def make_lm_split(cfg: ModelConfig, v: int) -> SplitModel:
    dev_cfg, srv_cfg = _split_cfgs(cfg, v)

    def init_device(key):
        ks = jax.random.split(key, v + 1)
        return {
            "embed": {"tok": cm.embed_init(ks[0], cfg)["tok"]},
            "prologue": [tfm.block_init(ks[1 + i], cfg, s)
                         for i, s in enumerate(dev_cfg.prologue)],
            "stack": [],
        }

    def init_server(key):
        ks = jax.random.split(key, 3 + len(srv_cfg.prologue)
                              + len(srv_cfg.pattern))
        params = {
            "prologue": [tfm.block_init(ks[3 + i], cfg, s)
                         for i, s in enumerate(srv_cfg.prologue)],
            "final_norm": cm.norm_init(cfg.d_model, cfg.norm_kind,
                                       cm.pdtype(cfg)),
            "head": cm._normal(ks[0], (cfg.d_model, cfg.vocab_size),
                               1.0 / math.sqrt(cfg.d_model), cm.pdtype(cfg)),
        }
        stack = []
        base = 3 + len(srv_cfg.prologue)
        for pos, s in enumerate(srv_cfg.pattern):
            keys = jax.random.split(ks[base + pos], srv_cfg.n_periods)
            stack.append(jax.vmap(lambda k: tfm.block_init(k, cfg, s))(keys))
        params["stack"] = stack
        return params

    def device_apply(dev, batch):
        tokens = batch["tokens"]
        positions = jnp.arange(tokens.shape[1])
        x = cm.embed_apply(dev["embed"], tokens, cfg)
        x, aux = tfm._stack_forward(dev, x, dev_cfg, positions)
        return x, aux

    def server_loss(srv, smashed, batch):
        positions = jnp.arange(smashed.shape[1])
        x, aux = tfm._stack_forward(srv, smashed, srv_cfg, positions)
        x = cm.apply_norm(srv["final_norm"], x, cfg.norm_kind, cfg.norm_eps)
        loss = cm.lm_head_loss(srv["head"], x, batch["labels"], cfg,
                               batch.get("mask"))
        return loss, aux

    def export(dev, srv):
        """Re-stack into a standard transformer params pytree (untied)."""
        flat = list(dev["prologue"])
        # unstack server periods
        flat += list(srv["prologue"])
        for i in range(srv_cfg.n_periods):
            for pos in range(len(srv_cfg.pattern)):
                flat.append(jax.tree.map(lambda t: t[i], srv["stack"][pos]))
        out_cfg = cfg.replace(tie_embeddings=False)
        n_pro = len(cfg.prologue)
        P = len(cfg.pattern) if cfg.pattern else 1
        params = {
            "embed": {"tok": dev["embed"]["tok"], "head": srv["head"]},
            "final_norm": srv["final_norm"],
            "prologue": flat[:n_pro],
            "stack": [],
        }
        body = flat[n_pro:]
        for pos in range(len(cfg.pattern)):
            per = [body[i * P + pos] for i in range(cfg.n_periods)]
            params["stack"].append(
                jax.tree.map(lambda *ts: jnp.stack(ts), *per))
        return params, out_cfg

    def smashed_spec(batch_size, seq):
        return jax.ShapeDtypeStruct((batch_size, seq, cfg.d_model),
                                    jnp.dtype(cfg.dtype))

    return SplitModel("lm", cfg, v, len(cfg.layer_specs()) - 1, init_device,
                      init_server, device_apply, server_loss, export,
                      smashed_spec)


# --------------------------------------------------------------------------
# enc-dec (whisper) split — cut inside the encoder
# --------------------------------------------------------------------------

def make_encdec_split(cfg: ModelConfig, v: int) -> SplitModel:
    n_enc = cfg.n_enc_layers
    assert 1 <= v < n_enc

    def init_device(key):
        full = whp.init(key, cfg)
        return {"enc_stack": jax.tree.map(lambda t: t[:v],
                                          full["enc_stack"])}

    def init_server(key):
        full = whp.init(key, cfg)
        full["enc_stack"] = jax.tree.map(lambda t: t[v:], full["enc_stack"])
        return full

    def device_apply(dev, batch):
        frames = batch["frames"].astype(cm.cdtype(cfg))
        x = frames + whp.sinusoid_pos(frames.shape[1],
                                      cfg.d_model).astype(frames.dtype)

        def body(x, p):
            return whp.enc_block_apply(p, x, cfg), None

        x, _ = jax.lax.scan(body, x, dev["enc_stack"])
        return x, {"loss": jnp.zeros((), jnp.float32)}

    def server_loss(srv, smashed, batch):
        def body(x, p):
            return whp.enc_block_apply(p, x, cfg), None

        x, _ = jax.lax.scan(body, smashed, srv["enc_stack"])
        memory = cm.apply_norm(srv["enc_norm"], x, "layernorm", cfg.norm_eps)
        xd = whp.decode_hidden(srv, batch["tokens"], memory, cfg)
        head = (srv["embed"]["tok"].T if cfg.tie_embeddings
                else srv["embed"]["head"])
        loss = cm.lm_head_loss(head, xd, batch["labels"], cfg,
                               batch.get("mask"))
        return loss, {"loss": jnp.zeros((), jnp.float32)}

    def export(dev, srv):
        params = dict(srv)
        params["enc_stack"] = jax.tree.map(
            lambda a, b: jnp.concatenate([a, b], 0),
            dev["enc_stack"], srv["enc_stack"])
        return params, cfg

    def smashed_spec(batch_size, seq):
        return jax.ShapeDtypeStruct((batch_size, cfg.enc_seq, cfg.d_model),
                                    jnp.dtype(cfg.dtype))

    return SplitModel("encdec", cfg, v, n_enc - 1, init_device, init_server,
                      device_apply, server_loss, export, smashed_spec)


# --------------------------------------------------------------------------
# LeNet (paper) split
# --------------------------------------------------------------------------

def make_lenet_split(v: int, input_hw: int = 28,
                     conv_impl: str = "direct") -> SplitModel:
    """``conv_impl``: "direct" (lax conv, fastest solo on XLA:CPU) or
    "im2col" (matmul form — required for vmapped fleets and scanned
    round axes, see ``models.lenet.conv_im2col``). Params are identical
    between the two; only the apply lowering differs."""
    def init_device(key):
        return ln.split_params(ln.init(key, input_hw), v)[0]

    def init_server(key):
        return ln.split_params(ln.init(key, input_hw), v)[1]

    def device_apply(dev, batch):
        return (ln.apply_range(dev, batch["image"], 0, v, conv_impl),
                {"loss": jnp.zeros((), jnp.float32)})

    def server_loss(srv, smashed, batch):
        logits = ln.apply_range(srv, smashed, v, ln.N_LAYERS, conv_impl)
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(logp, batch["label"][:, None], axis=-1)
        weight = batch.get("sample_weight")
        if weight is None:
            return jnp.mean(nll), {"loss": jnp.zeros((), jnp.float32)}
        # padded client slots (fleet layout masks): masked rows carry
        # exactly zero weight, so their data never reaches loss or grads
        w = weight.reshape(-1).astype(nll.dtype)
        loss = (nll[:, 0] * w).sum() / jnp.maximum(w.sum(), 1.0)
        return loss, {"loss": jnp.zeros((), jnp.float32)}

    def export(dev, srv):
        return ln.merge_params(dev, srv), None

    def smashed_spec(batch_size, seq=None):
        shp = ln.layer_shapes(input_hw)[v - 1]
        return jax.ShapeDtypeStruct((batch_size,) + tuple(shp), jnp.float32)

    def eval_metrics(dev, srv, batch):
        """In-jit test-set metrics; host-equivalent of export +
        ``lenet.accuracy`` (tests pin the agreement)."""
        smashed = ln.apply_range(dev, batch["image"], 0, v, conv_impl)
        logits = ln.apply_range(srv, smashed, v, ln.N_LAYERS, conv_impl)
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(logp, batch["label"][:, None], axis=-1)
        acc = jnp.mean(
            (jnp.argmax(logits, -1) == batch["label"]).astype(jnp.float32))
        return {"acc": acc, "loss": jnp.mean(nll)}

    return SplitModel("lenet", None, v, ln.N_LAYERS - 1, init_device,
                      init_server, device_apply, server_loss, export,
                      smashed_spec, eval_metrics, masked_loss=True)


def make_split_model(cfg_or_name, v: int, **kw) -> SplitModel:
    if cfg_or_name == "lenet" or cfg_or_name is None:
        return make_lenet_split(v, **kw)
    cfg: ModelConfig = cfg_or_name
    if cfg.family == "cnn":
        return make_lenet_split(v, **kw)
    if cfg.encdec:
        return make_encdec_split(cfg, v)
    return make_lm_split(cfg, v)
