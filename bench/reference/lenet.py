"""Plain reference of the paper's LeNet (arXiv:2204.08119, Table III).

Twelve layers, each a valid cut: 3x3 convolutions (VALID for the first
four, SAME for the last two, so a 28x28 input keeps a 2x2 map), ReLU,
2x2 max-pools, three dense layers. Parameters are laid out as the split
model under test holds them (``{"CONV1": {"w", "b"}, ...}``, HWIO kernels),
so one tree serves both. The loss is the mean negative log-likelihood.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# name, kind, (in channels or None, out), padding
LAYERS = (("CONV1", "conv", 1, 32, "VALID"), ("CONV2", "conv", 32, 32, "VALID"),
          ("POOL1", "pool", None, None, None),
          ("CONV3", "conv", 32, 64, "VALID"), ("CONV4", "conv", 64, 64, "VALID"),
          ("POOL2", "pool", None, None, None),
          ("CONV5", "conv", 64, 128, "SAME"), ("CONV6", "conv", 128, 128, "SAME"),
          ("POOL3", "pool", None, None, None),
          ("FC1", "fc", None, 382, None), ("FC2", "fc", None, 192, None),
          ("FC3", "fc", None, 10, None))


def init(key, cfg: dict, v: int):
    """(device-side params, server-side params) for cut ``v``, drawn from
    ``key``: kernels N(0, 1/fan_in), biases zero."""
    hw, c = cfg["input_hw"], 1
    params = {}
    keys = jax.random.split(key, len(LAYERS))
    flat = None
    for i, (name, kind, cin, cout, pad) in enumerate(LAYERS):
        if kind == "conv":
            params[name] = {
                "w": jax.random.normal(keys[i], (3, 3, cin, cout))
                / math.sqrt(9 * cin),
                "b": jnp.zeros((cout,))}
            hw = hw - 2 if pad == "VALID" else hw
            c = cout
        elif kind == "pool":
            hw //= 2
        else:
            fin = flat if flat is not None else hw * hw * c
            params[name] = {
                "w": jax.random.normal(keys[i], (fin, cout)) / math.sqrt(fin),
                "b": jnp.zeros((cout,))}
            flat = cout
    names = [n for n, *_ in LAYERS]
    dev = {n: params[n] for n in names[:v] if n in params}
    srv = {n: params[n] for n in names[v:] if n in params}
    return dev, srv


def _apply(params, x, lo, hi, nm):
    for name, kind, _, _, pad in LAYERS[lo:hi]:
        if kind == "conv":
            p = params[name]
            x = jax.nn.relu(nm.conv(x, p["w"], pad) + p["b"].astype(x.dtype))
        elif kind == "pool":
            B, H, W, C = x.shape
            x = jnp.max(x.reshape(B, H // 2, 2, W // 2, 2, C), axis=(2, 4))
        else:
            p = params[name]
            x = x.reshape(x.shape[0], -1)
            x = nm.einsum("bi,io->bo", x, p["w"]) + p["b"].astype(x.dtype)
            if name != "FC3":
                x = jax.nn.relu(x)
    return x


def make(cfg: dict, v: int):
    """(device_apply, server_loss) of the split at cut ``v``; each takes
    the numerics (``reference.numerics``) it computes in."""
    n = len(LAYERS)

    def device_apply(dev, batch, nm):
        return _apply(dev, batch["image"].astype(nm.dtype), 0, v, nm)

    def server_loss(srv, smashed, batch, nm):
        logits = _apply(srv, smashed, v, n, nm).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(logp, batch["label"][:, None], axis=-1)
        return jnp.mean(nll)

    return device_apply, server_loss
