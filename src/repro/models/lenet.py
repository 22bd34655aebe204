"""The paper's 12-layer chain-topology LeNet (Table III).

| 1 CONV1 32@3x3 | 2 CONV2 32@3x3 | 3 POOL1 2x2 | 4 CONV3 64@3x3 |
| 5 CONV4 64@3x3 | 6 POOL2 2x2 | 7 CONV5 128@3x3 | 8 CONV6 128@3x3 |
| 9 POOL3 2x2 | 10 FC1 382 | 11 FC2 192 | 12 FC3 10 |

The 12 layers are the paper's cut-layer set V = {1..12}. The paper's
Fig. 1(b)/Table II numbers imply VALID padding for the first conv pair
(POOL1 smashed data = 12*12*32*4B = 18.4 KB per sample, matching xi_s =
18 KB); we use VALID for conv1-4 and SAME for conv5-6 so the spatial map
stays >= 2x2 on 28x28 inputs.

Every layer's output is a valid smashed-data tensor, so CPSL can cut at any
v — `apply_range(params, x, lo, hi)` runs layers [lo, hi).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

LAYERS = ["CONV1", "CONV2", "POOL1", "CONV3", "CONV4", "POOL2",
          "CONV5", "CONV6", "POOL3", "FC1", "FC2", "FC3"]
N_LAYERS = len(LAYERS)
_CONV = {"CONV1": (1, 32, "VALID"), "CONV2": (32, 32, "VALID"),
         "CONV3": (32, 64, "VALID"), "CONV4": (64, 64, "VALID"),
         "CONV5": (64, 128, "SAME"), "CONV6": (128, 128, "SAME")}
_FC = {"FC1": 382, "FC2": 192, "FC3": 10}


def layer_shapes(input_hw: int = 28) -> list:
    """Per-layer output shapes (H, W, C) or (F,), following Table III."""
    h, c = input_hw, 1
    shapes = []
    for name in LAYERS:
        if name.startswith("CONV"):
            cin, cout, pad = _CONV[name]
            if pad == "VALID":
                h = h - 2
            c = cout
            shapes.append((h, h, c))
        elif name.startswith("POOL"):
            h = h // 2
            shapes.append((h, h, c))
        else:
            shapes.append((_FC[name],))
    return shapes


def init(key, input_hw: int = 28) -> dict:
    params = {}
    ks = jax.random.split(key, N_LAYERS)
    h = input_hw
    c = 1
    flat = None
    for i, name in enumerate(LAYERS):
        if name.startswith("CONV"):
            cin, cout, pad = _CONV[name]
            scale = 1.0 / math.sqrt(9 * cin)
            params[name] = {
                "w": jax.random.normal(ks[i], (3, 3, cin, cout)) * scale,
                "b": jnp.zeros((cout,)),
            }
            if pad == "VALID":
                h -= 2
            c = cout
        elif name.startswith("POOL"):
            h //= 2
        else:
            if flat is None:
                flat = h * h * c
            fout = _FC[name]
            params[name] = {
                "w": jax.random.normal(ks[i], (flat, fout)) / math.sqrt(flat),
                "b": jnp.zeros((fout,)),
            }
            flat = fout
    return params


def conv_im2col(x, w, b, pad):
    """3x3 conv as im2col + matmul: 9 shifted slices concatenated into
    patch rows, one dot against the flattened kernel.

    Forward equals ``lax.conv_general_dilated`` up to summation order
    (within 4 eps of sum |x||w| on XLA:CPU, asserted in tests); the
    point is the *batched* lowering: under
    ``jax.vmap`` over per-client / per-replica WEIGHTS a direct conv
    becomes a grouped convolution (XLA:CPU naive emitter, ~10x slower —
    see ``CPSLConfig.unroll_clients``), while this form becomes a
    batched ``dot_general`` (eigen batched gemm). The slice/concat
    patch extraction has no weight operand, so vmap only grows its
    batch dim, and — unlike direct convs, which XLA:CPU lowers to its
    naive emitter inside while-loop bodies (~36x, measured) — the dot
    stays fast inside ``lax.scan``, enabling scanned round/cluster axes
    (``CPSLConfig.scan_rounds``)."""
    B, H, W, C = x.shape
    if pad == "SAME":
        x = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
        Ho, Wo = H, W
    else:
        Ho, Wo = H - 2, W - 2
    cols = jnp.concatenate(
        [x[:, di:di + Ho, dj:dj + Wo, :] for di in range(3)
         for dj in range(3)], -1)                       # (B, Ho, Wo, 9C)
    y = cols.reshape(B, Ho * Wo, 9 * C) @ w.astype(x.dtype).reshape(
        9 * C, -1)
    return y.reshape(B, Ho, Wo, -1) + b.astype(x.dtype)


def _apply_layer(params, x, name, conv_impl="direct"):
    if name.startswith("CONV"):
        _, _, pad = _CONV[name]
        p = params[name]
        if conv_impl == "im2col":
            return jax.nn.relu(conv_im2col(x, p["w"], p["b"], pad))
        y = lax.conv_general_dilated(
            x, p["w"].astype(x.dtype), window_strides=(1, 1), padding=pad,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return jax.nn.relu(y + p["b"].astype(x.dtype))
    if name.startswith("POOL"):
        # 2x2/stride-2 max-pool as reshape + reduce-max: forward is
        # bit-identical to lax.reduce_window, but the gradient avoids
        # XLA:CPU's SelectAndScatter (a scalar loop, ~15x slower than the
        # reduce-max transpose, measured on XLA:CPU).
        # Tie-routing differs (reduce-max splits the cotangent among tied
        # maxima, e.g. ReLU zeros; SelectAndScatter picks the first) —
        # both are valid subgradients of max.
        B, H, W, C = x.shape
        if H % 2 or W % 2:   # odd maps (non-28 input_hw): VALID drops the rim
            x = x[:, :H - H % 2, :W - W % 2]
            B, H, W, C = x.shape
        return jnp.max(x.reshape(B, H // 2, 2, W // 2, 2, C), axis=(2, 4))
    p = params[name]
    if x.ndim > 2:
        x = x.reshape(x.shape[0], -1)
    y = x @ p["w"].astype(x.dtype) + p["b"].astype(x.dtype)
    return jax.nn.relu(y) if name != "FC3" else y


def apply_range(params: dict, x: jnp.ndarray, lo: int, hi: int,
                conv_impl: str = "direct"):
    """Run layers [lo, hi). x: (B,28,28,1) if lo==0, else the smashed
    data. ``conv_impl``: "direct" (lax conv) or "im2col" (vmap/scan
    friendly matmul form, see ``conv_im2col``)."""
    for name in LAYERS[lo:hi]:
        x = _apply_layer(params, x, name, conv_impl)
    return x


def forward(params: dict, x: jnp.ndarray) -> jnp.ndarray:
    return apply_range(params, x, 0, N_LAYERS)


def loss_fn(params: dict, batch: dict) -> jnp.ndarray:
    logits = forward(params, batch["image"])
    # paper: log-likelihood loss == cross-entropy on log-softmax
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, batch["label"][:, None], axis=-1)
    return jnp.mean(nll)


def split_params(params: dict, v: int) -> Tuple[dict, dict]:
    """Device-side = layers [0, v), server-side = layers [v, 12)."""
    dev = {k: params[k] for k in LAYERS[:v] if k in params}
    srv = {k: params[k] for k in LAYERS[v:] if k in params}
    return dev, srv


def merge_params(dev: dict, srv: dict) -> dict:
    out = dict(dev)
    out.update(srv)
    return out


def accuracy(params: dict, images, labels, batch: int = 512) -> float:
    hits, n = 0, 0
    fwd = jax.jit(forward)
    for i in range(0, len(images), batch):
        lg = fwd(params, images[i:i + batch])
        hits += int((jnp.argmax(lg, -1) == labels[i:i + batch]).sum())
        n += len(images[i:i + batch])
    return hits / max(n, 1)
