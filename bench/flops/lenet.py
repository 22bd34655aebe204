"""Operations and sizes of the paper's LeNet (Table III), per image.

The arithmetic of ``repro.core.profile.lenet_profile``, kept here so the
yardstick cannot move with the program: a 3x3 convolution costs
2*9*cin*cout per output position, a 2x2 pool 4 per output element, a
dense layer 2*in*out. ``profile`` gives the per-cut constants of the
paper's latency model (eqs. 15-24), ``train_flops_per_sample`` what one
training sample costs (forward plus a backward of twice the forward).
"""
from __future__ import annotations

import numpy as np

PARAM_BITS = 32
_CONV = {"CONV1": (1, 32, "VALID"), "CONV2": (32, 32, "VALID"),
         "CONV3": (32, 64, "VALID"), "CONV4": (64, 64, "VALID"),
         "CONV5": (64, 128, "SAME"), "CONV6": (128, 128, "SAME")}
_FC = {"FC1": 382, "FC2": 192, "FC3": 10}
LAYERS = ["CONV1", "CONV2", "POOL1", "CONV3", "CONV4", "POOL2",
          "CONV5", "CONV6", "POOL3", "FC1", "FC2", "FC3"]


def layers(input_hw: int = 28):
    """[(name, params, forward FLOPs, output elements)] per layer."""
    h, c, flat = input_hw, 1, None
    out = []
    for name in LAYERS:
        if name.startswith("CONV"):
            cin, cout, pad = _CONV[name]
            h = h - 2 if pad == "VALID" else h
            c = cout
            out.append((name, 9 * cin * cout + cout,
                        2 * 9 * cin * cout * h * h, h * h * c))
        elif name.startswith("POOL"):
            h //= 2
            out.append((name, 0, h * h * c * 4, h * h * c))
        else:
            fin = flat if flat is not None else h * h * c
            fout = _FC[name]
            out.append((name, fin * fout + fout, 2 * fin * fout, fout))
            flat = fout
    return out


def profile(cfg: dict, bp_ratio: float = 1.0, act_bits: int = 32) -> dict:
    """Per-cut arrays (index v-1 for cut v) of the latency model: device
    model bits, smashed bits per sample, smashed-gradient bits, and FLOPs
    per sample on each side, forward and backward. The paper's latency
    model takes backward = forward (``bp_ratio`` 1)."""
    ls = layers(cfg["input_hw"])
    params = np.array([p for _, p, _, _ in ls], dtype=float)
    flops = np.array([f for _, _, f, _ in ls], dtype=float)
    elems = np.array([e for _, _, _, e in ls], dtype=float)
    xi_d = np.cumsum(params) * float(PARAM_BITS)
    xi_s = elems * act_bits
    g_dF = np.cumsum(flops)
    g_sF = g_dF[-1] - g_dF
    return {"xi_d": xi_d, "xi_s": xi_s, "xi_g": xi_s.copy(),
            "gamma_dF": g_dF, "gamma_dB": bp_ratio * g_dF,
            "gamma_sF": g_sF, "gamma_sB": bp_ratio * g_sF}


def train_flops_per_sample(cfg: dict) -> float:
    """Forward and backward FLOPs of one image through the whole model."""
    return 3.0 * sum(f for _, _, f, _ in layers(cfg["input_hw"]))
