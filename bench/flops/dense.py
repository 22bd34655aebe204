"""Operations and sizes of a dense decoder LM (GQA attention, gated MLP),
per sequence of ``seq`` tokens.

The arithmetic of ``repro.core.profile.lm_profile`` for dense layers,
kept here so the yardstick cannot move with the program: a projection
costs 2 FLOPs per weight per token, attention 4*seq^2*heads*head_dim per
layer (scores and the weighted sum over the full square), the output
head 2*seq*d*vocab. ``profile`` gives the per-cut constants of the
paper's latency model, ``train_flops_per_sample`` what one training
sequence costs (forward plus a backward of twice the forward; the
forward that rematerialisation repeats is not counted).
"""
from __future__ import annotations

import numpy as np

PARAM_BITS = 32


def _layer(cfg: dict, seq: int):
    d = cfg["hidden_size"]
    H, G = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // H
    attn_p = d * H * hd + 2 * d * G * hd + H * hd * d
    attn_f = 2 * seq * attn_p + 2 * seq * seq * H * hd * 2
    mlp_p = 3 * d * cfg["intermediate_size"]
    return attn_p + mlp_p + 2 * d, attn_f + 2 * seq * mlp_p


def forward_flops(cfg: dict, seq: int) -> float:
    _, f = _layer(cfg, seq)
    return float(cfg["num_hidden_layers"] * f
                 + 2 * seq * cfg["hidden_size"] * cfg["vocab_size"])


def profile(cfg: dict, seq: int, bp_ratio: float = 2.0,
            act_bits: int = 16) -> dict:
    """Per-cut arrays (index v-1 for cut v), as ``lenet.profile``."""
    d, n = cfg["hidden_size"], cfg["num_hidden_layers"]
    p_l, f_l = _layer(cfg, seq)
    xi_d = np.zeros(n)
    g_dF = np.zeros(n)
    cum_p, cum_f = cfg["vocab_size"] * d, 0
    for v in range(1, n + 1):
        cum_p += p_l
        cum_f += f_l
        xi_d[v - 1] = cum_p * PARAM_BITS
        g_dF[v - 1] = cum_f
    xi_s = np.full(n, float(seq * d * act_bits))
    g_sF = np.maximum(forward_flops(cfg, seq) - g_dF, 0.0)
    return {"xi_d": xi_d, "xi_s": xi_s, "xi_g": xi_s.copy(),
            "gamma_dF": g_dF, "gamma_dB": bp_ratio * g_dF,
            "gamma_sF": g_sF, "gamma_sB": bp_ratio * g_sF}


def train_flops_per_sample(cfg: dict, seq: int) -> float:
    return 3.0 * forward_flops(cfg, seq)
