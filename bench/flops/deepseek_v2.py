"""Operations and sizes of DeepSeek-V2 (MLA attention, a leading dense
layer, DeepSeekMoE layers) per sequence of ``seq`` tokens, from the
configuration file's keys.

- ``profile``: the per-cut constants of the paper's latency model, the
  arithmetic of ``repro.core.profile.lm_profile`` kept here so the
  yardstick cannot move with the program. It prices the wireless
  deployment: an MoE layer holds all ``published.n_routed_experts``
  experts' parameters and runs ``num_experts_per_tok + n_shared_experts``
  experts a token; attention counts the full square (scores over q.k of
  nope + rope width, and the weighted sum over v).
- ``train_flops_per_sample``: what this chip computes for one training
  sequence, forward plus a backward of twice the forward (the forward
  that rematerialisation repeats is not counted): the router over every
  expert, the held experts at k * held / E evaluations a token, the
  shared experts on every token, the head over the vocabulary slice.
- ``gmm_cost``: operations and bytes of the grouped matmuls over
  the held experts, for their roofline share.
"""
from __future__ import annotations

import numpy as np

PARAM_BITS = 32


def _mla(cfg: dict, seq: int):
    """(params, forward FLOPs) of one attention."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    params = (d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv)
              + H * dv * d)
    return params, 2 * seq * params + 2 * seq * seq * H * (dn + dr + dv)


def _swiglu(d: int, ff: int, seq: int):
    return 3 * d * ff, 2 * seq * 3 * d * ff


def _layers(cfg: dict):
    """True for each MoE layer, in order."""
    return [i >= cfg["first_k_dense_replace"]
            for i in range(cfg["num_hidden_layers"])]


def profile(cfg: dict, seq: int, bp_ratio: float = 2.0,
            act_bits: int = 16) -> dict:
    """Per-cut arrays (index v-1 for cut v), as ``dense.profile``."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    E = cfg["published"]["n_routed_experts"]
    k, ns = cfg["num_experts_per_tok"], cfg["n_shared_experts"]
    ff = cfg["moe_intermediate_size"]
    p_l, f_l = [], []
    for moe in _layers(cfg):
        ap, af = _mla(cfg, seq)
        if moe:
            fp = d * E + 3 * E * d * ff
            ffl = 2 * seq * 3 * (k + ns) * d * ff
        else:
            fp, ffl = _swiglu(d, cfg["intermediate_size"], seq)
        p_l.append(ap + fp + 2 * d)
        f_l.append(af + ffl)
    n = len(p_l)
    total = sum(f_l) + 2 * seq * d * V
    xi_d, g_dF = np.zeros(n), np.zeros(n)
    cum_p, cum_f = V * d, 0
    for v in range(1, n + 1):
        cum_p += p_l[v - 1]
        cum_f += f_l[v - 1]
        xi_d[v - 1] = cum_p * PARAM_BITS
        g_dF[v - 1] = cum_f
    xi_s = np.full(n, float(seq * d * act_bits))
    g_sF = np.maximum(total - g_dF, 0.0)
    return {"xi_d": xi_d, "xi_s": xi_s, "xi_g": xi_s.copy(),
            "gamma_dF": g_dF, "gamma_dB": bp_ratio * g_dF,
            "gamma_sF": g_sF, "gamma_sB": bp_ratio * g_sF}


def forward_flops(cfg: dict, seq: int) -> float:
    """This chip's forward FLOPs for one sequence."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    E = cfg["published"]["n_routed_experts"]
    k, ns = cfg["num_experts_per_tok"], cfg["n_shared_experts"]
    ff = cfg["moe_intermediate_size"]
    held = k * cfg["n_routed_experts"] / E       # evaluations a token
    total = 2 * seq * d * V
    for moe in _layers(cfg):
        total += _mla(cfg, seq)[1]
        if moe:
            total += 2 * seq * d * E + (held + ns) * _swiglu(d, ff, seq)[1]
        else:
            total += _swiglu(d, cfg["intermediate_size"], seq)[1]
    return float(total)


def train_flops_per_sample(cfg: dict, seq: int) -> float:
    return 3.0 * forward_flops(cfg, seq)


def gmm_cost(cfg: dict, routed: float, layer_calls: float,
             dtype_bytes: int = 2):
    """(FLOPs, bytes) of the grouped matmuls (gate, up, down over the held
    experts) of a training window: ``routed`` token choices on held
    experts in all (the ``moe_routed`` counter) over ``layer_calls`` MoE
    layer calls. A step makes four passes of each layer's three products:
    the forward, its recomputation under remat, and the backward's two
    (the rows' gradient and the weights'). A pass multiplies each routed
    row once by its expert's matrices, reads the held experts' weights
    once a layer call, and reads and writes each row's operands once."""
    d, ff = cfg["hidden_size"], cfg["moe_intermediate_size"]
    n, passes = cfg["n_routed_experts"], 4
    flops = passes * 3 * 2 * routed * d * ff
    moved = passes * dtype_bytes * (3 * n * d * ff * layer_calls
                                    + routed * 3 * (d + ff))
    return float(flops), float(moved)
