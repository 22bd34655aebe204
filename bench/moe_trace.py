"""Reduction of a traced training window to what the MoE readers read
(``metrics/moe_ms.py``, ``gmm_roofline.py``, ``moe_load_ratio.py``),
beside ``program_trace`` (which it leaves as it is).

- Device time by the MoE layer's ``jax.named_scope`` scopes
  (``models/common.py``: ``moe_router``, ``moe_permute``,
  ``moe_experts``, ``moe_combine``, ``moe_shared``, and ``mla`` for the
  attention), each busy moment charged once, to the innermost op
  covering it, as ``program_trace.device_scopes`` charges it.
- The grouped matmuls' device time: ops whose own name (the last part of
  the path) is a ragged dot, with their operations and bytes from the
  configuration's ``flops`` module (``gmm_cost``) and the window's
  ``moe_routed`` counter. On the TPU, XLA's ragged-dot kernels (and the
  kernel that tiles their groups) carry its own names,
  ``ragged-dot-none`` and ``ragged-dot-metadata``, and no JAX path: they
  are the experts' products, charged to ``moe_experts``.
- The load ratio: the largest held expert's token choices in one layer
  call (``moe_load_max``, the window's largest) over the mean a held
  expert gets in one (``moe_routed`` over held experts and layer calls).
"""
from __future__ import annotations

import re
from collections import defaultdict

from bench import harness, program_trace

MOE_SCOPES = ("moe_router", "moe_permute", "moe_experts", "moe_combine",
              "moe_shared")
SCOPES = ("mla",) + MOE_SCOPES
GMM = "gmm"
_GMM_OP = re.compile(r"ragged[-_]dot")
_WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")


def is_gmm(tf_op: str) -> bool:
    return bool(_GMM_OP.match((tf_op or "").split("/")[-1]))


def scope_of(tf_op: str) -> str:
    """The innermost of ``SCOPES`` in an op's path, its own name left
    out (a grouped matmul: ``moe_experts``); ``other`` where none is
    named."""
    if is_gmm(tf_op):
        return "moe_experts"
    found = [w for part in (tf_op or "").split("/")[:-1]
             for w in _WORD.findall(part) if w in SCOPES]
    return found[-1] if found else "other"


def charge(ops, lo: float, hi: float, label) -> dict:
    """{label(tf_op): device-busy seconds} in [lo, hi], averaged over the
    devices; where ops overlap the moment goes to the innermost (the one
    that started last; of two that started together, the one that ends
    first)."""
    out = defaultdict(float)
    for evs in ops.values():
        iv = [(max(s, lo), min(e, hi), label(op)) for op, s, e in evs
              if e > lo and s < hi and e > s]
        pts = sorted([(s, 1, i) for i, (s, _, _) in enumerate(iv)]
                     + [(e, 0, i) for i, (_, e, _) in enumerate(iv)])
        active, t = set(), lo
        for when, opening, i in pts:
            if active and when > t:
                inner = max(active, key=lambda j: (iv[j][0], -iv[j][1], j))
                out[iv[inner][2]] += (when - t) / len(ops)
            t = when
            if opening:
                active.add(i)
            else:
                active.discard(i)
    return dict(out)


def counters(history, cfg: dict) -> dict:
    """The window's MoE counters, and its MoE layer calls, from the round
    records (``counts``); empty where the program counts none."""
    rounds = [h.get("counts") or {} for h in history]
    if not rounds or not all("moe_routed" in c for c in rounds):
        return {}
    dep = cfg["deployment"]
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    calls = len(rounds) * dep["n_clusters"] * dep["local_epochs"] * layers
    routed = sum(c["moe_routed"] for c in rounds)
    load_max = max(c["moe_load_max"] for c in rounds)
    mean = routed / (calls * cfg["n_routed_experts"])
    return {"routed": routed, "layer_calls": calls, "load_max": load_max,
            "load_ratio": load_max / mean if mean else None}


def context(events, ops, history, cfg: dict) -> dict:
    """What the MoE readers take from a traced window: ``events``:
    ``trace.load(path)``; ``ops``: ``program_trace.device_ops(path)``;
    ``history``: the window's round records; ``cfg``: the cell's
    configuration."""
    lo, hi = program_trace.window(events)
    out = {"moe_scopes": charge(ops, lo, hi, scope_of),
           "moe": counters(history, cfg)}
    gmm_s = charge(ops, lo, hi,
                   lambda op: GMM if is_gmm(op) else "other").get(GMM)
    moe = out["moe"]
    if gmm_s and moe:
        flops = harness.load_module(harness.BENCH / "flops"
                                    / f"{cfg['flops']}.py")
        f, b = flops.gmm_cost(cfg, moe["routed"], moe["layer_calls"])
        out["gmm"] = {"seconds": gmm_s, "flops": f, "bytes": b}
    return out
