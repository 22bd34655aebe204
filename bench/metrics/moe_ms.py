"""MoE layer: device-busy milliseconds per round in the MoE layers'
scopes (router, permute, experts, combine, shared experts), forward and
backward, each busy moment charged to the innermost op covering it, from
the device trace (``bench/moe_trace.py``)."""

from bench.moe_trace import MOE_SCOPES


def read(ctx):
    s = ctx.get("moe_scopes")
    if not s or not ctx["rounds"] or not any(k in s for k in MOE_SCOPES):
        return None
    return 1e3 * sum(s.get(k, 0.0) for k in MOE_SCOPES) / ctx["rounds"]
