"""Kernels: the grouped matmuls' share of their roofline, in %: the
least time the chip could take for their operations and bytes
(``gmm_cost`` of the configuration's ``flops`` module, from the
``moe_routed`` counter), the larger of operations over the bf16 peak and
bytes over the HBM bandwidth, over their device time in the trace
(``bench/moe_trace.py``)."""


def read(ctx):
    g = ctx.get("gmm")
    if not g or not g["seconds"] or not ctx.get("peak_bytes_per_s"):
        return None
    floor = max(g["flops"] / ctx["peak_flops"],
                g["bytes"] / ctx["peak_bytes_per_s"])
    return 100.0 * floor / g["seconds"]
