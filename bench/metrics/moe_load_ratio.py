"""MoE layer: the largest held expert's token choices in one layer call
over the mean a held expert gets in one, over the window, from the
program's ``moe_routed`` and ``moe_load_max`` counters
(``bench/moe_trace.py``); 1 is an even load."""


def read(ctx):
    return (ctx.get("moe") or {}).get("load_ratio")
