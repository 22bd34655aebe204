"""Planner: host microseconds per Alg. 3 call (``greedy_spectrum``, one
per cluster the Gibbs cache misses), the window's ``spectrum_s`` counter
over its ``spectrum_calls`` (``core/resource.py``)."""


def read(ctx):
    counts = [h["counts"] for h in ctx.get("history") or () if "counts" in h]
    calls = sum(c.get("spectrum_calls", 0) for c in counts)
    if not calls:
        return None
    return 1e6 * sum(c.get("spectrum_s", 0.0) for c in counts) / calls
