"""Split model: the whole round's share of the chip's bf16 peak, in %.
The forward and backward FLOPs one sample needs (``bench/flops``, no
recomputation counted) times the samples the traced window trained,
over the window's seconds times the peak (``bench/peaks.json``)."""


def read(ctx):
    if not ctx.get("busy_s") or not ctx["samples"]:
        return None
    return 100.0 * ctx["flops_per_sample"] * ctx["samples"] / (
        ctx["window_s"] * ctx["peak_flops"])
