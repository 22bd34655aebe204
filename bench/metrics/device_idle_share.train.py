"""Device: share of the traced window, in %, in which no op ran on the
device (1 - busy union / window), in the training cells."""


def read(ctx):
    if not ctx.get("busy_s"):
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
