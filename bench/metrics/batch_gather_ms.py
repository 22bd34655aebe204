"""Input pipeline: host milliseconds per round spent drawing the
clusters' batches (``cluster_batch``), from the harness's span."""


def read(ctx):
    s = ctx["spans"].get("gather")
    return 1e3 * sum(s) / ctx["rounds"] if s and ctx["rounds"] else None
