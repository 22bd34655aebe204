"""CPSL round: device-busy milliseconds per round, the union of the
device's op intervals in the traced window over the rounds in it."""


def read(ctx):
    if not ctx.get("busy_s") or not ctx["rounds"]:
        return None
    return 1e3 * ctx["busy_s"] / ctx["rounds"]
