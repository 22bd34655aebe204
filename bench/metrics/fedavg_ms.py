"""CPSL round: device-busy milliseconds per round in ops of the
program's ``fedavg`` scope (eq. 8), each busy moment charged to the
innermost op covering it, from the device trace."""

SCOPE = "fedavg"


def read(ctx):
    s = (ctx.get("device_scopes") or {}).get(SCOPE)
    if s is None or not ctx["rounds"]:
        return None
    return 1e3 * s / ctx["rounds"]
