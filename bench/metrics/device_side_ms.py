"""Split model: device-busy milliseconds per round in ops of the
program's ``device_side`` scope (the device models, forward and
backward), each busy moment charged to the innermost op covering it, from
the device trace."""

SCOPE = "device_side"


def read(ctx):
    s = (ctx.get("device_scopes") or {}).get(SCOPE)
    if s is None or not ctx["rounds"]:
        return None
    return 1e3 * s / ctx["rounds"]
