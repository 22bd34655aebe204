"""Split model: device-busy milliseconds per round in ops of the
program's ``server_side`` scope (the server model and its loss, forward
and backward), each busy moment charged to the innermost op covering it,
from the device trace."""

SCOPE = "server_side"


def read(ctx):
    s = (ctx.get("device_scopes") or {}).get(SCOPE)
    if s is None or not ctx["rounds"]:
        return None
    return 1e3 * s / ctx["rounds"]
