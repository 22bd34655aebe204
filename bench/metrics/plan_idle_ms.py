"""Planner: device-idle milliseconds per round while the program's
``cpsl.plan`` span (or its children ``network``, ``cluster``) was the
innermost open span on the host, from the device trace."""

PLAN = ("plan", "network", "cluster")


def read(ctx):
    gaps = ctx.get("program_idle_gaps") or {}
    if not ctx["rounds"] or not any(k in gaps for k in PLAN):
        return None
    return 1e3 * sum(gaps.get(k, 0.0) for k in PLAN) / ctx["rounds"]
