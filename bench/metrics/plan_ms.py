"""Planner: host milliseconds per round in ``CPSLTrainer._plan_round``
(network draw, clustering and spectrum), from the harness's span."""


def read(ctx):
    s = ctx["spans"].get("plan")
    return 1e3 * sum(s) / len(s) if s else None
