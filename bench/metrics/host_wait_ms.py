"""CPSL round: host milliseconds per round blocked on the device, the
mean of the program's ``sync`` span (``float(loss)`` in
``CPSL.run_round``) over the window's rounds."""


def read(ctx):
    phases = [h["phase_s"] for h in ctx.get("history") or ()
              if "sync" in h.get("phase_s", {})]
    if not phases:
        return None
    return 1e3 * sum(p["sync"] for p in phases) / len(phases)
