"""CPSL round: host milliseconds per round spent handing the cluster
steps and FedAvg calls to the device, the mean of the program's ``step``
plus ``fedavg`` spans over the window's rounds."""


def read(ctx):
    phases = [h["phase_s"] for h in ctx.get("history") or ()
              if "step" in h.get("phase_s", {})]
    if not phases:
        return None
    return 1e3 * sum(p["step"] + p.get("fedavg", 0.0)
                     for p in phases) / len(phases)
