"""Readings that the limits of ``correct`` are set from (not run by the
benchmark's own runs).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3

For each seed, in one process: the cell's set-up through the trainer
(the same recorded steps and plans a run compares), then the numbers a
run compares for

- ``program``: the system, against the plain reference;
- ``control``: the reference computed one precision below the
  configuration's (``control`` in its file), in the system's place;
- ``half_batch``: the reference leaving out half of every device's batch
  (the mean over the rest), in the system's place;
- ``frozen``: the reference whose steps return their state unchanged
  (learning rates 0), in the system's place;
- ``plan_fault``: each recorded plan with its priced latency 0.1% off
  (an answer altered where it is made); reads 1e-3 in
  ``plan_price_gap`` by construction.

A step that returns its state unchanged reads 1 in ``grad_gap`` and
``change_gap`` by their definition and needs no run. One JSON line per
seed on stdout.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def plan_fault(plans):
    return [dict(p, lat=p["lat"] * 1.001) for p in plans]


def readings(cell, seed: int, faults: bool = True) -> dict:
    """One seed's readings; ``faults=False`` leaves out the half batch
    and the altered plans."""
    from bench.drivers import trainer as drv
    from bench.reference import numerics
    cell.traffic = dict(cell.traffic, timing_rounds=0)
    with tempfile.TemporaryDirectory() as tmp:
        trainer, rec, _ = drv.setup(cell, seed, str(Path(tmp) / "ckpt"))
        rec.close()
        del trainer
    drv.release_program()
    cfg = cell.config
    t0 = time.monotonic()
    ref = drv.replay(cfg, seed, rec.steps, numerics.REF)
    ref_s = time.monotonic() - t0
    prog = {"loss": [s["loss"] for s in rec.steps[:drv.N_STEPS - 1]],
            "first": rec.first, "change": rec.change}
    out = {"seed": seed, "reference_s": ref_s,
           "program": dict(drv.training_numbers(prog, ref),
                           **drv.plan_numbers(cfg, cell.traffic, rec.plans))}
    ctrl = drv.replay(cfg, seed, rec.steps, numerics.BY_NAME[cfg["control"]])
    out["control"] = drv.training_numbers(ctrl, ref)
    out["losses"] = {"program": prog["loss"], "reference": ref["loss"]}
    if not faults:
        return out
    half = drv.replay(cfg, seed, rec.steps, numerics.REF, halve=True)
    out["half_batch"] = drv.training_numbers(half, ref)
    dep = dict(cfg["deployment"], lr_device=0.0, lr_server=0.0)
    frozen = drv.replay(dict(cfg, deployment=dep), seed, rec.steps,
                        numerics.REF)
    out["frozen"] = drv.training_numbers(frozen, ref)
    out["plan_fault"] = drv.plan_numbers(cfg, cell.traffic,
                                         plan_fault(rec.plans))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--cpu", action="store_true",
                    help="run without a TPU (rehearsal only)")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from repro import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import harness
    cell = harness.load_cell(args.workload)
    if not args.cpu:
        harness.require_chip(cell.chips)
    for s in args.seeds.split(","):
        print(json.dumps(readings(cell, int(s))), flush=True)


if __name__ == "__main__":
    main()
