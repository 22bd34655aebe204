"""Readings of the departures a guessed model would make, beside
``calibrate.py``'s (not run by the benchmark's own runs).

    python3 bench/fault_readings.py --workload <cell> --seeds 1,2,3 \
        [--faults name,...]

For each seed, in one process: the cell's set-up through the trainer (the
same recorded steps a run compares), then the numbers a run compares for

- ``program``: the system, against the plain reference;
- ``control``: the reference one precision below the configuration's;
- each of the configuration's ``faults`` (or those named): the reference
  with that switch of the configuration set (for DeepSeek-V2:
  renormalised top-k weights, plain rope, capacity dropping), in the
  system's place.

One JSON line per seed on stdout.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(cell, seed: int, faults=None) -> dict:
    """One seed's readings; ``faults``: the names to read (all the
    configuration's when None, none when empty)."""
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
    prog = {"loss": [s["loss"] for s in rec.steps[:drv.N_STEPS - 1]],
            "first": rec.first, "change": rec.change}
    out = {"seed": seed, "reference_s": time.monotonic() - t0,
           "program": drv.training_numbers(prog, ref)}
    ctrl = drv.replay(cfg, seed, rec.steps, numerics.BY_NAME[cfg["control"]])
    out["control"] = drv.training_numbers(ctrl, ref)
    names = cfg.get("faults", {}) if faults is None else faults
    for name in names:
        bad = drv.replay(dict(cfg, **cfg["faults"][name]), seed, rec.steps,
                         numerics.REF)
        out[name] = drv.training_numbers(bad, ref)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default=None,
                    help="comma-separated fault names; '' for none")
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
    faults = (None if args.faults is None
              else [f for f in args.faults.split(",") if f])
    for s in args.seeds.split(","):
        print(json.dumps(readings(cell, int(s), faults)), flush=True)


if __name__ == "__main__":
    main()
