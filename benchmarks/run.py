"""Benchmark harness (deliverable d): one benchmark per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # quick mode
    PYTHONPATH=src python -m benchmarks.run --full
    PYTHONPATH=src python -m benchmarks.run --only fig7_cut_layer

``--artifact PATH`` additionally appends one cumulative record per run —
``{"stamp": ..., "quick": ..., "benches": {name: {"wall_s", "ok"}}}`` —
to the JSON list at PATH, so successive CI runs accrete a timing history
in one file. The record is stamped from the required ``--stamp`` argument
(callers pass e.g. the CI run id or ``date -u``), never from the ambient
clock, so reruns are reproducible and artifacts diff cleanly.
"""
from __future__ import annotations

import argparse
import json
import os
import time
import traceback

from repro import compile_cache

from benchmarks import (bench_dynamics, bench_fleet, bench_planner,
                        bench_round, bench_rt, bench_scale, bench_simfleet,
                        fig5_training, fig6_cluster_size, fig7_cut_layer,
                        fig8_resource, roofline, table2_latency)

BENCHES = {
    "table2_latency": table2_latency.main,
    "fig7_cut_layer": fig7_cut_layer.main,
    "fig8_resource": fig8_resource.main,
    "fig8b_smoke": fig8_resource.smoke,
    "fig5_training": fig5_training.main,
    "fig6_cluster_size": fig6_cluster_size.main,
    "roofline": roofline.main,
    "bench_dynamics": bench_dynamics.main,
    "bench_planner": bench_planner.main,
    "bench_round": bench_round.main,
    "bench_fleet": bench_fleet.main,
    "bench_simfleet": bench_simfleet.main,
    "bench_rt": bench_rt.main,
    "bench_scale": bench_scale.main,
}


def _append_artifact(path: str, record: dict):
    history = []
    if os.path.exists(path):
        with open(path) as f:
            history = json.load(f)
        assert isinstance(history, list), f"{path} is not a JSON list"
    history.append(record)
    with open(path, "w") as f:
        json.dump(history, f, indent=2)
    print(f"artifact ({len(history)} run(s)) -> {path}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--artifact", default=None,
                    help="append this run's record to a cumulative JSON list")
    ap.add_argument("--stamp", default=None,
                    help="label for the --artifact record (CI run id, "
                         "date -u, ...); required with --artifact")
    args = ap.parse_args()
    if args.artifact and not args.stamp:
        ap.error("--artifact requires --stamp (no ambient-clock stamping)")
    compile_cache.enable()
    quick = not args.full
    names = [args.only] if args.only else list(BENCHES)
    failures = []
    record = {"stamp": args.stamp, "quick": quick, "benches": {}}
    for name in names:
        print(f"\n{'='*72}\n== {name} (paper {name.split('_')[0]})\n{'='*72}",
              flush=True)
        t0 = time.time()
        try:
            BENCHES[name](quick)
            wall = time.time() - t0
            record["benches"][name] = {"wall_s": round(wall, 3), "ok": True}
            print(f"-- {name} done in {wall:.1f}s", flush=True)
        except Exception:
            failures.append(name)
            record["benches"][name] = {"wall_s": round(time.time() - t0, 3),
                                       "ok": False}
            traceback.print_exc()
    if args.artifact:
        _append_artifact(args.artifact, record)
    if failures:
        raise SystemExit(f"benchmarks failed: {failures}")
    print("\nall benchmarks complete")


if __name__ == "__main__":
    main()
