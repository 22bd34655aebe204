"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the TPU it is started on and
prints, as the last line of stdout, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device`` and, traced,
``breakdown``; ``checks`` (each number compared, beside its limit) comes
last. The same numbers are the last lines of stderr. Without a TPU, or
with fewer chips than the cell asks for, it exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             t_start: float, root: Path = ROOT, require_chip: bool = True,
             peaks=None):
    """(result line, checks) of one run of cell ``name``."""
    from bench import harness
    cell = harness.load_cell(name, root)
    if require_chip:
        devs = harness.require_chip(cell.chips)
    else:
        import jax
        devs = jax.devices()[:cell.chips]
    return cell.driver().run(cell, seed, seconds, trace, t_start, devs,
                             peaks=peaks)


def main(argv=None):
    args = parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        sys.stderr.write(f"bench: no system under test at {ROOT / 'src'}\n")
        raise SystemExit(2)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from repro import compile_cache
    compile_cache.enable()
    # every program into the cache, so a cell's later runs compile nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import harness
    result, checks = run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), T_START)
    harness.emit(result, checks)


if __name__ == "__main__":
    main()
