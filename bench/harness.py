"""What every cell shares: finding a cell's files by name, the device and
its peaks, compile counts, host spans, and the result line.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix. ``configs/<config>.json`` holds the
configuration's sizes, ``traffic/<traffic>.json`` the job and the name of
the driver that runs it (``drivers/<driver>.py``), and each per-layer
metric has a reader ``metrics/<metric>.py``. Adding a cell, a
configuration or a metric adds files; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import sys
import time
from collections import defaultdict
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_module(path: Path):
    """The module in file ``path``, loaded once per process."""
    name = "bench._files." + str(path.resolve()).replace(".", "_") \
        .replace("/", ".").replace("-", "_")
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[name] = mod
    return sys.modules[name]


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list           # metric entries this cell reports, trace 0
    per_layer: list            # ... and with --trace 1
    bench_dir: Path = BENCH

    def driver(self):
        return load_module(self.bench_dir / "drivers"
                           / f"{self.traffic['driver']}.py")

    def reader(self, metric: str):
        return load_module(self.bench_dir / "metrics" / f"{metric}.py")


def _applies(metric: dict, cell: str, e2e_of_cell) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_of_cell is None or metric.get("moves") in e2e_of_cell


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    bench_dir = root / spec["paths"][0]
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload named {name!r}; "
                         f"known: {sorted(cells)}")
    w = cells[name]
    config = json.loads((bench_dir / "configs"
                         / f"{w['config']}.json").read_text())
    traffic = json.loads((bench_dir / "traffic"
                          / f"{w['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"] if _applies(m, name, None)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if _applies(m, name, e2e_names)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer,
                bench_dir)


def peak(kind: str, bench_dir: Path = BENCH) -> dict:
    """The published peaks of one chip of ``kind``; unknown is an error."""
    table = json.loads((bench_dir / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device_kind {kind!r} in peaks.json")
    return table[kind]


def require_chip(chips: int):
    """The devices of the run; exits non-zero on anything but enough TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        sys.stderr.write(f"bench: needs {chips} TPU chip(s); JAX found "
                         f"{len(devs)} {devs[0].platform!r} device(s)\n")
        raise SystemExit(3)
    return devs[:chips]


def device_info(devs) -> dict:
    peak_bytes = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak_bytes = max(peak_bytes, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak_bytes}


class CompileCounters:
    """Backend compiles (and their seconds) and persistent-cache hits,
    from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.compiles, self.compile_s, self.cache_hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Spans:
    """Host spans the harness puts around the program's calls: seconds
    per call by name, and, while tracing, a ``TraceAnnotation`` named
    ``bench.<name>`` so the trace reduction can say what the host was
    doing in each idle gap of the device."""

    def __init__(self, trace: bool):
        self.trace = trace
        self.seconds = defaultdict(list)

    def wrap(self, name: str, fn):
        if self.trace:
            from jax.profiler import TraceAnnotation
        else:
            TraceAnnotation = None

        def wrapped(*args, **kw):
            t0 = time.perf_counter()
            if TraceAnnotation is None:
                out = fn(*args, **kw)
            else:
                with TraceAnnotation("bench." + name):
                    out = fn(*args, **kw)
            self.seconds[name].append(time.perf_counter() - t0)
            return out
        return wrapped


def note(msg: str):
    sys.stderr.write(f"bench: {msg}\n")
    sys.stderr.flush()


def emit(result: dict, checks: dict):
    """Prints the numbers compared, each beside its limit, as the last
    lines on stderr, then the result line (``checks`` as its last key)
    as the last line on stdout."""
    for name, c in checks.items():
        sys.stderr.write(f"check {name} {c['value']!r} limit "
                         f"{c['limit']!r} {'ok' if c['ok'] else 'FAIL'}\n")
    sys.stderr.flush()
    line = dict(result)
    line["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                      for k, c in checks.items()}
    sys.stdout.write(json.dumps(line) + "\n")
    sys.stdout.flush()
