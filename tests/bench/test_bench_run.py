"""A run of the benchmark on the CPU at a test size: no result without a
TPU, the result line's schema, and ``correct`` false when the timed path
is broken underneath."""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402
from bench import run as bench_run  # noqa: E402

CELL = "tiny-lenet.quick"
SEED = 3_000_000_019        # more than 32 signed bits hold
PEAKS = {"cpu": {"bf16_flops_per_s": 1e12}}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A benchmark root holding the shipped files plus the fixture cell."""
    root = tmp_path_factory.mktemp("bench_root")
    shutil.copytree(ROOT / "bench", root / "bench")
    fx = Path(__file__).resolve().parent / "fixtures"
    for sub in ("configs", "traffic"):
        for f in (fx / sub).glob("*.json"):
            shutil.copy(f, root / "bench" / sub / f.name)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["workloads"] = [{"name": CELL, "config": "tiny-lenet",
                          "traffic": "quick", "chips": 1, "why": "fixture"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(autouse=True)
def small_images(monkeypatch):
    """The launcher's synthetic image set at 2000 training images: a
    test-size run; four devices draw 180 each from three classes."""
    import functools
    import repro.launch.train as launch
    monkeypatch.setattr(launch, "synthetic_mnist", functools.partial(
        launch.synthetic_mnist, n_train=2000, n_test=100))


def _run(root, trace=False):
    return bench_run.run_cell(CELL, SEED, 0.2, trace, time.monotonic(),
                              root=root, require_chip=False, peaks=PEAKS)


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "paper-lenet.gibbs", "--seed", "1", "--seconds", "1", "--trace",
         "0"], env=env, capture_output=True, text=True, timeout=120,
        cwd=ROOT)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert "{" not in proc.stdout


def test_result_line_schema(root, capsys):
    result, checks = _run(root, trace=True)
    harness.emit(result, checks)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert line["correct"] is True
    assert {"correct", "attempted", "failed", "metrics", "device",
            "breakdown"} <= set(line)
    assert line["attempted"] >= 2 and line["failed"] == 0
    dev = line["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes", "busy_s",
            "window_s"} <= set(dev)
    units = {m["name"]: m["unit"] for m in json.loads(
        (root / "BENCHMARK.json").read_text())["per_layer"]}
    assert line["metrics"]
    for name, m in line["metrics"].items():
        assert m["unit"] == units[name]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    for name, c in line["checks"].items():
        assert set(c) == {"value", "limit"}
        assert f"check {name} " in err
    assert err.strip().splitlines()[-1].startswith("check ")


def _state_unchanged(monkeypatch):
    from repro.core.cpsl import CPSL
    step = CPSL.cluster_step
    monkeypatch.setattr(CPSL, "cluster_step",
                        lambda self, s, b: (s, step(self, s, b)[1]))


def _half_batch(monkeypatch):
    import jax
    from repro.core.cpsl import CPSL
    step = CPSL.cluster_step
    monkeypatch.setattr(CPSL, "cluster_step", lambda self, s, b: step(
        self, s, jax.tree.map(lambda t: t[:, :t.shape[1] // 2], b)))


def _answer_altered(monkeypatch):
    from repro.train.trainer import CPSLTrainer
    plan = CPSLTrainer._plan_round

    def altered(self, v, rnd):
        clusters, xs, lat = plan(self, v, rnd)
        return clusters, xs, lat * 1.001

    monkeypatch.setattr(CPSLTrainer, "_plan_round", altered)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered],
                         ids=lambda f: f.__name__.strip("_"))
def test_broken_timed_path_is_not_correct(root, monkeypatch, fault):
    fault(monkeypatch)
    result, checks = _run(root)
    assert result["correct"] is False
    assert any(not c["ok"] for c in checks.values())
