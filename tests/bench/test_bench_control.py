"""The control of ``correct`` at a test size: the plain reference
computed one precision below the configuration's (three bfloat16 passes
for the float32-at-highest LeNet, fp8 dots for the bfloat16 Qwen2), put
in the system's place, reads at least ten times what the system reads,
and the system passes the shipped limits. The fp8 control also fails
those limits here; the three-pass one does so only on the chip, at the
cell's own size, where the CPU's exact float32 dots are not the
reference's (``bench/calibrate.py`` gives those readings; PERF.md keeps
them)."""
import functools
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import calibrate, harness  # noqa: E402

FX = Path(__file__).resolve().parent / "fixtures"
SHIPPED = {"tiny-lenet": "paper-lenet", "tiny-qwen2": "qwen2-0.5b"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_root")
    shutil.copytree(ROOT / "bench", root / "bench")
    for sub in ("configs", "traffic"):
        for f in (FX / sub).glob("*.json"):
            shutil.copy(f, root / "bench" / sub / f.name)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["workloads"] = [{"name": f"{c}.quick", "config": c,
                          "traffic": "quick", "chips": 1, "why": "fixture"}
                         for c in SHIPPED]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.mark.parametrize("config", sorted(SHIPPED))
def test_control_fails_where_the_system_passes(root, monkeypatch, config):
    import repro.launch.train as launch
    monkeypatch.setattr(launch, "synthetic_mnist", functools.partial(
        launch.synthetic_mnist, n_train=2000, n_test=100))
    limits = json.loads((ROOT / "bench" / "configs"
                         / f"{SHIPPED[config]}.json").read_text())["limits"]
    cell = harness.load_cell(f"{config}.quick", root)
    assert cell.config["limits"] == limits
    r = calibrate.readings(cell, 3_000_000_021, faults=False)
    assert all(v <= limits[k] for k, v in r["program"].items()), r
    ratio = max(c / max(r["program"][k], 1e-9)
                for k, c in r["control"].items())
    assert ratio >= 10, r
    if config == "tiny-qwen2":
        assert any(v > limits[k] for k, v in r["control"].items()), r
