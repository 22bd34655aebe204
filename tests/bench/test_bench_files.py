"""``BENCHMARK.json`` names only files that exist, each file loads by
name, and a cell, configuration, traffic mix or per-layer metric is added
by adding files alone."""
import json
import re
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
EMPTY = {"rounds": 0, "samples": 0, "window_s": 1.0, "busy_s": None,
         "spans": {}, "flops_per_sample": 1.0, "peak_flops": 1.0,
         "memory_peak_bytes": 0}


def test_top_level_keys_and_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    cells = len(SPEC["workloads"])
    # a full check of 24 cells at this run length fits its 43200 s
    assert (2 + 14 * 24) * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    assert 1 <= cells <= 24
    for p in SPEC["paths"]:
        assert (ROOT / p).is_dir()


def test_names_units_bounds():
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in SPEC[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file_is_the_configuration(cfg):
    path = ROOT / cfg["file"]
    assert path.is_file() and cfg["file"].startswith(SPEC["paths"][0] + "/")
    data = json.loads(path.read_text())
    assert data["reduced"] == cfg["reduced"]
    assert (ROOT / SPEC["paths"][0] / "reference"
            / f"{data['reference']}.py").is_file()
    assert (ROOT / SPEC["paths"][0] / "flops" / f"{data['flops']}.py").is_file()


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_every_cell_loads_by_name(cell):
    c = harness.load_cell(cell["name"])
    assert c.config["name"] == cell["config"]
    assert c.driver().run
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in e2e
        assert c.reader(m["name"]).read(EMPTY) is None


def test_a_cell_is_added_by_files_alone(tmp_path):
    """A configuration, traffic mix and metric that no shipped file
    names, added as files and entries only."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    fx = Path(__file__).resolve().parent / "fixtures"
    shutil.copy(fx / "configs" / "tiny-lenet.json",
                tmp_path / "bench" / "configs" / "tiny-lenet.json")
    shutil.copy(fx / "traffic" / "quick.json",
                tmp_path / "bench" / "traffic" / "quick.json")
    (tmp_path / "bench" / "metrics" / "rounds_seen.py").write_text(
        "def read(ctx):\n    return ctx['rounds'] or None\n")
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append({"name": "tiny-lenet.quick",
                              "config": "tiny-lenet", "traffic": "quick",
                              "chips": 1, "why": "fixture"})
    spec["per_layer"].append({"name": "rounds_seen", "unit": "rounds",
                              "better": "higher", "source": "host_clock",
                              "layer": "trainer loop",
                              "moves": "samples_per_s",
                              "workloads": ["tiny-lenet.quick"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    c = harness.load_cell("tiny-lenet.quick", tmp_path)
    assert c.config["name"] == "tiny-lenet"
    assert c.traffic["resource"] == "gibbs"
    assert [m["name"] for m in c.per_layer] == ["rounds_seen"]
    assert c.reader("rounds_seen").read(dict(EMPTY, rounds=3)) == 3
    assert c.driver().run
