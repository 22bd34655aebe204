"""The trace reduction by the program's own names (``bench/program_trace``):
idle gaps by ``cpsl.*`` span, device time by ``jax.named_scope`` scope,
op paths read from a trace recorded on a TPU v5e, and the readers of the
program's spans, counters and scopes on a traced CPU run."""
import json
import shutil
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, program_trace, trace  # noqa: E402
from bench import run as bench_run  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "fixtures" / "v5e_probe.xplane.pb"
HOST, DEV = "/host:CPU", "/device:TPU:0"
READERS = ("spectrum_us", "plan_idle_ms", "host_wait_ms", "dispatch_ms",
           "server_side_ms", "device_side_ms", "update_ms", "fedavg_ms")


def _events():
    return [
        (HOST, "python", "bench.window", 0.0, 10.0),
        (HOST, "python", "bench.round", 0.0, 5.0),
        (HOST, "python", "cpsl.round", 0.0, 5.0),
        (HOST, "python", "bench.plan", 0.0, 2.0),
        (HOST, "python", "cpsl.plan", 0.0, 2.0),
        (HOST, "python", "cpsl.cluster", 0.5, 2.0),
        (HOST, "python", "bench.step", 2.0, 2.5),
        (HOST, "python", "cpsl.step", 2.0, 2.5),
        (HOST, "python", "cpsl.sync", 4.5, 5.0),
        (DEV, "XLA Ops", "fusion.1", 2.5, 4.0),
        (DEV, "XLA Ops", "dot.2", 3.5, 4.5),
        (DEV, "XLA Modules", "jit_step", 2.5, 4.5),
        (DEV, "XLA Ops", "fusion.1", 6.0, 7.0),
        (DEV, "XLA Ops", "fusion.3", 11.0, 12.0),
    ]


def test_program_idle_gaps_beside_bench_spans():
    events = _events()
    gaps = program_trace.program_idle_gaps(events)
    # idle [0, 2.5], [4.5, 6], [7, 10] by the innermost open cpsl span
    assert gaps == pytest.approx({"plan": 0.5, "cluster": 1.5, "step": 0.5,
                                  "sync": 0.5, "outside spans": 4.0})
    r = trace.reduce(events)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    # the harness's own labels are as they were without the cpsl spans
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"plan": 2.0, "step": 0.5, "round": 0.5, "outside spans": 4.0})


@pytest.mark.parametrize("tf_op, scope", [
    ("jit(_fused_step)/jit(main)/jvp(device_side)/conv_general_dilated",
     "device_side"),
    ("jit(_fused_step)/transpose(jvp(server_side))/while/body/dot_general:",
     "server_side"),
    ("jit(_fused_step)/update/sub", "update"),
    ("jit(_fedavg)/fedavg/dot_general", "fedavg"),
    ("jit(_run_round_fused)/while/body/fedavg/mul", "fedavg"),
    ("jit(f)/jvp(device_side)/dynamic-update-slice:update", "device_side"),
    ("jit(stack)/concatenate", "unscoped"),
    ("jit(f)/update", "unscoped"),
    ("", "unscoped"),
])
def test_scope_of_op_path(tf_op, scope):
    assert program_trace.scope_of(tf_op) == scope


def test_device_scopes_charge_each_moment_once():
    """A ``while`` (server side) holds two ops of its body, one of them a
    device-side op; an unscoped copy overlaps the while's end."""
    ops = {DEV: [
        ("jit(f)/jvp(server_side)/while", 1.0, 5.0),
        ("jit(f)/jvp(server_side)/while/body/dot_general", 1.5, 2.5),
        ("jit(f)/jvp(device_side)/conv", 3.0, 4.0),
        ("", 4.5, 6.0),
        ("jit(f)/update/sub", 6.5, 7.0),
        ("jit(f)/update/sub", 9.5, 12.0),          # runs past the window
    ]}
    got = program_trace.device_scopes(ops, 0.0, 10.0)
    # server side: [1, 3] and [4, 4.5]; the copy takes [4.5, 6]
    assert got == pytest.approx({"server_side": 2.5, "device_side": 1.0,
                                 "unscoped": 1.5, "update": 1.0})
    events = [(DEV, "XLA Ops", op or "copy", s, e)
              for op, s, e in ops[DEV]] + [(HOST, "python", "bench.window",
                                            0.0, 10.0)]
    assert sum(got.values()) == pytest.approx(trace.reduce(events)["busy_s"])


def test_recorded_v5e_ops_read_their_paths():
    """The fixture's fusions carry the path of the JAX op they came from,
    and the hand-read XSpace gives the very events ``trace.load`` does."""
    ops = program_trace.device_ops(str(RECORDED))
    events = trace.load(str(RECORDED))
    devs = trace.device_events(events)
    assert set(ops) == set(devs) == {DEV}
    assert [(s, e) for _, s, e in ops[DEV]] == \
        [(s, e) for _, s, e in devs[DEV]]
    paths = {}
    for (tf_op, _, _), (name, _, _) in zip(ops[DEV], devs[DEV]):
        paths.setdefault(name.split(" ")[0], set()).add(tf_op)
    assert paths["convolution_tanh_fusion"] == {"jit(<lambda>)/dot_general:"}
    assert paths["fusion"] == {"jit(<lambda>)/dot_general:"}
    assert paths["multiply_add_fusion"] == {"jit(<lambda>)/add:"}
    lo, hi = program_trace.window(events)
    scopes = program_trace.device_scopes(ops, lo, hi)
    busy = trace.reduce(events)["busy_s"]
    assert scopes == pytest.approx({"unscoped": busy})


def test_recorded_v5e_paths_agree_with_xprof():
    """``xprof``'s own trace viewer reads the same path for every op."""
    from xprof.convert import raw_to_tool_data
    out = raw_to_tool_data.xspace_to_tool_data([str(RECORDED)],
                                               "trace_viewer", {})[0]
    viewer = json.loads(out)["traceEvents"]
    want = sorted(e["args"].get("tf_op", "") for e in viewer
                  if e.get("ph") == "X" and e.get("pid") == 3
                  and e.get("tid") == 3)
    got = sorted(op for op, _, _ in
                 program_trace.device_ops(str(RECORDED))[DEV])
    assert got == want and len(got) == 15


# -- a traced run on the CPU -------------------------------------------------

CELL = "tiny-lenet.quick"
SEED = 3_000_000_037
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


def test_traced_cpu_run_reads_program_spans(root, monkeypatch):
    """Through ``run_cell``, traced: the window's round records carry the
    program's spans and counters, and the readers of the host-side ones
    read them. The device-trace readers read nothing on a CPU."""
    import functools
    import repro.launch.train as launch
    from repro.train.trainer import CPSLTrainer
    monkeypatch.setattr(launch, "synthetic_mnist", functools.partial(
        launch.synthetic_mnist, n_train=2000, n_test=100))
    histories, found = [], {}
    run = CPSLTrainer.run

    def keep_history(self, *a, **kw):
        histories.append(self.history)
        return run(self, *a, **kw)

    load = trace.load

    def keep_trace(path):
        events = load(path)
        found.update(events=events, ops=program_trace.device_ops(path))
        return events

    monkeypatch.setattr(CPSLTrainer, "run", keep_history)
    monkeypatch.setattr(trace, "load", keep_trace)
    result, checks = bench_run.run_cell(CELL, SEED, 0.2, True,
                                        time.monotonic(), root=root,
                                        require_chip=False, peaks=PEAKS)
    assert result["correct"] is True
    rounds = result["attempted"]
    history = histories[-1][-rounds:]
    assert all(h["counts"]["syncs"] == 1 for h in history)
    assert any(n.startswith("cpsl.plan") for _, _, n, _, _ in found["events"])
    ctx = {"rounds": rounds, "window_s": result["device"]["window_s"]}
    ctx.update(program_trace.context(found["events"], found["ops"],
                                     history))
    cell = harness.load_cell(CELL, root)
    got = {name: cell.reader(name).read(ctx) for name in READERS}
    for name in ("spectrum_us", "host_wait_ms", "dispatch_ms"):
        assert got[name] > 0, name
    assert got["host_wait_ms"] < 1e3 * result["device"]["window_s"]
