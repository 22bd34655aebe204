"""The trace reduction: busy union, idle share and what the host was
doing in each idle gap, on hand-made events and on a trace recorded on
a TPU v5e (``fixtures/v5e_probe.xplane.pb``)."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "fixtures" / "v5e_probe.xplane.pb"


def test_reduce_hand_made_events():
    host, dev = "/host:CPU", "/device:TPU:0"
    events = [
        (host, "python", "bench.window", 0.0, 10.0),
        (host, "python", "bench.round", 0.0, 5.0),
        (host, "python", "bench.plan", 0.0, 2.0),
        (host, "python", "bench.step", 2.0, 2.5),
        (dev, "XLA Ops", "fusion.1", 2.5, 4.0),
        (dev, "XLA Ops", "dot.2", 3.5, 4.5),        # overlaps the fusion
        (dev, "XLA Modules", "jit_step", 2.5, 4.5),  # not an op: left out
        (dev, "XLA Ops", "fusion.1", 6.0, 7.0),
        (dev, "XLA Ops", "fusion.3", 11.0, 12.0),   # after the window
    ]
    r = trace.reduce(events)
    assert r["window_s"] == 10.0 and r["devices"] == 1
    assert r["busy_s"] == pytest.approx(3.0)      # [2.5, 4.5] + [6, 7]
    assert dict(r["device_ops"]) == pytest.approx({"fusion.1": 2.5,
                                                   "dot.2": 1.0,
                                                   "fusion.3": 0.0})
    # idle [0, 2.5], [4.5, 6], [7, 10] by the innermost open host span
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"plan": 2.0, "step": 0.5, "round": 0.5, "outside spans": 4.0})


def test_reduce_without_device_events_reads_no_busy_time():
    r = trace.reduce([("/host:CPU", "python", "bench.window", 0.0, 1.0)])
    assert r["busy_s"] == 0.0 and r["devices"] == 0


def test_reduce_recorded_v5e_trace():
    """Three rounds of a 4 ms host span ('plan'), a jitted matmul chain
    ('step') and a second jit, recorded on one TPU v5e."""
    r = trace.reduce(trace.load(str(RECORDED)))
    assert r["devices"] == 1
    assert 0.0 < r["busy_s"] < r["window_s"] < 1.0
    gaps = dict(r["idle_gaps"])
    assert gaps["plan"] >= 3 * 0.004 * 0.9
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert r["device_ops"][0][1] > 0
