"""Deployment-runtime loopback tests.

The contract: a multi-process loopback run reproduces the in-process
looped ``CPSL.run_round`` bit-exactly — same rng streams, same batch
index tables, same FedAvg — including under retries (dropped frames are
resent and deduplicated) and slow devices under the "wait" policy; a
device that fails to upload is excluded from FedAvg with exactly the
simulated-dropout semantics (weight 0, pre-cluster row); chaos runs
never hang (every wait is deadline-bounded).

These tests spawn real worker processes (jax re-imports per worker), so
each scenario uses the smallest deployment that exercises it.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.rt.device import pin_to_cpu
from repro.rt.faults import FaultRule
from repro.rt.orchestrator import (Orchestrator, RTConfig,
                                   loopback_reference, run_loopback)
from repro.rt.protocol import MsgType

STATE_KEYS = ("dev", "srv", "dev_opt", "srv_opt", "step")

# the in-process looped reference now lives next to the orchestrator
# (tests/test_rt_recovery.py and examples/rt_loopback.py share it)
reference_state = loopback_reference


def assert_state_bit_exact(got, ref):
    for key in STATE_KEYS:
        la, lb = jax.tree.leaves(got[key]), jax.tree.leaves(ref[key])
        assert len(la) == len(lb), key
        for a, b in zip(la, lb):
            assert a.dtype == b.dtype and a.shape == b.shape, key
            assert jnp.array_equal(a, b), \
                f"{key}: max diff {np.abs(np.asarray(a) - np.asarray(b)).max()}"


def round_records(records):
    return [r for r in records if r.get("kind") != "qos"]


def _cfg(**kw):
    base = dict(n_devices=2, cluster_size=2, rounds=1, local_epochs=1,
                batch=4, n_train=400, n_test=64, samples_per_device=60,
                phase_timeout_s=60.0)
    base.update(kw)
    return RTConfig(**base)


def test_loopback_bit_exact_two_clusters():
    """THE contract: 2 clusters x 2 devices, L=2, 2 rounds — the
    multi-process runtime == the in-process reference, bit for bit
    (params, both optimizer states, step counter)."""
    cfg = _cfg(n_devices=4, rounds=2, local_epochs=2,
               trace_path=None)
    state, records = run_loopback(cfg)
    ref, ref_loss = reference_state(cfg)
    assert_state_bit_exact(state, ref)

    rounds = round_records(records)
    assert [r["round"] for r in rounds] == [0, 1]
    assert rounds[-1]["loss"] == pytest.approx(ref_loss, abs=0)
    for r in rounds:
        assert r["source"] == "rt" and r["dropped"] == []
        assert r["wall_s"] > 0 and r["planned_latency_s"] > 0
        assert r["clusters_global"] == [[0, 1], [2, 3]]
    qos = [r for r in records if r.get("kind") == "qos"]
    phases = {q["phase"] for q in qos}
    assert {"fwd", "bwd", "grad_wait", "upload", "server",
            "round"} <= phases


def test_retry_recovers_bit_exact():
    """A dropped SMASHED frame is retransmitted after the rpc timeout
    and the run still matches the reference exactly — retries are
    invisible to the numerics."""
    cfg = _cfg(rpc_timeout_s=0.75, backoff_s=0.1,
               faults={1: [FaultRule("drop", times=1,
                                     msg_types=(int(MsgType.SMASHED),))]})
    state, records = run_loopback(cfg)
    ref, _ = reference_state(cfg)
    assert_state_bit_exact(state, ref)
    assert round_records(records)[0]["dropped"] == []
    # the recovery is visible in QoS: device 1's upload took >1 attempt
    ups = [q for q in records if q.get("kind") == "qos"
           and q["phase"] == "upload" and q["device"] == 1]
    assert any(q.get("attempt", 0) > 0 for q in ups)


def test_failed_upload_matches_simulated_dropout():
    """A device whose AGG upload never arrives is excluded from FedAvg
    with EXACTLY the simulated straggler-dropout semantics: eq.-8 weight
    0, everything else unchanged — bit-exact vs the reference run with
    that device's data-size weight zeroed."""
    cfg = _cfg(phase_timeout_s=4.0, rpc_timeout_s=0.5, retries=2,
               backoff_s=0.1,
               faults={1: [FaultRule("drop",
                                     msg_types=(int(MsgType.AGG),))]})
    state, records = run_loopback(cfg)
    ref, _ = reference_state(cfg, zero_weight=(0, 1))
    assert_state_bit_exact(state, ref)
    assert round_records(records)[0]["dropped"] == [1]


def test_disconnect_mid_round_no_hang():
    """A device that hard-disconnects mid-round is detected (reader EOF),
    the epoch runs masked without it, and the run completes — no hangs,
    bookkeeping records the drop."""
    cfg = _cfg(rounds=2,
               faults={1: [FaultRule("disconnect", after=1,
                                     msg_types=(int(MsgType.SMASHED),))]})
    state, records = run_loopback(cfg)
    rounds = round_records(records)
    assert [r["round"] for r in rounds] == [0, 1]
    assert rounds[0]["dropped"] == []       # clean round before the fault
    assert rounds[1]["dropped"] == [1]
    for leaf in jax.tree.leaves(state["dev"]) + jax.tree.leaves(state["srv"]):
        assert bool(jnp.all(jnp.isfinite(leaf)))
    # round 0 (pre-fault) is still the bit-exact reference round
    ref1, _ = reference_state(_cfg(rounds=1))
    assert float(rounds[0]["loss"]) == float(
        reference_state(_cfg(rounds=1))[1])


def test_wait_policy_rides_out_slow_device():
    """policy="wait": a slow device (injected compute delay) stalls the
    cluster instead of being dropped — still bit-exact, and the round's
    measured wall-clock shows the wait."""
    cfg = _cfg(straggler_policy="wait",
               faults={1: [FaultRule("slow", delay_s=1.2)]})
    state, records = run_loopback(cfg)
    ref, _ = reference_state(cfg)
    assert_state_bit_exact(state, ref)
    rec = round_records(records)[0]
    assert rec["dropped"] == []
    assert rec["wall_s"] > 1.0


def test_workers_pinned_to_cpu():
    """Every spawned worker runs JAX on the CPU — it never opens the
    accelerator the server process holds — and says so in READY."""
    orch = Orchestrator(_cfg(trace_path=None))
    try:
        orch.start()
        orch.run()
        assert orch.server.platforms == {0: "cpu", 1: "cpu"}
    finally:
        orch.stop()


def test_pin_to_cpu_refuses_after_backend_init():
    """The pin must precede the first backend: once one is up, JAX has
    fixed its platforms and the pin raises instead of silently failing."""
    jax.devices()
    with pytest.raises(RuntimeError, match="already initialised"):
        pin_to_cpu()
