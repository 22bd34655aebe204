"""repro.sim — dynamics process, batched evaluators, two-timescale
controller, and the end-to-end engine (JSONL trace recompute)."""
import json

import jax
import numpy as np
import pytest

from repro.configs.base import CPSLConfig, SimCfg
from repro.core import latency as lt
from repro.core import profile as pf
from repro.core import resource as rs
from repro.core.channel import NetworkCfg, device_means, sample_network
from repro.sim.controller import TwoTimescaleController, balanced_sizes
from repro.sim.dynamics import DynamicsCfg, NetworkProcess
from repro.sim.engine import SimEngine, recompute_trace_latencies

PROF = pf.lenet_profile()


def _net(n=6, seed=0):
    ncfg = NetworkCfg(n_devices=n, n_subcarriers=2 * n)
    return sample_network(ncfg, *device_means(ncfg, seed),
                          np.random.default_rng(seed)), ncfg


# --------------------------------------------------------------------------
# dynamics
# --------------------------------------------------------------------------

def test_gauss_markov_stationary_moments():
    """AR(1) with sqrt(1-rho^2) innovation keeps the static model's
    N(mu, sigma^2) stationary law."""
    ncfg = NetworkCfg(n_devices=4, homogeneous=True)
    proc = NetworkProcess(ncfg, DynamicsCfg(rho_snr=0.8, rho_f=0.8, seed=1))
    snrs = []
    for _ in range(4000):
        proc.evolve()
        snrs.append(proc.snr_db.copy())
    snrs = np.array(snrs)
    assert abs(snrs.mean() - ncfg.snr_homog_db) < 0.2
    assert abs(snrs.std() - ncfg.snr_sigma_db) < 0.2


def test_gauss_markov_correlation_orders_with_rho():
    """Higher rho => higher lag-1 autocorrelation; rho=0 ~ i.i.d."""
    def lag1(rho):
        ncfg = NetworkCfg(n_devices=1, homogeneous=True)
        proc = NetworkProcess(ncfg, DynamicsCfg(rho_snr=rho, seed=3))
        xs = []
        for _ in range(3000):
            proc.evolve()
            xs.append(proc.snr_db[0])
        xs = np.array(xs) - np.mean(xs)
        return float(np.dot(xs[:-1], xs[1:]) / np.dot(xs, xs))

    c0, c9 = lag1(0.0), lag1(0.9)
    assert abs(c0) < 0.1
    assert c9 > 0.8


def test_forced_departure_and_arrival():
    ncfg = NetworkCfg(n_devices=4)
    proc = NetworkProcess(ncfg, DynamicsCfg(
        forced_departures={0: (1,)}, p_arrive=1.0, seed=0))
    ev = proc.sample_departures(0)
    assert [e.kind for e in ev] == ["depart"] and ev[0].device == 1
    assert proc.n_active == 3
    net, ids = proc.snapshot()
    assert 1 not in ids and len(net.f) == 3
    ev = proc.sample_arrivals()
    assert [e.kind for e in ev] == ["arrive"] and ev[0].device == 4
    assert proc.n_active == 4 and proc.n_devices == 5


def test_min_devices_floor():
    ncfg = NetworkCfg(n_devices=3)
    proc = NetworkProcess(ncfg, DynamicsCfg(
        p_depart=1.0, min_devices=2, seed=0))
    for _ in range(5):
        proc.sample_departures()
    assert proc.n_active == 2


def test_energy_depletion_departs_device():
    ncfg = NetworkCfg(n_devices=3)
    proc = NetworkProcess(ncfg, DynamicsCfg(
        energy_budget_j=1.0, min_devices=1, seed=0))
    ev = proc.consume([0, 1], [0.4, 2.0])
    assert [e.kind for e in ev] == ["energy_depleted"] and ev[0].device == 1
    assert proc.n_active == 2
    ev = proc.consume([0], [0.7])
    assert ev and ev[0].device == 0
    assert proc.n_active == 1


def test_energy_pinned_departure_carries_cause():
    """Regression: a floor-pinned, already-depleted device that finally
    leaves used to emit a bare "depart" — indistinguishable from churn,
    so energy-driven departures were undercounted. The departure now
    carries cause="energy_depleted"."""
    ncfg = NetworkCfg(n_devices=2)
    proc = NetworkProcess(ncfg, DynamicsCfg(
        energy_budget_j=1.0, min_devices=2, p_arrive=1.0, seed=0))
    ev = proc.consume([0], [2.0])
    # pinned at the floor: depletion recorded, device stays active
    assert [e.kind for e in ev] == ["energy_depleted"]
    assert proc.n_active == 2 and proc.energy[0] == 0.0
    # an arrival lifts the floor; the pinned device now actually leaves
    assert [e.kind for e in proc.sample_arrivals()] == ["arrive"]
    ev = proc.consume([0], [0.1])
    assert [e.kind for e in ev] == ["depart"] and ev[0].device == 0
    assert ev[0].cause == "energy_depleted"
    assert ev[0].to_dict()["cause"] == "energy_depleted"
    assert proc.n_active == 2
    # ordinary churn departures carry no cause
    assert all(e.cause is None
               for e in NetworkProcess(
                   ncfg, DynamicsCfg(forced_departures={0: (0,)},
                                     min_devices=1, seed=0)
               ).sample_departures(0))


# --------------------------------------------------------------------------
# batched evaluation
# --------------------------------------------------------------------------

def test_evaluator_bit_identical_to_scalar():
    net, ncfg = _net(5, seed=7)
    pb = lt.PartitionBatch(1, net, ncfg, PROF, 16, 2, [5], np.arange(5))
    xs = np.random.default_rng(0).integers(1, 7, size=(64, 5))
    want = np.array([lt.cluster_latency(1, list(range(5)), x, net, ncfg,
                                        PROF, 16, 2) for x in xs])
    np.testing.assert_array_equal(pb.latencies(xs), want)


@pytest.mark.parametrize("seed,K,L", [(0, 5, 1), (3, 5, 1), (17, 5, 1),
                                      (4, 1, 1), (5, 2, 2), (6, 8, 2)])
def test_batched_greedy_identical_decisions(seed, K, L):
    net, ncfg = _net(max(K, 5), seed=seed)
    args = (1, list(range(K)), net, ncfg, PROF, 16, L)
    xg, lg = rs.greedy_spectrum(*args)
    xb, lb = rs.SpectrumTable(*args).greedy(list(range(K)))
    np.testing.assert_array_equal(xg, xb)
    assert lg == lb


def test_batched_gibbs_identical_decisions():
    net, ncfg = _net(12, seed=5)
    a = rs.gibbs_clustering(1, net, ncfg, PROF, 16, 1, 4, 3, iters=150,
                            seed=2, spectrum_fn=rs.greedy_spectrum)
    b = rs.gibbs_clustering(1, net, ncfg, PROF, 16, 1, 4, 3, iters=150,
                            seed=2)
    assert a[0] == b[0] and a[2] == b[2]
    for x1, x2 in zip(a[1], b[1]):
        np.testing.assert_array_equal(x1, x2)


# --------------------------------------------------------------------------
# controller
# --------------------------------------------------------------------------

def test_balanced_sizes():
    assert balanced_sizes(10, 5) == [5, 5]
    assert balanced_sizes(7, 5) == [4, 3]
    assert balanced_sizes(11, 5) == [4, 4, 3]
    assert balanced_sizes(1, 5) == [1]
    assert balanced_sizes(0, 5) == []


def _controller(n=6, seed=0):
    ncfg = NetworkCfg(n_devices=n, n_subcarriers=2 * n)
    scfg = SimCfg(cluster_size=3, saa_samples=1, saa_gibbs_iters=8,
                  gibbs_iters=20, cuts=(2, 3), seed=seed)
    return TwoTimescaleController(PROF, ncfg, 16, 1, scfg), ncfg


def test_controller_two_timescales_and_plan():
    ctrl, ncfg = _controller(6)
    proc = NetworkProcess(ncfg, DynamicsCfg(seed=0))
    net, ids = proc.snapshot()
    v, means = ctrl.select_cut(*proc.means_of(ids), slot=0)
    assert v in (2, 3) and len(means) == 2
    plan = ctrl.plan_slot(net, ids, slot=0)
    assert sorted(i for c in plan.clusters for i in c) == list(range(6))
    for c, x in zip(plan.clusters, plan.xs):
        assert x.sum() == ncfg.n_subcarriers and len(x) == len(c)
    # plan latency agrees with the cost model
    want = lt.round_latency(plan.v, plan.clusters, plan.xs, net, ncfg,
                            PROF, 16, 1)
    assert plan.latency == pytest.approx(want, rel=1e-12)


def test_controller_repair_drops_departed_and_reallocates():
    ctrl, ncfg = _controller(6)
    proc = NetworkProcess(ncfg, DynamicsCfg(seed=0))
    net, ids = proc.snapshot()
    ctrl.select_cut(*proc.means_of(ids), slot=0)
    plan = ctrl.plan_slot(net, ids, slot=0)
    gone = int(ids[plan.clusters[0][0]])
    repaired = ctrl.repair(plan, net, [gone])
    assert repaired.stale
    survivors = [int(ids[i]) for c in repaired.clusters for i in c]
    assert gone not in survivors
    assert len(survivors) == 5
    # affected cluster re-ran Alg. 3: full spectrum among survivors
    for c, x in zip(repaired.clusters, repaired.xs):
        assert len(x) == len(c) and x.sum() == ncfg.n_subcarriers
    want = lt.round_latency(repaired.v, repaired.clusters, repaired.xs,
                            net, ncfg, PROF, 16, 1)
    assert repaired.latency == pytest.approx(want, rel=1e-12)


def test_controller_repair_drops_empty_cluster():
    ctrl, ncfg = _controller(6)
    proc = NetworkProcess(ncfg, DynamicsCfg(seed=0))
    net, ids = proc.snapshot()
    ctrl.select_cut(*proc.means_of(ids), slot=0)
    plan = ctrl.plan_slot(net, ids, slot=0)
    gone = [int(ids[i]) for i in plan.clusters[0]]
    repaired = ctrl.repair(plan, net, gone)
    assert len(repaired.clusters) == len(plan.clusters) - 1


def test_plan_slot_multichain_with_custom_spectrum_fn():
    """gibbs_chains > 1 must be honored on the custom-spectrum_fn
    fallback too (it used to silently run one chain): chain 0 draws the
    old single-chain stream bit for bit, chains c > 0 draw
    default_rng((seed, c)), and the best-of-R plan latency is monotone
    non-increasing in the chain count."""
    ncfg = NetworkCfg(n_devices=6, n_subcarriers=12)
    proc = NetworkProcess(ncfg, DynamicsCfg(seed=0))
    net, ids = proc.snapshot()
    lats = []
    for chains in (1, 2, 4):
        scfg = SimCfg(cluster_size=3, gibbs_iters=25, cuts=(2,), seed=0,
                      gibbs_chains=chains)
        ctrl = TwoTimescaleController(PROF, ncfg, 16, 1, scfg,
                                      spectrum_fn=rs.greedy_spectrum)
        ctrl.v = 2
        plan = ctrl.plan_slot(net, ids, slot=0)
        assert sorted(i for c in plan.clusters for i in c) == list(range(6))
        lats.append(plan.latency)
    # chain 0 of every multichain run shares the chains=1 stream, so
    # best-of-R can only improve: lat(1) >= lat(2) >= lat(4) bit-wise
    assert lats[0] >= lats[1] >= lats[2]
    # and chain 0 is bit-identical to the direct single-chain Gibbs call
    sizes = balanced_sizes(6, 3)
    _, _, direct = rs.gibbs_clustering(
        2, net, ncfg, PROF, 16, 1, n_clusters=len(sizes),
        cluster_size=max(sizes), iters=25, seed=0 + 0 + 53_639,
        sizes=sizes, spectrum_fn=rs.greedy_spectrum)
    assert lats[0] == direct


# --------------------------------------------------------------------------
# engine end-to-end
# --------------------------------------------------------------------------

def test_engine_end_to_end_trace(tmp_path):
    """Train real CPSL-LeNet under Gauss-Markov fading with a forced
    mid-round departure; the JSONL trace must recompute exactly."""
    from repro.data.pipeline import CPSLDataset
    from repro.data.synthetic import non_iid_split, synthetic_mnist

    xtr, ytr, _, _ = synthetic_mnist(800, 100, seed=0)
    idx = non_iid_split(ytr, n_devices=6, samples_per_device=100)
    ds = CPSLDataset(xtr, ytr, idx, batch=8)
    ncfg = NetworkCfg(n_devices=6, n_subcarriers=12)
    ccfg = CPSLConfig(cut_layer=3, n_clusters=2, cluster_size=3,
                      local_epochs=1, batch_per_device=8)
    trace_path = str(tmp_path / "trace.jsonl")
    scfg = SimCfg(rounds=3, epoch_len=2, cluster_size=3, saa_samples=1,
                  saa_gibbs_iters=6, gibbs_iters=12, cuts=(3,),
                  trace_path=trace_path, seed=0)
    dcfg = DynamicsCfg(rho_snr=0.9, rho_f=0.95,
                       forced_departures={1: (4,)}, seed=0)
    eng = SimEngine("lenet", ds, PROF, ncfg, dcfg, scfg, ccfg)
    state, trace = eng.run(jax.random.PRNGKey(0))

    assert state is not None and len(trace) == 3
    assert all(np.isfinite(rec["loss"]) for rec in trace)
    departs = [e for rec in trace for e in rec["events"]
               if e["kind"] == "depart"]
    assert departs and departs[0]["device"] == 4
    assert trace[1]["stale"]
    assert trace[1]["n_active"] == 6 and trace[2]["n_active"] == 5

    # per-round latencies recompute from the JSONL file alone
    lines = [json.loads(l) for l in open(trace_path)]
    got = np.array([r["latency_s"] for r in lines])
    want = recompute_trace_latencies(lines, PROF, ncfg, 8, 1)
    assert np.abs(got - want).max() < 1e-6
    # sim clock is the running sum of round latencies
    assert lines[-1]["sim_time_s"] == pytest.approx(got.sum())


def test_engine_no_train_mode_fast():
    """train=False exercises the full control plane without jax."""
    from repro.data.pipeline import CPSLDataset
    ncfg = NetworkCfg(n_devices=8, n_subcarriers=16)
    ccfg = CPSLConfig(cluster_size=4, batch_per_device=16)
    scfg = SimCfg(rounds=6, epoch_len=3, cluster_size=4, saa_samples=1,
                  saa_gibbs_iters=6, gibbs_iters=15, cuts=(2, 3), seed=1)
    dcfg = DynamicsCfg(p_depart=0.1, p_arrive=0.5, min_devices=3, seed=1)
    ds = CPSLDataset(np.zeros((8, 28, 28, 1)), np.zeros(8, np.int64),
                     [np.array([d]) for d in range(8)], batch=16)
    eng = SimEngine("lenet", ds, PROF, ncfg, dcfg, scfg, ccfg, train=False)
    _, trace = eng.run()
    assert len(trace) == 6
    for rec in trace:
        if rec.get("skipped"):
            continue
        want = lt.round_latency(rec["v"], rec["clusters"],
                                rec["xs"], _ns(rec), ncfg, PROF, 16, 1)
        assert rec["latency_s"] == pytest.approx(want, rel=1e-12)
        assert "loss" not in rec


def _ns(rec):
    from repro.core.channel import NetworkState
    return NetworkState(f=np.asarray(rec["f"], float),
                        rate=np.asarray(rec["rate"], float))
