"""Population-scale planning: pruning / tiling / hierarchy exactness.

The scale path (ISSUE 8) is three approximations with exactness
fallbacks, each pinned here:

  * top-k greedy spectrum (``greedy_spectrum_topk``, ``_greedy_group``'s
    ``topk``): k >= K is bit-identical to the full Alg. 3;
  * chunked ``PartitionBatchJ``: every chunk size (incl. ragged last
    tiles) is bit-identical to the unchunked evaluation, and the float32
    opt-in agrees to ~1e-5 relative;
  * hierarchical two-level Gibbs (``hierarchical_gibbs_clustering``):
    a single bucket is bit-identical to ``gibbs_clustering_multichain``,
    and multi-bucket solutions keep every partition/budget invariant and
    price within 2% of the flat plan.

Plus the integration layers: ``SimCfg.plan_mode="bucketed"`` collapses
to the flat plan when n <= bucket_size, and the episode fleet's
``cost_chunk`` streaming changes no decision.
"""
import numpy as np
import pytest

from repro.configs.base import SimCfg, SimFleetCfg
from repro.core import profile as pf
from repro.core import resource as rs
from repro.core.channel import NetworkCfg, device_means, sample_network
from repro.core.latency import PartitionBatch, PartitionBatchJ
from repro.sim.batched import (gibbs_clustering_multichain,
                               hierarchical_gibbs_clustering)
from repro.sim.controller import TwoTimescaleController, balanced_sizes
from repro.sim.dynamics import DynamicsCfg
from repro.sim.fleet import SimFleetRunner

PROF = pf.lenet_profile()


def _net(n, seed=0, c=None):
    ncfg = NetworkCfg(n_devices=n, n_subcarriers=c or 2 * n)
    mu_f, mu_snr = device_means(ncfg, seed)
    net = sample_network(ncfg, mu_f, mu_snr, np.random.default_rng(seed))
    return ncfg, net


# --------------------------------------------------------------------------
# top-k greedy spectrum (Alg. 3 pruning)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(8))
def test_topk_greedy_k_ge_K_bit_identical(seed):
    """k >= K: pruned candidates are all K devices in index order and
    come from the bit-exact PartitionBatch, so allocation and latency
    are bit-identical to the looped ``greedy_spectrum``."""
    rng = np.random.default_rng(1000 + seed)
    K = int(rng.integers(2, 9))
    C = int(rng.integers(K, 4 * K + 1))
    v = int(rng.integers(1, PROF.n_cuts + 1))
    ncfg, net = _net(K, seed, c=C)
    devs = list(range(K))
    x0, l0 = rs.greedy_spectrum(v, devs, net, ncfg, PROF, 16, 2, C=C)
    for k in (K, K + 3):
        xk, lk = rs.greedy_spectrum_topk(v, devs, net, ncfg, PROF, 16, 2,
                                         C=C, k=k)
        assert np.array_equal(x0, xk)
        assert l0 == lk


@pytest.mark.parametrize("seed", range(4))
def test_topk_greedy_k_lt_K_feasible(seed):
    """k < K is heuristic but always feasible: one subcarrier minimum,
    budget exactly spent, and the reported latency re-prices exactly."""
    from repro.core.latency import cluster_latency
    rng = np.random.default_rng(2000 + seed)
    K = int(rng.integers(4, 10))
    C = int(rng.integers(2 * K, 5 * K))
    v = int(rng.integers(1, PROF.n_cuts + 1))
    ncfg, net = _net(K, seed, c=C)
    devs = list(range(K))
    x, lat = rs.greedy_spectrum_topk(v, devs, net, ncfg, PROF, 16, 2, C=C,
                                     k=2)
    assert int(np.sum(x)) == C and np.all(x >= 1)
    assert lat == cluster_latency(v, devs, x, net, ncfg, PROF, 16, 2)


@pytest.mark.parametrize("n,K,chains,seed", [(20, 5, 1, 0), (18, 4, 2, 3)])
def test_multichain_topk_ge_K_bit_identical(n, K, chains, seed):
    """``spectrum_topk >= K`` threaded through the lockstep planner
    (_greedy_group) reproduces the unpruned multichain plan exactly."""
    ncfg, net = _net(n, seed)
    sizes = balanced_sizes(n, K)
    kw = dict(iters=40, seed=seed, chains=chains, sizes=sizes)
    cl0, xs0, l0 = gibbs_clustering_multichain(
        3, net, ncfg, PROF, 16, 2, len(sizes), max(sizes), **kw)
    clk, xsk, lk = gibbs_clustering_multichain(
        3, net, ncfg, PROF, 16, 2, len(sizes), max(sizes),
        spectrum_topk=K, **kw)
    assert cl0 == clk and l0 == lk
    assert all(np.array_equal(a, b) for a, b in zip(xs0, xsk))


# --------------------------------------------------------------------------
# chunked / float32 PartitionBatchJ
# --------------------------------------------------------------------------

@pytest.mark.parametrize("sizes", [[3, 2, 2], [4, 3, 3], [5]])
def test_chunked_partitionbatchj_bit_identical(sizes):
    """Every chunk size — dividing R, ragged last tile, chunk > R —
    returns bit-identical latencies to the unchunked path."""
    rng = np.random.default_rng(11)
    N = int(sum(sizes))
    R = 7
    ncfg, net = _net(N, 11)
    dev = np.stack([rng.permutation(N) for _ in range(R)])
    v = rng.integers(1, PROF.n_cuts + 1, size=R)
    xs = rng.integers(1, 6, size=(R, N))
    base = PartitionBatchJ(v, net, ncfg, PROF, 16, 2, sizes, dev,
                           net_rows=np.zeros(R, np.int64)
                           if np.asarray(net.f).ndim > 1 else None)
    ref_c = base.cluster_latencies(xs)
    ref_l = base.latencies(xs)
    for chunk in (1, 2, 3, 4, 7, 8, 100):
        pbj = PartitionBatchJ(v, net, ncfg, PROF, 16, 2, sizes, dev,
                              chunk_size=chunk)
        assert np.array_equal(pbj.cluster_latencies(xs), ref_c)
        assert np.array_equal(pbj.latencies(xs), ref_l)


def test_chunked_broadcast_row():
    """Chunking also streams the broadcast shape: one device row scored
    against (P, N) candidate allocations."""
    rng = np.random.default_rng(5)
    ncfg, net = _net(6, 5)
    xs = rng.integers(1, 5, size=(11, 6))
    ref = PartitionBatchJ(2, net, ncfg, PROF, 16, 1, [6],
                          np.arange(6)).latencies(xs)
    got = PartitionBatchJ(2, net, ncfg, PROF, 16, 1, [6], np.arange(6),
                          chunk_size=4).latencies(xs)
    assert np.array_equal(got, ref)


@pytest.mark.parametrize("chunk", [None, 3])
def test_partitionbatchj_float32_parity(chunk):
    """float32 opt-in halves the cost tensors; values stay within 1e-5
    relative of the float64 NumPy reference (chunked or not)."""
    rng = np.random.default_rng(9)
    sizes = [4, 3]
    N = 7
    ncfg, net = _net(N, 9)
    dev = np.stack([rng.permutation(N) for _ in range(5)])
    xs = rng.integers(1, 6, size=(5, N))
    ref = PartitionBatch(3, net, ncfg, PROF, 16, 2, sizes, dev).latencies(xs)
    got = PartitionBatchJ(3, net, ncfg, PROF, 16, 2, sizes, dev,
                          dtype=np.float32, chunk_size=chunk).latencies(xs)
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, ref, rtol=1e-5)


# --------------------------------------------------------------------------
# hierarchical two-level Gibbs
# --------------------------------------------------------------------------

def test_bucket_devices_invariants():
    ncfg, net = _net(37, 0)
    bs = rs.bucket_devices(net, 5)
    assert [len(b) for b in bs] == [8, 8, 7, 7, 7]
    assert np.array_equal(np.sort(np.concatenate(bs)), np.arange(37))
    # identity fallback and clamping
    assert np.array_equal(rs.bucket_devices(net, 1)[0], np.arange(37))
    assert len(rs.bucket_devices(net, 100)) == 37


@pytest.mark.parametrize("n,K,chains,seed",
                         [(17, 5, 1, 0), (30, 5, 3, 1), (23, 4, 2, 7)])
def test_single_bucket_hierarchical_bit_identical(n, K, chains, seed):
    """One bucket => the hierarchical planner IS the flat multichain
    planner: same RNG streams, same lockstep call, bit-identical
    clusters, allocations, and latency."""
    ncfg, net = _net(n, seed)
    sizes = balanced_sizes(n, K)
    cl0, xs0, l0 = gibbs_clustering_multichain(
        3, net, ncfg, PROF, 16, 2, len(sizes), max(sizes), iters=60,
        seed=seed, chains=chains, sizes=sizes)
    for kw in (dict(n_buckets=1), dict(bucket_size=n),
               dict(bucket_size=10 * n)):
        cl1, xs1, l1 = hierarchical_gibbs_clustering(
            3, net, ncfg, PROF, 16, 2, K, iters=60, seed=seed,
            chains=chains, **kw)
        assert cl0 == cl1 and l0 == l1
        assert all(np.array_equal(a, b) for a, b in zip(xs0, xs1))


def test_hierarchical_multibucket_invariants():
    """Multi-bucket: stitched clusters partition the population, stay
    within the target size, spend each cluster's full subcarrier budget,
    and the total is the sum of per-bucket bests."""
    n, K = 96, 5
    ncfg, net = _net(n, 3)
    res = hierarchical_gibbs_clustering(3, net, ncfg, PROF, 16, 2, K,
                                        iters=60, seed=3, chains=2,
                                        bucket_size=32, full=True)
    assert sorted(d for c in res.clusters for d in c) == list(range(n))
    assert all(1 <= len(c) <= K for c in res.clusters)
    assert all(int(np.sum(x)) == ncfg.n_subcarriers for x in res.xs)
    assert len(res.buckets) == 3
    np.testing.assert_allclose(res.latency, res.bucket_latencies.sum(),
                               rtol=1e-12)
    # clusters never straddle buckets
    owner = np.empty(n, dtype=np.int64)
    for b, ids in enumerate(res.buckets):
        owner[ids] = b
    assert all(len({int(owner[d]) for d in c}) == 1 for c in res.clusters)


def test_hierarchical_chains_monotone():
    """Per-bucket best-of-chains: more chains never worsens the total
    (streams are prefix-stable in the chain count)."""
    ncfg, net = _net(60, 2)
    lats = [hierarchical_gibbs_clustering(3, net, ncfg, PROF, 16, 2, 5,
                                          iters=50, seed=2, chains=c,
                                          bucket_size=30)[2]
            for c in (1, 2, 4)]
    assert lats[1] <= lats[0] and lats[2] <= lats[1]


@pytest.mark.parametrize("n", [100, 320])
def test_hierarchical_plan_quality_vs_flat(n):
    """At the paper's K=5, C=30: forced to one bucket, the hierarchical
    plan prices exactly as the flat multichain plan; at buckets of 160
    devices (two buckets at N=320, per-bucket iters 2 x bucket, flat
    iters 2N) it prices within 2% of flat."""
    K, B, L, chains = 5, 16, 1, 2
    ncfg, net = _net(n, 0, c=30)
    sizes = balanced_sizes(n, K)
    flat = gibbs_clustering_multichain(
        3, net, ncfg, PROF, B, L, len(sizes), max(sizes), iters=2 * n,
        seed=0, chains=chains, sizes=sizes)[2]

    def hier(bucket_size):
        return hierarchical_gibbs_clustering(
            3, net, ncfg, PROF, B, L, K, iters=2 * min(n, bucket_size),
            seed=0, chains=chains, bucket_size=bucket_size)[2]

    assert hier(n) == flat
    assert hier(160) <= 1.02 * flat


# --------------------------------------------------------------------------
# controller bucketed plan mode
# --------------------------------------------------------------------------

def _plans_equal(a, b):
    return (a.v == b.v and a.clusters == b.clusters and a.latency == b.latency
            and all(np.array_equal(x, y) for x, y in zip(a.xs, b.xs)))


def test_controller_bucketed_single_bucket_equals_flat():
    """plan_mode="bucketed" with n <= bucket_size makes the exact same
    plan as the flat controller (both multichain and chains=1)."""
    n = 14
    ncfg, net = _net(n, 4)
    ids = np.arange(n)
    for chains in (1, 2):
        scfg_f = SimCfg(cluster_size=4, gibbs_iters=40, gibbs_chains=chains,
                        seed=4)
        scfg_b = scfg_f.replace(plan_mode="bucketed", bucket_size=64)
        ctl_f = TwoTimescaleController(PROF, ncfg, 16, 2, scfg_f)
        ctl_b = TwoTimescaleController(PROF, ncfg, 16, 2, scfg_b)
        ctl_f.v = ctl_b.v = 3
        assert _plans_equal(ctl_f.plan_slot(net, ids, slot=2),
                            ctl_b.plan_slot(net, ids, slot=2))


def test_controller_bucketed_multibucket_plan():
    """Past the bucket size the bucketed mode still emits a feasible
    plan over every active device."""
    n = 40
    ncfg, net = _net(n, 6)
    scfg = SimCfg(cluster_size=5, gibbs_iters=30, gibbs_chains=2, seed=6,
                  plan_mode="bucketed", bucket_size=16, spectrum_topk=5)
    ctl = TwoTimescaleController(PROF, ncfg, 16, 2, scfg)
    ctl.v = 2
    plan = ctl.plan_slot(net, np.arange(n), slot=0)
    assert sorted(d for c in plan.clusters for d in c) == list(range(n))
    assert all(int(np.sum(x)) == ncfg.n_subcarriers for x in plan.xs)
    assert plan.latency > 0


# --------------------------------------------------------------------------
# fleet cost_chunk streaming
# --------------------------------------------------------------------------

def test_fleet_cost_chunk_identical_decisions():
    """Streaming the in-jit greedy candidate tensors (cost_chunk) changes
    no decision and no priced latency: padded clusters are fully gated,
    real clusters see identical candidate batches."""
    ncfg = NetworkCfg(n_devices=8, n_subcarriers=12)
    dcfg = DynamicsCfg(rho_snr=0.9, rho_f=0.95, seed=0)
    base = dict(rounds=4, seeds=(0,), policies=("greedy", "proposed"),
                cluster_sizes=(3,), cuts=(2,), batch_per_device=16,
                local_epochs=1, gibbs_iters=10, epoch_len=2,
                saa_cuts=(2, 3), saa_samples=2, saa_gibbs_iters=6)
    res0 = SimFleetRunner(PROF, ncfg, dcfg,
                          SimFleetCfg(**base)).run()
    res1 = SimFleetRunner(PROF, ncfg, dcfg,
                          SimFleetCfg(**base, cost_chunk=2)).run()
    t0, t1 = res0["trace"], res1["trace"]
    assert np.array_equal(t0["dev"], t1["dev"])
    assert np.array_equal(t0["xs"], t1["xs"])
    assert np.array_equal(t0["v"], t1["v"])
    np.testing.assert_allclose(t1["latency"], t0["latency"], rtol=1e-12)
