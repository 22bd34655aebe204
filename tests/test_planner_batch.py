"""Multi-replica planner equivalence suite.

Pins the bit-exactness contract of the replicated planner layer
(``core.latency.PartitionBatch`` + ``sim.batched`` multichain Gibbs /
batched SAA) to the looped ``core.resource`` implementations:

  * chain 0 of ``gibbs_clustering_multichain`` reproduces
    ``gibbs_clustering`` exactly (clusters, xs, latency, and the full
    accept/reject trajectory via ``track=True``);
  * ``saa_cut_selection_batched`` returns the same ``v_star`` and per-cut
    means as the looped ``saa_cut_selection`` — including its
    common-random-numbers coupling (``seed + j`` reused for every cut);
  * best-of-R latency is monotone non-increasing in R (per-chain RNG
    streams are prefix-stable in the chain count);
  * partition/allocation invariants hold for every produced plan, and
    ``PartitionBatch`` totals match summed scalar ``cluster_latency``
    to 0 ULP.
"""
import numpy as np
import pytest

from _hyp import given, settings, st

from repro.core import latency as lt
from repro.core import profile as pf
from repro.core import resource as rs
from repro.core.channel import (NetworkCfg, NetworkState, device_means,
                                sample_network)
from repro.sim.batched import (MultiChainResult, PartitionBatch,
                               gibbs_clustering_multichain,
                               saa_cut_selection_batched)

PROF = pf.lenet_profile()


def _net(n, seed=0, ncfg=None):
    ncfg = ncfg or NetworkCfg(n_devices=n, n_subcarriers=2 * n)
    return sample_network(ncfg, *device_means(ncfg, seed),
                          np.random.default_rng(seed)), ncfg


def _assert_same_plan(a, b):
    """(clusters, xs, lat) triples identical, bit-for-bit."""
    assert a[0] == [[int(d) for d in c] for c in b[0]]
    for x, y in zip(a[1], b[1]):
        np.testing.assert_array_equal(x, y)
    assert a[2] == b[2]


# --------------------------------------------------------------------------
# chain-0 bit-exactness
# --------------------------------------------------------------------------

@pytest.mark.parametrize("seed,iters", [(0, 60), (5, 150), (21, 90)])
def test_multichain_chain0_bit_exact(seed, iters):
    """Chain 0 (same seed) reproduces the looped single chain exactly:
    clusters, xs, latency, and the whole accept/reject trajectory."""
    net, ncfg = _net(12, seed=seed)
    single = rs.gibbs_clustering(2, net, ncfg, PROF, 16, 1, 4, 3,
                                 iters=iters, seed=seed, track=True)
    res = gibbs_clustering_multichain(2, net, ncfg, PROF, 16, 1, 4, 3,
                                      iters=iters, seed=seed, chains=3,
                                      track=True, full=True)
    assert isinstance(res, MultiChainResult)
    # trajectory: same accepted latency after every iteration
    assert single[3] == res.hists[0]
    _assert_same_plan(single[:3], res.chain_results[0])
    # best-of-R includes chain 0, so it can only improve on it
    assert res.latency <= single[2]
    assert res.latency == res.chain_latencies.min()


def test_multichain_single_chain_is_drop_in():
    """chains=1 returns the exact looped (clusters, xs, lat) tuple."""
    net, ncfg = _net(12, seed=9)
    single = rs.gibbs_clustering(3, net, ncfg, PROF, 16, 2, 4, 3,
                                 iters=100, seed=4)
    multi = gibbs_clustering_multichain(3, net, ncfg, PROF, 16, 2, 4, 3,
                                        iters=100, seed=4, chains=1)
    _assert_same_plan(single, multi)


def test_multichain_chain0_bit_exact_uneven_sizes():
    """The `sizes` path (churn: N != M*K) keeps chain-0 exactness."""
    net, ncfg = _net(7, seed=13)
    kw = dict(iters=80, seed=1, sizes=[3, 2, 2])
    single = rs.gibbs_clustering(1, net, ncfg, PROF, 16, 1, 3, 3,
                                 track=True, **kw)
    res = gibbs_clustering_multichain(1, net, ncfg, PROF, 16, 1, 3, 3,
                                      chains=2, track=True, full=True, **kw)
    assert single[3] == res.hists[0]
    _assert_same_plan(single[:3], res.chain_results[0])


def test_best_of_r_monotone_in_chains():
    """Prefix-stable per-chain streams: best-of-R latency is monotone
    non-increasing in R, and equals the running min of chain bests."""
    net, ncfg = _net(12, seed=2)
    full = gibbs_clustering_multichain(2, net, ncfg, PROF, 16, 1, 4, 3,
                                       iters=120, seed=3, chains=6,
                                       full=True)
    lats = [gibbs_clustering_multichain(2, net, ncfg, PROF, 16, 1, 4, 3,
                                        iters=120, seed=3, chains=r)[2]
            for r in (1, 2, 4, 6)]
    assert all(a >= b for a, b in zip(lats, lats[1:]))
    for r, lat in zip((1, 2, 4, 6), lats):
        assert lat == full.chain_latencies[:r].min()


# --------------------------------------------------------------------------
# batched SAA == looped SAA (incl. the CRN coupling)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 7])
def test_saa_batched_matches_looped(seed):
    net, ncfg = _net(6, seed=seed)
    kw = dict(n_samples=2, gibbs_iters=20, seed=seed, cuts=(1, 2, 3, 4))
    v1, m1 = rs.saa_cut_selection(PROF, ncfg, 16, 1, 2, 3, **kw)
    v2, m2 = saa_cut_selection_batched(PROF, ncfg, 16, 1, 2, 3, **kw)
    assert v1 == v2
    np.testing.assert_array_equal(m1, m2)


def test_saa_batched_means_override_and_sizes():
    """The dynamic-controller calling convention (tracked means + uneven
    sizes) stays bit-identical too."""
    ncfg = NetworkCfg(n_devices=7, n_subcarriers=14)
    mu_f, mu_snr = device_means(ncfg, 11)
    kw = dict(n_samples=3, gibbs_iters=15, seed=42, cuts=(2, 3),
              means_override=(mu_f, mu_snr), sizes=[3, 2, 2])
    v1, m1 = rs.saa_cut_selection(PROF, ncfg, 16, 1, 3, 3, **kw)
    v2, m2 = saa_cut_selection_batched(PROF, ncfg, 16, 1, 3, 3, **kw)
    assert v1 == v2
    np.testing.assert_array_equal(m1, m2)


def test_saa_crn_coupling_pinned():
    """CRN: sample j reuses ``seed + j`` for every cut, so each per-cut
    mean is independent of which other cuts are evaluated — in both the
    looped and the batched implementation."""
    net, ncfg = _net(6, seed=1)
    kw = dict(n_samples=2, gibbs_iters=15, seed=3)
    for fn in (rs.saa_cut_selection, saa_cut_selection_batched):
        _, m_joint = fn(PROF, ncfg, 16, 1, 2, 3, cuts=(1, 3), **kw)
        _, m1 = fn(PROF, ncfg, 16, 1, 2, 3, cuts=(1,), **kw)
        _, m3 = fn(PROF, ncfg, 16, 1, 2, 3, cuts=(3,), **kw)
        np.testing.assert_array_equal(m_joint, np.concatenate([m1, m3]))


def test_saa_multichain_means_never_worse():
    """chains>1 takes best-of-R per (cut, sample) cell: means can only
    improve on the single-chain estimate, elementwise."""
    net, ncfg = _net(6, seed=4)
    kw = dict(n_samples=2, gibbs_iters=25, seed=0, cuts=(1, 2, 3))
    _, m1 = saa_cut_selection_batched(PROF, ncfg, 16, 1, 2, 3, chains=1,
                                      **kw)
    _, m4 = saa_cut_selection_batched(PROF, ncfg, 16, 1, 2, 3, chains=4,
                                      **kw)
    assert (m4 <= m1).all()


# --------------------------------------------------------------------------
# PartitionBatch == summed scalar cluster_latency (0 ULP)
# --------------------------------------------------------------------------

def _left_to_right(values):
    """Accumulate m = 0, 1, ... in order (eq. 25 as the planners sum)."""
    total = 0.0
    for x in values:
        total += x
    return total


def _random_partition_case(seed, n=9, sizes=(4, 3, 2), L=1,
                           physical_gradients=False):
    rng = np.random.default_rng(seed)
    net, ncfg = _net(n, seed=seed)
    R = 5
    dev = np.stack([rng.permutation(n) for _ in range(R)])
    xs = rng.integers(1, 7, size=(R, n))
    return net, ncfg, dev, xs, sizes, L, physical_gradients


@pytest.mark.parametrize("seed,L,phys", [(0, 1, False), (3, 3, False),
                                         (8, 2, True), (17, 1, False)])
def test_partition_batch_matches_scalar_sum(seed, L, phys):
    """Totals match the left-to-right sum of per-cluster scalar
    ``cluster_latency`` calls to 0 ULP, per-cluster values elementwise.
    (Not Python's ``sum``: it compensates float rounding since 3.12.)"""
    net, ncfg, dev, xs, sizes, L, phys = _random_partition_case(
        seed, L=L, physical_gradients=phys)
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    pb = PartitionBatch(2, net, ncfg, PROF, 16, L, sizes, dev,
                        physical_gradients=phys)
    got_per = pb.cluster_latencies(xs)
    got_tot = pb.latencies(xs)
    for r in range(dev.shape[0]):
        per = [lt.cluster_latency(2, dev[r, s:e], xs[r, s:e], net, ncfg,
                                  PROF, 16, L, physical_gradients=phys)
               for s, e in zip(bounds[:-1], bounds[1:])]
        np.testing.assert_array_equal(got_per[r], per)
        assert got_tot[r] == _left_to_right(per)
        assert got_tot[r] == lt.round_latency(
            2, [dev[r, s:e] for s, e in zip(bounds[:-1], bounds[1:])],
            [xs[r, s:e] for s, e in zip(bounds[:-1], bounds[1:])],
            net, ncfg, PROF, 16, L, physical_gradients=phys)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), v=st.integers(1, 7))
def test_partition_batch_matches_scalar_sum_property(seed, v):
    net, ncfg, dev, xs, sizes, L, _ = _random_partition_case(seed)
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    pb = PartitionBatch(v, net, ncfg, PROF, 16, L, sizes, dev)
    got = pb.latencies(xs)
    for r in range(dev.shape[0]):
        want = _left_to_right(
            lt.cluster_latency(v, dev[r, s:e], xs[r, s:e], net, ncfg,
                               PROF, 16, L)
            for s, e in zip(bounds[:-1], bounds[1:]))
        assert got[r] == want


def test_partition_batch_per_replica_cuts_and_nets():
    """Per-replica cut layers + stacked network draws: each replica
    scores bit-identically to its own scalar evaluation."""
    ncfg = NetworkCfg(n_devices=8, n_subcarriers=16)
    mu_f, mu_snr = device_means(ncfg, 0)
    rng = np.random.default_rng(0)
    nets = [sample_network(ncfg, mu_f, mu_snr, rng) for _ in range(3)]
    snet = NetworkState(f=np.stack([n.f for n in nets]),
                        rate=np.stack([n.rate for n in nets]))
    sizes = (3, 3, 2)
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    R = 6
    vs = np.array([1, 2, 3, 4, 2, 5])
    rows = np.array([0, 1, 2, 0, 2, 1])
    dev = np.stack([rng.permutation(8) for _ in range(R)])
    xs = rng.integers(1, 5, size=(R, 8))
    pb = PartitionBatch(vs, snet, ncfg, PROF, 16, 2, sizes, dev,
                        net_rows=rows)
    got = pb.latencies(xs)
    for r in range(R):
        want = _left_to_right(
            lt.cluster_latency(int(vs[r]), dev[r, s:e], xs[r, s:e],
                               nets[rows[r]], ncfg, PROF, 16, 2)
            for s, e in zip(bounds[:-1], bounds[1:]))
        assert got[r] == want


def test_partition_batch_one_layout_many_candidates():
    """A single (1, N) device row broadcast against (P, N) candidate
    allocations — the greedy-stepping shape."""
    net, ncfg = _net(5, seed=6)
    rng = np.random.default_rng(1)
    xs = rng.integers(1, 8, size=(20, 5))
    pb = PartitionBatch(3, net, ncfg, PROF, 16, 1, [5],
                        np.arange(5)[None, :])
    got = pb.latencies(xs)
    want = np.array([lt.cluster_latency(3, list(range(5)), x, net, ncfg,
                                        PROF, 16, 1) for x in xs])
    np.testing.assert_array_equal(got, want)


# --------------------------------------------------------------------------
# partition / allocation invariants (property tests)
# --------------------------------------------------------------------------

def _check_invariants(clusters, xs, n_devices, ncfg):
    flat = sorted(d for c in clusters for d in c)
    assert flat == list(range(n_devices))          # exact partition
    for c, x in zip(clusters, xs):
        assert len(x) == len(c)
        assert x.sum() == ncfg.n_subcarriers       # full budget spent
        assert (x >= 1).all()                      # min 1 per device


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), chains=st.integers(1, 4))
def test_multichain_partition_invariants(seed, chains):
    net, ncfg = _net(6, seed=seed)
    clusters, xs, lat = gibbs_clustering_multichain(
        1, net, ncfg, PROF, 16, 1, 2, 3, iters=30, seed=seed, chains=chains)
    _check_invariants(clusters, xs, 6, ncfg)
    assert lat == pytest.approx(
        lt.round_latency(1, clusters, xs, net, ncfg, PROF, 16, 1),
        rel=1e-12)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_multichain_uneven_sizes_invariants(seed):
    net, ncfg = _net(7, seed=seed)
    clusters, xs, _ = gibbs_clustering_multichain(
        1, net, ncfg, PROF, 16, 1, 3, 3, iters=25, seed=seed, chains=3,
        sizes=[3, 2, 2])
    _check_invariants(clusters, xs, 7, ncfg)
    assert sorted(len(c) for c in clusters) == [2, 2, 3]


# --------------------------------------------------------------------------
# trainer wiring
# --------------------------------------------------------------------------

def test_trainer_gibbs_mc_and_cached_compressed_profile(tmp_path):
    """resource_mgmt="gibbs-mc" at chains=1 plans identically to "gibbs",
    and the cr<1 profile is built once per trainer, not per round."""
    from repro.configs.base import CPSLConfig
    from repro.core.cpsl import CPSL
    from repro.core.splitting import make_split_model
    from repro.data.pipeline import CPSLDataset
    from repro.train.trainer import CPSLTrainer, TrainerCfg

    ds = CPSLDataset(np.zeros((6, 28, 28, 1)), np.zeros(6, np.int64),
                     [np.array([d]) for d in range(6)], batch=8)

    def mk(kind, chains=1, compress="none"):
        ccfg = CPSLConfig(cut_layer=3, n_clusters=2, cluster_size=3,
                          local_epochs=1, batch_per_device=8,
                          compress_uploads=compress)
        tcfg = TrainerCfg(rounds=1, ckpt_dir=str(tmp_path / f"{kind}{chains}"),
                          resource_mgmt=kind, gibbs_iters=15,
                          gibbs_chains=chains, seed=0, async_ckpt=False)
        return CPSLTrainer(CPSL(make_split_model("lenet", 3), ccfg), ds,
                           PROF, NetworkCfg(n_devices=6), tcfg)

    plain = mk("gibbs")._plan_round(3, 0)
    mc1 = mk("gibbs-mc", chains=1)._plan_round(3, 0)
    _assert_same_plan(plain, mc1)
    mc4 = mk("gibbs-mc", chains=4)._plan_round(3, 0)
    assert mc4[2] <= plain[2]            # best-of-R never plans worse

    tr = mk("gibbs", compress="topk")
    assert tr._prof_compressed is not None
    assert (tr._prof_compressed.xi_d < PROF.xi_d).all()
    cached = tr._prof_compressed
    tr._plan_round(3, 0)
    assert tr._prof_compressed is cached     # reused, not rebuilt
    assert mk("gibbs")._prof_compressed is None


def test_multichain_single_cluster_no_swaps():
    """M=1: nothing to swap; the plan is the greedy allocation."""
    net, ncfg = _net(4, seed=3)
    clusters, xs, lat = gibbs_clustering_multichain(
        1, net, ncfg, PROF, 16, 1, 1, 4, iters=50, seed=0, chains=2)
    # the cache runs Alg. 3 on the sorted key and reorders (same pairing
    # rule as core.resource._round_latency_cached)
    key = sorted(clusters[0])
    x_sorted, want = rs.greedy_spectrum(1, key, net, ncfg, PROF, 16, 1)
    rank = {d: i for i, d in enumerate(key)}
    np.testing.assert_array_equal(
        xs[0], x_sorted[[rank[d] for d in clusters[0]]])
    assert lat == want
