"""Latency model (§V) and resource-management algorithms (§VII):
hand-checked values, greedy vs brute force, Gibbs vs random, SAA, and
hypothesis property tests (diminishing gains, partition feasibility)."""
import math

import numpy as np
import pytest

from _hyp import given, settings, st

from repro.core import latency as lt
from repro.core import profile as pf
from repro.core import resource as rs
from repro.core.channel import (NetworkCfg, NetworkState, device_means,
                                sample_network)


def _net(n=6, seed=0, f=None, snr_db=None):
    rng = np.random.default_rng(seed)
    f = np.asarray(f, float) if f is not None \
        else rng.uniform(0.1e9, 1e9, n)
    snr_db = np.asarray(snr_db, float) if snr_db is not None \
        else rng.uniform(5, 30, n)
    rate = 1e6 * np.log2(1 + 10 ** (snr_db / 10))
    return NetworkState(f=np.asarray(f, float), rate=np.asarray(rate, float))


PROF = pf.paper_constants_profile()
NCFG = NetworkCfg(n_devices=6, n_subcarriers=12)


def test_cluster_latency_hand_computed():
    """Check eq. (19)/(24) against a hand calculation."""
    net = _net(2, f=[0.5e9, 0.5e9], snr_db=[17.0, 17.0])
    r = net.rate[0]
    x = np.array([3, 3])
    c = PROF.at(1)
    tau_b = c["xi_d"] / (NCFG.n_subcarriers * r)
    tau_d = 16 * c["gamma_dF"] / 0.5e9
    tau_s = 16 * c["xi_s"] / (3 * r)
    tau_e = 2 * 16 * (c["gamma_sF"] + c["gamma_sB"]) / 100e9
    tau_g = c["xi_g"] / (3 * r)
    tau_u = 16 * c["gamma_dB"] / 0.5e9
    tau_t = c["xi_d"] / (3 * r)
    want = (tau_b + tau_d + tau_s + tau_e) + (tau_g + tau_u + tau_t)
    got = lt.cluster_latency(1, [0, 1], x, net, NCFG, PROF, B=16, L=1)
    assert abs(got - want) < 1e-9


def test_inner_phase_count():
    """D_m = d_S + (L-1) d_I + d_E: latency strictly increases with L."""
    net = _net(3)
    x = np.array([4, 4, 4])
    lats = [lt.cluster_latency(1, [0, 1, 2], x, net, NCFG, PROF, 16, L)
            for L in (1, 2, 4)]
    d_I = lats[1] - lats[0]
    assert lats[2] - lats[1] == pytest.approx(2 * d_I, rel=1e-9)


def test_round_latency_sums_clusters():
    net = _net(6)
    cl = [[0, 1, 2], [3, 4, 5]]
    xs = [np.array([4, 4, 4])] * 2
    total = lt.round_latency(1, cl, xs, net, NCFG, PROF, 16, 1)
    parts = [lt.cluster_latency(1, c, x, net, NCFG, PROF, 16, 1)
             for c, x in zip(cl, xs)]
    assert total == pytest.approx(sum(parts))


def test_greedy_matches_bruteforce():
    net = _net(3, seed=3)
    xg, lg = rs.greedy_spectrum(1, [0, 1, 2], net, NCFG, PROF, 16, 2, C=8)
    xb, lb = rs.brute_force_spectrum(1, [0, 1, 2], net, NCFG, PROF, 16, 2,
                                     C=8)
    assert lg == pytest.approx(lb, rel=1e-6)
    assert xg.sum() == 8


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), K=st.integers(2, 5),
       C=st.integers(6, 16))
def test_greedy_spectrum_properties(seed, K, C):
    if C < K:
        C = K
    net = _net(K, seed=seed)
    x, lat = rs.greedy_spectrum(1, list(range(K)), net, NCFG, PROF, 16, 1,
                                C=C)
    assert x.sum() == C and (x >= 1).all()
    # diminishing gains: more subcarriers never increases latency
    lat1 = lt.cluster_latency(1, list(range(K)), x + 1, net, NCFG, PROF,
                              16, 1)
    assert lat1 <= lat + 1e-12


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_gibbs_feasible_partition(seed):
    net = _net(6, seed=seed)
    cl, xs, lat = rs.gibbs_clustering(1, net, NCFG, PROF, 16, 1,
                                      n_clusters=2, cluster_size=3,
                                      iters=30, seed=seed)
    flat = sorted(d for c in cl for d in c)
    assert flat == list(range(6))                 # exact partition
    for c, x in zip(cl, xs):
        assert len(c) == 3 and x.sum() == NCFG.n_subcarriers


def test_gibbs_no_worse_than_random():
    net = _net(12, seed=7)
    ncfg = NetworkCfg(n_devices=12, n_subcarriers=24)
    _, _, lat_g = rs.gibbs_clustering(1, net, ncfg, PROF, 16, 1, 4, 3,
                                      iters=400, seed=0)
    _, _, lat_r = rs.random_clustering(1, net, ncfg, PROF, 16, 1, 4, 3,
                                       seed=0)
    assert lat_g <= lat_r + 1e-9


def test_saa_picks_reasonable_cut():
    prof = pf.lenet_profile()
    ncfg = NetworkCfg(n_devices=6, n_subcarriers=12)
    v_star, means = rs.saa_cut_selection(prof, ncfg, B=16, L=1,
                                         n_clusters=2, cluster_size=3,
                                         n_samples=2, gibbs_iters=20,
                                         seed=0)
    assert 1 <= v_star <= prof.n_cuts
    assert means[v_star - 1] == means.min()
    # shallow cuts (small device compute) must beat the deepest cuts for
    # the paper's weak-device regime
    assert v_star <= 6


def test_lenet_profile_matches_paper_smashed_size():
    prof = pf.lenet_profile()
    # POOL1 is layer 3: xi_s = 12*12*32*4 bytes = 18 KB (paper Table II)
    assert prof.xi_s[2] == pytest.approx(18 * 1024 * 8)
    # workloads monotone in v
    assert (np.diff(prof.gamma_dF) >= 0).all()
    assert (np.diff(prof.gamma_sF) <= 0).all()
    assert (np.diff(prof.xi_d) >= 0).all()


def test_paper_round_latency_calibration():
    """§VIII-B: SL 13.90s, FL 33.43s, CPSL 3.78s. Our faithful formulas land
    within 30% (the paper's CPSL number appears to exclude per-round model
    distribution/upload)."""
    ncfg = NetworkCfg(homogeneous=True, f_sigma=0.0, snr_sigma_db=0.0)
    net = sample_network(ncfg, *device_means(ncfg, 0),
                         np.random.default_rng(0))
    prof = pf.paper_constants_profile()
    sl = lt.vanilla_sl_round_latency(1, net, ncfg, prof, B=16)
    fl = lt.fl_round_latency(net, ncfg, prof, B=16)
    clusters = [list(range(m * 5, (m + 1) * 5)) for m in range(6)]
    xs = [np.full(5, 6)] * 6
    cpsl = lt.round_latency(1, clusters, xs, net, ncfg, prof, 16, 1)
    assert abs(sl - 13.90) / 13.90 < 0.10
    assert abs(fl - 33.43) / 33.43 < 0.15
    assert abs(cpsl - 3.78) / 3.78 < 0.30
    assert cpsl < sl < fl


@pytest.mark.parametrize("seed,K,C", [(0, 2, 5), (1, 2, 6), (5, 3, 7),
                                      (11, 3, 9), (21, 4, 8), (2, 4, 10)])
def test_greedy_matches_bruteforce_small_instances(seed, K, C):
    """Alg. 3 greedy finds the exhaustive optimum on these instances."""
    net = _net(K, seed=seed)
    xg, lg = rs.greedy_spectrum(1, list(range(K)), net, NCFG, PROF, 16, 1,
                                C=C)
    xb, lb = rs.brute_force_spectrum(1, list(range(K)), net, NCFG, PROF,
                                     16, 1, C=C)
    assert lg == pytest.approx(lb, rel=1e-6)
    assert xg.sum() == C and (xg >= 1).all()


def test_greedy_near_optimal_many_instances():
    """Greedy is a heuristic, not exact: across these 60 random instances
    it is never better than brute force and lands within 13% of it (the
    worst observed gap across 360 surveyed instances was 12.1%)."""
    for seed in range(20):
        for K, C in [(2, 6), (3, 9), (4, 10)]:
            net = _net(K, seed=seed)
            _, lg = rs.greedy_spectrum(1, list(range(K)), net, NCFG, PROF,
                                       16, 1, C=C)
            _, lb = rs.brute_force_spectrum(1, list(range(K)), net, NCFG,
                                            PROF, 16, 1, C=C)
            assert lb - 1e-9 <= lg <= 1.13 * lb


def test_greedy_early_exit_c_equals_k():
    net = _net(4, seed=2)
    x, lat = rs.greedy_spectrum(1, [0, 1, 2, 3], net, NCFG, PROF, 16, 1, C=4)
    assert (x == 1).all()
    assert lat == pytest.approx(
        lt.cluster_latency(1, [0, 1, 2, 3], x, net, NCFG, PROF, 16, 1))


@pytest.mark.parametrize("L,physical", [(1, False), (3, False), (2, True)])
def test_cluster_latency_batch_matches_scalar(L, physical):
    """``PartitionBatch`` with one cluster row scored against P candidate
    allocations is bit-identical to scalar calls, elementwise."""
    net = _net(5, seed=9)
    rng = np.random.default_rng(0)
    xs = rng.integers(1, 9, size=(40, 5))
    got = lt.PartitionBatch(1, net, NCFG, PROF, 16, L, [5], np.arange(5),
                            physical_gradients=physical).latencies(xs)
    want = np.array([lt.cluster_latency(1, list(range(5)), x, net, NCFG,
                                        PROF, 16, L,
                                        physical_gradients=physical)
                     for x in xs])
    np.testing.assert_array_equal(got, want)


def test_cluster_latency_batch_1d_input():
    net = _net(3, seed=4)
    x = np.array([2, 3, 4])
    got = lt.PartitionBatch(1, net, NCFG, PROF, 16, 1, [3],
                            np.arange(3)).latencies(x)
    assert got.shape == (1,)
    assert got[0] == lt.cluster_latency(1, [0, 1, 2], x, net, NCFG, PROF,
                                        16, 1)


def test_gibbs_uneven_sizes_partition():
    """`sizes` support: a 7-device network split 3/2/2 stays a partition."""
    net = _net(7, seed=13)
    ncfg = NetworkCfg(n_devices=7, n_subcarriers=12)
    cl, xs, lat = rs.gibbs_clustering(1, net, ncfg, PROF, 16, 1,
                                      n_clusters=3, cluster_size=3,
                                      iters=40, seed=1, sizes=[3, 2, 2])
    assert sorted(d for c in cl for d in c) == list(range(7))
    assert sorted(len(c) for c in cl) == [2, 2, 3]
    for c, x in zip(cl, xs):
        assert x.sum() == ncfg.n_subcarriers


@pytest.mark.parametrize("L", [1, 2])
@pytest.mark.parametrize("C_of_K", ["K", "K+1", "30"])
@pytest.mark.parametrize("K", [1, 2, 5, 8])
def test_spectrum_table_bit_identical_to_greedy(K, C_of_K, L):
    """The table path's (x, D_m) equal the scalar Alg. 3's, float for
    float, at every cut of the LeNet profile, over random networks and
    device orders; C = K (one subcarrier each) and C = K + 1 (one step)
    are the edges. The last two networks repeat devices, so candidates
    tie and the first-index tie-break decides."""
    prof = pf.lenet_profile()
    C = {"K": K, "K+1": K + 1, "30": 30}[C_of_K]
    ncfg = NetworkCfg(n_devices=10, n_subcarriers=C)
    rng = np.random.default_rng([K, C, L])
    nets = [sample_network(ncfg, *device_means(ncfg, int(rng.integers(99))),
                           rng) for _ in range(3)]
    nets += [NetworkState(f=net.f[idx], rate=net.rate[idx]) for net, idx in
             ((nets[0], np.zeros(10, int)), (nets[1], np.arange(10) // 2))]
    for net in nets:
        devs = [int(d) for d in rng.choice(10, K, replace=False)]
        table = rs.SpectrumTable(1, devs, net, ncfg, prof, 16, L)
        for v in range(1, prof.n_cuts + 1):
            if v > 1:
                table = rs.SpectrumTable(v, devs, net, ncfg, prof, 16, L)
            x, lat = table.greedy(devs)
            xr, latr = rs.greedy_spectrum(v, devs, net, ncfg, prof, 16, L)
            np.testing.assert_array_equal(x, xr)
            assert lat == latr


@pytest.mark.parametrize("n,M,K,sizes,L", [
    (12, 4, 3, None, 1), (7, 3, 3, [3, 2, 2], 2), (11, 3, 4, [4, 4, 3], 1),
    (10, 2, 4, None, 2), (30, 6, 5, None, 1)])
def test_gibbs_default_table_matches_scalar_greedy(n, M, K, sizes, L):
    """Gibbs on the table path (the default) against Gibbs on the scalar
    Alg. 3: the same clusters, xs and latency, with uneven ``sizes`` and
    with devices left out of the plan (M * K < n)."""
    prof = pf.lenet_profile()
    ncfg = NetworkCfg(n_devices=n, n_subcarriers=30)
    net = sample_network(ncfg, *device_means(ncfg, n),
                         np.random.default_rng(n + L))
    kw = dict(iters=120, seed=n, sizes=sizes, track=True)
    got = rs.gibbs_clustering(1, net, ncfg, prof, 16, L, M, K, **kw)
    want = rs.gibbs_clustering(1, net, ncfg, prof, 16, L, M, K,
                               spectrum_fn=rs.greedy_spectrum, **kw)
    assert got[0] == want[0] and got[2] == want[2] and got[3] == want[3]
    for x, xr in zip(got[1], want[1]):
        np.testing.assert_array_equal(x, xr)


def test_saa_and_baselines_on_table_path_match_scalar_greedy():
    """SAA (Alg. 2 over Alg. 4) and the baselines that optimise spectrum
    take the table path with the scalar greedy's results."""
    prof = pf.lenet_profile()
    ncfg = NetworkCfg(n_devices=6, n_subcarriers=12)
    kw = dict(n_clusters=2, cluster_size=3, n_samples=2, gibbs_iters=20,
              seed=4, cuts=[1, 4, 9])
    v, means = rs.saa_cut_selection(prof, ncfg, 16, 1, **kw)
    vr, means_r = rs.saa_cut_selection(prof, ncfg, 16, 1,
                                       spectrum_fn=rs.greedy_spectrum, **kw)
    assert v == vr
    np.testing.assert_array_equal(means, means_r)
    net = _net(6, seed=3)
    for plan in (rs.heuristic_clustering, rs.random_clustering):
        cl, xs, lat = plan(2, net, ncfg, prof, 16, 2, 2, 3,
                           optimize_spectrum=True)
        total = 0.0
        for c, x in zip(cl, xs):
            xr, latr = rs.greedy_spectrum(2, sorted(c), net, ncfg, prof, 16,
                                          2)
            np.testing.assert_array_equal(
                x, xr[np.argsort(np.argsort(c))])
            total += latr
        assert lat == total


def test_equal_split_x_budget():
    """Feasible split summing to exactly C, remainder to the leading
    devices; K > C is infeasible and must raise."""
    np.testing.assert_array_equal(lt.equal_split_x(5, 30), [6] * 5)
    np.testing.assert_array_equal(lt.equal_split_x(3, 13), [5, 4, 4])
    for K in range(1, 9):
        for C in range(K, 20):
            x = lt.equal_split_x(K, C)
            assert x.sum() == C and (x >= 1).all()
    with pytest.raises(ValueError):
        lt.equal_split_x(7, 6)


def test_uniform_xs_feasible_budget():
    """Regression: ``_uniform_xs`` used to hand max(C//K, 1) per device —
    over budget when K > C, and wasting the C mod K remainder otherwise.
    Now every cluster's allocation sums to exactly its budget."""
    ncfg = NetworkCfg(n_devices=10, n_subcarriers=12)
    xs = rs._uniform_xs([[0, 1, 2, 3, 4, 5, 6], [7, 8, 9]], ncfg)
    np.testing.assert_array_equal(xs[0], [2, 2, 2, 2, 2, 1, 1])
    np.testing.assert_array_equal(xs[1], [4, 4, 4])
    for x in xs:
        assert x.sum() == ncfg.n_subcarriers  # feasible, nothing wasted
    # K > C: the old code emitted an infeasible 1-per-device allocation
    with pytest.raises(ValueError):
        rs._uniform_xs([list(range(13))], ncfg)


def test_equal_split_curve_unequal_clusters():
    """Regression: the curve used to size every cluster like the first
    one (``K = len(clusters[0])``), mis-pricing or crashing the unequal
    churn-balanced layouts ``balanced_sizes`` routinely emits."""
    from repro.core.channel import device_means as dm, sample_network as sn

    ncfg = NetworkCfg(n_devices=10, n_subcarriers=12)
    clusters = [[0, 1, 2, 3], [4, 5, 6], [7, 8, 9]]   # balanced [4, 3, 3]
    got = lt.equal_split_curve(2, clusters, ncfg, PROF, 16, 1,
                               rounds=3, seed=5)
    mu_f, mu_snr = dm(ncfg, 5)
    rng = np.random.default_rng(5)
    xs = [lt.equal_split_x(len(c), ncfg.n_subcarriers) for c in clusters]
    t, want = 0.0, []
    for _ in range(3):
        net = sn(ncfg, mu_f, mu_snr, rng)
        t += lt.round_latency(2, clusters, xs, net, ncfg, PROF, 16, 1)
        want.append(t)
    np.testing.assert_allclose(got, want, rtol=0)
    # every cluster priced at its own size, budget exactly spent
    for c, x in zip(clusters, xs):
        assert len(x) == len(c) and x.sum() == ncfg.n_subcarriers


def test_lm_profile_all_archs():
    from repro.configs import registry
    for arch in registry.list_archs():
        prof = pf.profile_for(arch, seq=2048)
        assert prof.n_cuts >= 1
        assert (prof.xi_d > 0).all() and (prof.xi_s > 0).all()
        assert (prof.gamma_dF >= 0).all()
        assert (np.diff(prof.xi_d) >= 0).all()
