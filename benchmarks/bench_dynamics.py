"""Dynamics-simulator benchmarks.

Part 1 — batched candidate evaluation: at the paper's N=30 configuration,
score P candidate subcarrier allocations with the vectorized
``cluster_latency_batch`` / ``BatchedClusterEvaluator`` vs the looped
scalar baseline; assert the >=10x speedup and bit-identical values, then
verify greedy and Gibbs make *numerically identical decisions* on both
paths (and report their end-to-end speedups).

Part 2 — an end-to-end "train under dynamics" run: CPSL-LeNet under
Gauss-Markov fading with device churn, driven by the online two-timescale
controller; writes a JSONL trace and cross-checks every traced round
latency against a fresh ``core.latency`` recomputation.

    PYTHONPATH=src python -m benchmarks.run --only bench_dynamics
"""
from __future__ import annotations

import os
import time

import numpy as np

from repro.core import latency as lt
from repro.core import resource as rs
from repro.core.channel import NetworkCfg, device_means, sample_network
from repro.core.profile import lenet_profile
from repro.sim.batched import (BatchedClusterEvaluator,
                               gibbs_clustering_batched,
                               greedy_spectrum_batched)


def _timeit(fn, reps):
    fn()                                    # warm-up
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    return (time.perf_counter() - t0) / reps, out


def bench_batched_evaluation(quick: bool):
    ncfg = NetworkCfg(n_devices=30)         # paper §VIII-A configuration
    prof = lenet_profile()
    net = sample_network(ncfg, *device_means(ncfg, 0),
                         np.random.default_rng(0))
    B, L, v = 16, 1, 3
    dev = list(range(5))                    # one paper cluster (K=5)
    P = 1000 if quick else 5000
    xs = np.random.default_rng(1).integers(1, 27, size=(P, 5))

    t_loop, want = _timeit(lambda: np.array(
        [lt.cluster_latency(v, dev, x, net, ncfg, prof, B, L) for x in xs]),
        2)
    t_core, got_core = _timeit(lambda: lt.cluster_latency_batch(
        v, dev, xs, net, ncfg, prof, B, L), 5)
    ev = BatchedClusterEvaluator(v, dev, net, ncfg, prof, B, L)
    t_ev, got_ev = _timeit(lambda: ev.latencies(xs), 5)

    assert np.array_equal(want, got_core), "core batch diverged from scalar"
    assert np.array_equal(want, got_ev), "evaluator diverged from scalar"
    sp_core, sp_ev = t_loop / t_core, t_loop / t_ev
    print(f"candidate evaluation, P={P}, K=5, N=30:")
    print(f"  looped scalar          {t_loop*1e3:9.2f} ms")
    print(f"  cluster_latency_batch  {t_core*1e3:9.2f} ms  ({sp_core:6.1f}x)")
    print(f"  BatchedClusterEvaluator{t_ev*1e3:9.2f} ms  ({sp_ev:6.1f}x)")
    # wall-clock asserts are noisy on shared CI runners; CI sets
    # BENCH_MIN_SPEEDUP=1 and relies on the bit-equality asserts above
    min_speedup = float(os.environ.get("BENCH_MIN_SPEEDUP", "10"))
    assert sp_ev >= min_speedup, \
        f"batched speedup {sp_ev:.1f}x < {min_speedup:g}x"

    # greedy: identical decisions, report end-to-end speedup
    reps = 10 if quick else 50
    t_g, (xg, lg) = _timeit(lambda: rs.greedy_spectrum(
        v, dev, net, ncfg, prof, B, L), reps)
    t_gb, (xb, lb) = _timeit(lambda: greedy_spectrum_batched(
        v, dev, net, ncfg, prof, B, L), reps)
    assert np.array_equal(xg, xb) and lg == lb, "greedy decisions diverged"
    print(f"greedy (K=5, C=30): loop {t_g*1e3:.2f} ms, batched "
          f"{t_gb*1e3:.2f} ms ({t_g/t_gb:.1f}x), identical allocation")

    # Gibbs: identical clusters/allocations/latency
    iters = 100 if quick else 400
    t_gi, a = _timeit(lambda: rs.gibbs_clustering(
        v, net, ncfg, prof, B, L, 6, 5, iters=iters, seed=0,
        spectrum_fn=rs.greedy_spectrum), 2)
    t_gib, b = _timeit(lambda: gibbs_clustering_batched(
        v, net, ncfg, prof, B, L, 6, 5, iters=iters, seed=0), 2)
    assert a[0] == b[0] and a[2] == b[2] \
        and all(np.array_equal(x, y) for x, y in zip(a[1], b[1])), \
        "Gibbs decisions diverged"
    print(f"Gibbs (N=30, M=6, {iters} iters): loop {t_gi*1e3:.1f} ms, "
          f"batched {t_gib*1e3:.1f} ms ({t_gi/t_gib:.1f}x), "
          f"identical clustering (D={a[2]:.3f}s)")


def bench_dynamics_run(quick: bool):
    import jax
    from repro.configs.base import CPSLConfig, SimCfg
    from repro.data.pipeline import CPSLDataset
    from repro.data.synthetic import non_iid_split, synthetic_mnist
    from repro.sim.dynamics import DynamicsCfg
    from repro.sim.engine import SimEngine, recompute_trace_latencies

    n_dev = 10 if quick else 30
    xtr, ytr, _, _ = synthetic_mnist(2000 if quick else 6000, 200, seed=0)
    idx = non_iid_split(ytr, n_devices=n_dev,
                        samples_per_device=150)
    ds = CPSLDataset(xtr, ytr, idx, batch=16)
    ncfg = NetworkCfg(n_devices=n_dev, n_subcarriers=max(2 * 5, n_dev))
    prof = lenet_profile()
    ccfg = CPSLConfig(cluster_size=5, batch_per_device=16, local_epochs=1)
    scfg = SimCfg(rounds=4 if quick else 12, epoch_len=3, cluster_size=5,
                  saa_samples=1 if quick else 3,
                  saa_gibbs_iters=10 if quick else 40,
                  gibbs_iters=30 if quick else 120,
                  cuts=(2, 3, 4),
                  trace_path="/tmp/bench_dynamics_trace.jsonl", seed=0)
    dcfg = DynamicsCfg(rho_snr=0.9, rho_f=0.95, p_arrive=0.3,
                       forced_departures={1: (0,)}, min_devices=4, seed=0)
    eng = SimEngine("lenet", ds, prof, ncfg, dcfg, scfg, ccfg)
    t0 = time.perf_counter()
    _, trace = eng.run(jax.random.PRNGKey(0))
    wall = time.perf_counter() - t0
    executed = [r for r in trace if not r.get("skipped")]
    lats = np.array([r["latency_s"] for r in executed])
    want = recompute_trace_latencies(trace, prof, ncfg,
                                     ccfg.batch_per_device,
                                     ccfg.local_epochs)
    err = np.abs(lats - want).max()
    assert err < 1e-6, f"trace latency recompute error {err}"
    n_events = sum(len(r.get("events", [])) for r in trace)
    last = executed[-1]
    print(f"dynamics run: {len(trace)} rounds, {n_events} churn events, "
          f"sim time {last['sim_time_s']:.1f}s, wall {wall:.1f}s, "
          f"final loss {last.get('loss', float('nan')):.3f}, "
          f"trace recompute err {err:.2e} -> {scfg.trace_path}")


def main(quick: bool = True):
    bench_batched_evaluation(quick)
    bench_dynamics_run(quick)


if __name__ == "__main__":
    main()
