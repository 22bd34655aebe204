"""Plain reference of the paper's round latency and its spectrum rules
(arXiv:2204.08119, eqs. 15-25 and Alg. 3).

A cluster's latency, for devices with compute ``f`` (cycles/s, times
FLOPs per cycle ``kappa``), per-subcarrier rate ``r`` and ``x``
subcarriers each, at cut constants ``c`` (``bench.flops.*.profile`` at
the cut), batch ``B`` and ``L`` local epochs:

    start  max(xi_d/(C r) + B gdF/f + B xi_s/(x r)) + K B (gsF+gsB)/fs
    inner  max(xi_g/(x r) + B gdB/f + B gdF/f + B xi_s/(x r)) + K B (..)/fs
    end    max(xi_g/(x r) + B gdB/f + xi_d/(x r))
    D_m  = start + (L-1) inner + end;  a round is the sum over clusters.

Alg. 3 starts every device at one subcarrier and hands out the rest one
at a time, each to the device whose extra subcarrier lowers the cluster's
latency most. The equal split gives C // K each and the remainder one by
one to the first devices.
"""
from __future__ import annotations

import numpy as np


def cluster_latency(c: dict, net: dict, devices, x, B: int, L: int) -> float:
    f = np.asarray(net["f"], float)[list(devices)] * net["kappa"]
    r = np.asarray(net["rate"], float)[list(devices)]
    x = np.asarray(x, float)
    K, C = len(devices), net["n_subcarriers"]
    t_e = K * B * (c["gamma_sF"] + c["gamma_sB"]) / (net["f_server"]
                                                   * net["kappa"])
    t_s = B * c["xi_s"] / (x * r)
    t_g = c["xi_g"] / (x * r)
    t_d = B * c["gamma_dF"] / f
    t_u = B * c["gamma_dB"] / f
    start = np.max(c["xi_d"] / (C * r) + t_d + t_s) + t_e
    inner = np.max(t_g + t_u + t_d + t_s) + t_e
    end = np.max(t_g + t_u + c["xi_d"] / (x * r))
    return float(start + (L - 1) * inner + end)


def round_latency(c, net, clusters, xs, B, L) -> float:
    return float(sum(cluster_latency(c, net, d, x, B, L)
                     for d, x in zip(clusters, xs)))


def greedy(c, net, devices, B, L) -> np.ndarray:
    K, C = len(devices), net["n_subcarriers"]
    x = np.ones(K, dtype=np.int64)
    for _ in range(C - K):
        cand = []
        for k in range(K):
            x[k] += 1
            cand.append(cluster_latency(c, net, devices, x, B, L))
            x[k] -= 1
        x[int(np.argmin(cand))] += 1
    return x


def equal_split(K: int, C: int) -> np.ndarray:
    base, rem = divmod(C, K)
    return np.full(K, base, dtype=np.int64) + (np.arange(K) < rem)
