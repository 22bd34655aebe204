"""Episode fleets: E dynamic-network episodes as ONE jitted program.

PR 4 batched the *training* side (``CPSL.run_fleet``); this module is the
mirror for the paper's latency results (§VIII, figs. 7-8): Monte-Carlo
evaluation of wireless round latency under network dynamics across
seeds / policies / cluster sizes / cut layers runs as a single
``lax.scan`` over slots with everything vmapped/broadcast over the
episode axis, instead of one host NumPy loop per episode.

Three layers, all float64 (the cost model's contract dtype):

  * a jnp port of ``sim.dynamics.NetworkProcess`` — Gauss-Markov
    AR(1) fading + compute drift with the exact stationary-law-preserving
    innovation scaling, over a FIXED population with an active-mask for
    churn: deterministic per-device depart/arrive slots, stochastic
    Bernoulli departures/arrivals on pre-drawn uniforms with the
    ``min_devices`` floor (decision-identical to
    ``NetworkProcess.sample_departures`` / ``sample_arrivals`` on shared
    draws), and energy depletion with the floor-pinned delayed-depart
    semantics of ``NetworkProcess.consume``;
  * a jnp port of the eq. (15)-(25) cost model — ``_cluster_latency_j``
    keeps the operand order of ``core.latency.cluster_latency`` /
    ``PartitionBatch`` term by term, and :class:`PartitionBatchJ` wraps
    it in the NumPy ``PartitionBatch`` API so the two cross-check on the
    same inputs to tight float64 tolerance (tests pin this);
  * fixed-shape per-slot control — balanced clustering over the active
    devices padded to (M, K) slot masks, with three policies selected
    per episode: equal-split (``core.latency.equal_split_x``), greedy
    Alg. 3 (lockstep ``lax.fori_loop``, same candidate argmin as
    ``core.resource.greedy_spectrum``), and the paper's PROPOSED
    two-timescale controller — Gibbs clustering with the embedded
    greedy (Alg. 4, ``_gibbs_cells``: fixed lockstep sweeps over
    pre-drawn uniforms, best-of-``gibbs_chains``) every slot plus SAA
    cut re-selection (Alg. 2, a (cut x sample x chain) cell batch
    around the tracked means) every ``epoch_len`` slots, with
    post-departure spectrum repair within the slot. The host
    ``TwoTimescaleController`` consumes the same pre-drawn uniforms
    (``draws=`` hooks), so the in-jit arm and the looped host oracle
    make identical decisions.

:class:`SimFleetRunner` prices the whole ``SimFleetCfg`` grid in one
dispatch, mirrors every decision in a looped NumPy reference
(``run_reference`` — identical innovations and pre-drawn controller /
churn uniforms, host ``round_latency`` pricing), and can couple a
static-scenario grid to ``CPSL.run_fleet`` for joint latency x accuracy
curves (``train_curves``).

Equivalence contract (tests/test_simfleet.py):
episode e's per-round latency trace matches the looped NumPy reference
— and the ``recompute_trace_latencies`` oracle re-derivation from the
traced (f, rate, clusters, xs, v) — to tight float64 tolerance, with
identical cut / cluster / allocation decisions on every arm including
the proposed one (Gibbs + SAA + churn + floor + repair in-jit).

Still host-only (``SimEngine`` remains the reference for these): the
event/JSONL trace emission, and arrival devices drawing fresh means
from the live ``NetworkProcess`` stream — fleet episodes pre-draw the
means of up to ``SimFleetCfg.n_reserve`` reserve devices instead.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import streams
from repro.configs.base import SimFleetCfg
from repro.core import latency as lt
from repro.core import resource as rs
from repro.core.channel import NetworkCfg, NetworkState, device_means
# the jnp cost engine lives in core.latency since the population-scale
# refactor; re-imported (and re-exported) here for the fleet program and
# for back-compat with importers of repro.sim.fleet.PartitionBatchJ
from repro.core.latency import (CutProfile, PartitionBatchJ, _CST_KEYS,
                                _cluster_latency_j, _sum_left_to_right,
                                equal_split_x)
from repro.sim.controller import balanced_sizes
from repro.sim.dynamics import DynamicsCfg

__all__ = ["PartitionBatchJ", "SimFleetRunner", "fleet_trace_records",
           "recompute_fleet_latencies"]

_F_FLOOR = 1e7                      # compute floor, as NetworkProcess
POLICY_EQUAL, POLICY_GREEDY, POLICY_PROPOSED = 0, 1, 2
LAYOUT_RANK, LAYOUT_COMPUTE = 0, 1


# --------------------------------------------------------------------------
# in-jit per-slot control: balanced layout + spectrum policies
# --------------------------------------------------------------------------

def _layout_one(order, n_active, Ktgt, *, M: int, K: int):
    """Balanced clustering of the first ``n_active`` entries of ``order``
    into clusters of target size ``Ktgt`` — the jnp mirror of
    ``controller.balanced_sizes`` + consecutive chunking. Returns
    (dev (M, K), mask (M, K), csize (M,))."""
    n = n_active
    Mreal = jnp.where(n > 0, -(-n // Ktgt), 0)       # ceil(n / Ktgt)
    Msafe = jnp.maximum(Mreal, 1)
    base = n // Msafe
    extra = n - base * Msafe
    m_idx = jnp.arange(M)
    csize = jnp.where(m_idx < Mreal, base + (m_idx < extra), 0)
    starts = jnp.concatenate([jnp.zeros(1, csize.dtype),
                              jnp.cumsum(csize)[:-1]])
    k_idx = jnp.arange(K)
    pos = starts[:, None] + k_idx[None, :]
    mask = k_idx[None, :] < csize[:, None]
    dev = jnp.take(order, jnp.clip(pos, 0, order.shape[0] - 1))
    return jnp.where(mask, dev, 0), mask, csize


def _equal_xs(csize, mask, C: int):
    """Per-cluster equal split with remainder distribution — the jnp
    mirror of ``core.latency.equal_split_x`` (padded slots get 1 to keep
    divisions finite; they are masked out of every latency term). The
    remainder goes to the first ``C mod K`` SURVIVORS in slot order —
    on a contiguous plan mask that is slots 0..rem-1 (bit-identical to
    the pre-repair behavior), on a gappy post-repair mask it matches the
    host repair's equal split over the surviving member list."""
    safe = jnp.maximum(csize, 1)
    base = C // safe
    rem = C - base * safe
    srank = jnp.cumsum(mask, axis=-1) - 1            # survivor rank
    xs = base[..., None] + (srank < rem[..., None])
    return jnp.where(mask, xs, 1)


def _greedy_xs(cst_b, fd, rd, mask, csize, *, C: int, B: int, L: int,
               f_server_kappa: float, kappa: float, chunk: int = 0):
    """Lockstep greedy Alg. 3 over every (episode, cluster) slot: start
    at one subcarrier per device, then C - K_m gated steps each granting
    one subcarrier to the argmin-latency candidate — candidate values
    and first-min tie-breaks match ``core.resource.greedy_spectrum``
    (per-cluster decisions are independent, so lockstep == sequential).

    ``cst_b``: constants broadcastable against the (E, M, Kc, K)
    candidate tensor. Returns (E, M, K) int allocations summing to C on
    every real cluster.

    ``chunk`` > 0 streams the cluster axis through ``lax.map`` in tiles
    of that many clusters, bounding the (E, M, Kc, K) candidate tensor
    at (E, chunk, Kc, K). Padded clusters (csize 0, mask all-False,
    fd/rd 1) take no greedy steps, so real clusters' allocations are
    unchanged — per-cluster decisions are independent of the batch they
    ride in."""
    E, M, K = fd.shape
    if chunk and chunk < M:
        nch = -(-M // chunk)
        pad = nch * chunk - M

        def tiles(a, fill):
            if pad:
                pads = jnp.full((E, pad) + a.shape[2:], fill, a.dtype)
                a = jnp.concatenate([a, pads], axis=1)
            a = a.reshape((E, nch, chunk) + a.shape[2:])
            return jnp.moveaxis(a, 1, 0)         # (nch, E, chunk, ...)

        def one(t):
            fdc, rdc, mkc, csc = t
            return _greedy_xs(cst_b, fdc, rdc, mkc, csc, C=C, B=B, L=L,
                              f_server_kappa=f_server_kappa, kappa=kappa)

        X = jax.lax.map(one, (tiles(fd, 1.0), tiles(rd, 1.0),
                              tiles(mask, False), tiles(csize, 0)))
        return jnp.moveaxis(X, 0, 1).reshape(E, nch * chunk, K)[:, :M]

    eye = jnp.eye(K, dtype=jnp.int32)
    fd4, rd4 = fd[:, :, None, :], rd[:, :, None, :]
    mask4 = mask[:, :, None, :]
    csize4 = csize[:, :, None]

    def body(i, X):
        cand = X[:, :, None, :] + eye[None, None]            # (E,M,Kc,K)
        D = _cluster_latency_j(cst_b, fd4, rd4, cand, mask4, csize4,
                               B=B, L=L, C=C,
                               f_server_kappa=f_server_kappa, kappa=kappa)
        D = jnp.where(mask, D, jnp.inf)      # only real slots are cands
        best = jnp.argmin(D, axis=-1)                        # (E, M)
        inc = jax.nn.one_hot(best, K, dtype=X.dtype)
        allowed = (i < C - csize) & (csize > 0)
        return X + inc * allowed[..., None]

    X0 = jnp.ones((E, M, K), dtype=jnp.int32)
    # scan over a strongly-typed index instead of fori_loop: jax lowers
    # static-bound fori_loop with a weak int64 counter in the scan carry
    # under x64 — a recompile hazard the jit audit (JIT004) rejects
    X, _ = jax.lax.scan(lambda Xc, i: (body(i, Xc), None), X0,
                        jnp.arange(C - 1, dtype=jnp.int32))
    return X


# --------------------------------------------------------------------------
# in-jit Alg. 4 — lockstep Gibbs cells (the proposed policy's planner)
# --------------------------------------------------------------------------

def _gibbs_cells(cst, fG, rG, activeG, KtgtG, keyG, propG, *, M: int,
                 K: int, C: int, B: int, L: int, f_server_kappa: float,
                 kappa: float, delta: float, chunk: int = 0):
    """G independent Gibbs chains (Alg. 4 with embedded Alg. 3) in
    lockstep — the in-jit mirror of ``core.resource.gibbs_clustering``
    on pre-drawn randomness (its ``draws=`` path), decision-for-decision
    on shared draws.

    Per cell g: ``keyG[g]`` (N,) floats whose stable argsort over the
    active devices is the initial balanced layout, and ``propG[g]``
    (iters, 5) uniforms map per sweep to (cluster m, other cluster mp,
    member i, member j, Metropolis accept) by the exact uniform->index
    rule of the host path. Each sweep re-runs the 2-row greedy on the
    swapped clusters only (the other rows' latencies are carried), as
    the host's cluster-keyed cache does. Cells with fewer than two real
    clusters never accept (the host sets ``iters = 0``).

    ``cst``: per-cell (G,) profile constants. Returns
    (dev, mask, csize, xs, total) of the best-so-far state — mask and
    csize are swap-invariant, so they equal the initial layout's."""
    G, N = fG.shape
    g_ar = jnp.arange(G)
    g_idx = g_ar[:, None, None]
    cst3 = {k: v[:, None, None] for k, v in cst.items()}
    cst4 = {k: v[:, None, None, None] for k, v in cst.items()}
    kw = dict(B=B, L=L, C=C, f_server_kappa=f_server_kappa, kappa=kappa)

    n_act = jnp.sum(activeG, axis=1)
    order = jnp.argsort(jnp.where(activeG, keyG, jnp.inf), axis=1)
    lay = jax.vmap(functools.partial(_layout_one, M=M, K=K))
    dev, mask, csize = lay(order, n_act, KtgtG)
    fd = fG[g_idx, dev]
    rd = rG[g_idx, dev]
    xs = _greedy_xs(cst4, fd, rd, mask, csize, chunk=chunk, **kw)
    lat_m = _cluster_latency_j(cst3, fd, rd, xs, mask, csize, **kw)
    cur = _sum_left_to_right(lat_m)

    Mreal = jnp.where(n_act > 0, -(-n_act // KtgtG), 0)
    enabled = Mreal >= 2
    dsafe = max(float(delta), 1e-12)
    k_idx = jnp.arange(K)
    m_ar = jnp.arange(M)
    iters = propG.shape[1]

    def body(it, carry):
        dev, xs, lat_m, cur, b_tot, b_dev, b_xs = carry
        u = jax.lax.dynamic_index_in_dim(propG, it, axis=1,
                                         keepdims=False)    # (G, 5)
        # fixed uniform->index mapping (host gibbs_clustering draws path):
        # trunc(u * n) with a min() guard on the u == 1.0 edge
        m = jnp.clip(jnp.minimum((u[:, 0] * Mreal).astype(jnp.int32),
                                 Mreal - 1), 0, M - 1)
        mp = jnp.clip(jnp.minimum((u[:, 1] * (Mreal - 1)).astype(jnp.int32),
                                  Mreal - 2), 0, M - 1)
        mp = jnp.clip(mp + (mp >= m), 0, M - 1)
        cm, cmp_ = csize[g_ar, m], csize[g_ar, mp]
        i = jnp.clip(jnp.minimum((u[:, 2] * cm).astype(jnp.int32), cm - 1),
                     0, K - 1)
        j = jnp.clip(jnp.minimum((u[:, 3] * cmp_).astype(jnp.int32),
                                 cmp_ - 1), 0, K - 1)
        # candidate: swap member i of cluster m with member j of mp
        dm, dmp = dev[g_ar, m], dev[g_ar, mp]               # (G, K)
        vi, vj = dm[g_ar, i], dmp[g_ar, j]
        dm2 = jnp.where(k_idx[None, :] == i[:, None], vj[:, None], dm)
        dmp2 = jnp.where(k_idx[None, :] == j[:, None], vi[:, None], dmp)
        dev2 = jnp.stack([dm2, dmp2], axis=1)               # (G, 2, K)
        mask2 = jnp.stack([mask[g_ar, m], mask[g_ar, mp]], axis=1)
        cs2 = jnp.stack([cm, cmp_], axis=1)
        fd2 = fG[g_idx, dev2]
        rd2 = rG[g_idx, dev2]
        xs2 = _greedy_xs(cst4, fd2, rd2, mask2, cs2, **kw)
        lat2 = _cluster_latency_j(cst3, fd2, rd2, xs2, mask2, cs2, **kw)
        oh_m = m_ar[None, :] == m[:, None]                  # (G, M)
        oh_mp = m_ar[None, :] == mp[:, None]
        lat_new = jnp.where(oh_m, lat2[:, 0:1], lat_m)
        lat_new = jnp.where(oh_mp, lat2[:, 1:2], lat_new)
        new_tot = _sum_left_to_right(lat_new)
        eps = 1.0 / (1.0 + jnp.exp(jnp.minimum((new_tot - cur) / dsafe,
                                               700.0)))
        acc = enabled & (u[:, 4] < eps)
        um = (oh_m & acc[:, None])[:, :, None]
        ump = (oh_mp & acc[:, None])[:, :, None]
        dev_n = jnp.where(um, dm2[:, None, :], dev)
        dev_n = jnp.where(ump, dmp2[:, None, :], dev_n)
        xs_n = jnp.where(um, xs2[:, 0:1, :], xs)
        xs_n = jnp.where(ump, xs2[:, 1:2, :], xs_n)
        lat_n = jnp.where(oh_m & acc[:, None], lat2[:, 0:1], lat_m)
        lat_n = jnp.where(oh_mp & acc[:, None], lat2[:, 1:2], lat_n)
        cur_n = jnp.where(acc, new_tot, cur)
        better = cur_n < b_tot
        b_tot = jnp.where(better, cur_n, b_tot)
        b_dev = jnp.where(better[:, None, None], dev_n, b_dev)
        b_xs = jnp.where(better[:, None, None], xs_n, b_xs)
        return dev_n, xs_n, lat_n, cur_n, b_tot, b_dev, b_xs

    b_tot, b_dev, b_xs = cur, dev, xs
    if iters:
        carry = (dev, xs, lat_m, cur, b_tot, b_dev, b_xs)
        _, _, _, _, b_tot, b_dev, b_xs = jax.lax.fori_loop(
            0, iters, body, carry)
    return b_dev, mask, csize, b_xs, b_tot


# --------------------------------------------------------------------------
# the episode fleet program
# --------------------------------------------------------------------------

def _simulate(data, *, B: int, L: int, C: int, M: int, K: int, T: int,
              bw: float, kappa: float, f_server_kappa: float,
              f_sigma: float, snr_sigma: float, rho_f: float,
              rho_snr: float, coef_f: float, coef_s: float,
              p_compute: float, p_tx: float, track_energy: bool,
              greedy_rows: tuple, proposed_rows: tuple = (),
              gibbs_delta: float = 1e-4, p_depart: float = 0.0,
              p_arrive: float = 0.0, min_floor: int = 0,
              epoch_len: int = 1, saa_cuts: tuple = (),
              n_reserve: int = 0, cost_chunk: int = 0):
    """The whole E-episode, T-slot simulation as one scan.

    ``data``: one pytree of episode arrays — means/innovations
    (E, N) / (T, E, N), grid selectors (E,), the per-cut constant table
    ``cst_full`` {key: (n_cuts,)}, churn schedules, and (when the grid
    needs them) pre-drawn uniforms for Bernoulli churn (``u_dep``
    (T, E, N), ``u_arr`` (T, E)), the proposed arm's per-slot Gibbs
    draws (``gkey`` (T, P, R, N), ``gprop`` (T, P, R, iters, 5)) and
    per-epoch SAA draws (``saa_eta`` (n_ep, P, J, 2, N), ``saa_key``
    (n_ep, P, J, R, N), ``saa_prop`` (n_ep, P, J, R, S, 5)).

    ``greedy_rows`` / ``proposed_rows`` (host-static tuples) are the
    episode indices on those policies — policy-specific work runs only
    on its rows. Slot order (the fleet convention, mirrored by
    ``SimFleetRunner.run_reference``): scheduled churn -> SAA (epoch
    boundaries) -> plan -> Bernoulli departures -> repair -> price ->
    energy -> stochastic arrival -> AR(1) evolve; arrivals take effect
    the next slot. Returns a dict of slot-major stacked traces whose
    mask/xs/csize are the EXECUTED (post-repair) decision."""
    mu_f, mu_snr = data["mu_f"], data["mu_snr"]
    depart, arrive = data["depart"], data["arrive"]
    Ktgt, perm_rank = data["Ktgt"], data["perm_rank"]
    cst_full = data["cst_full"]
    E, N = mu_f.shape
    e_idx = jnp.arange(E)[:, None, None]
    gi = jnp.asarray(greedy_rows, dtype=jnp.int32)
    pi = jnp.asarray(proposed_rows, dtype=jnp.int32)
    P = len(proposed_rows)
    by_compute = (data["layout_mode"] == LAYOUT_COMPUTE)[:, None]
    lay = jax.vmap(functools.partial(_layout_one, M=M, K=K))
    use_churn = p_depart > 0.0
    use_arr = p_arrive > 0.0
    use_saa = bool(saa_cuts) and P > 0
    gkw = dict(M=M, K=K, C=C, B=B, L=L, f_server_kappa=f_server_kappa,
               kappa=kappa, delta=gibbs_delta, chunk=cost_chunk)
    # rows whose repair re-runs the greedy Alg. 3 (vs equal split)
    grr = tuple(sorted(set(greedy_rows) | set(proposed_rows)))
    gri = jnp.asarray(grr, dtype=jnp.int32)
    is_res = jnp.arange(N) >= N - n_reserve if n_reserve \
        else jnp.zeros(N, dtype=bool)

    def dyn(a, i):
        return jax.lax.dynamic_index_in_dim(a, i, axis=0, keepdims=False)

    if use_saa:
        vC = jnp.asarray([v - 1 for v in saa_cuts], jnp.int32)
        V = len(saa_cuts)
        J = data["saa_eta"].shape[2]
        Rs = data["saa_key"].shape[3]
        Ss = data["saa_prop"].shape[4]
    if P:
        R = data["gkey"].shape[2]
        Gi = data["gprop"].shape[3]

    f0 = jnp.maximum(mu_f + f_sigma * data["eta_f0"], _F_FLOOR)
    snr0 = mu_snr + snr_sigma * data["eta_s0"]
    # devices scheduled to never be present (depart <= arrive) start
    # departed; reserve rows carry (T, T) sentinels and must not
    departed0 = (depart <= arrive) & ~is_res[None, :]

    def step(carry, inp):
        f, snr, energy, depleted, departed, arrdyn, v_idx = carry
        t, eps_f_t, eps_s_t = inp
        rate = bw * jnp.log2(1.0 + 10.0 ** (snr / 10.0))

        # -- scheduled churn at slot start (gid order, floor-gated) ----
        arrived = (arrive <= t) | arrdyn
        alive = arrived & ~departed
        n0 = jnp.sum(alive, axis=1)
        sched = alive & (depart == t)
        ex = sched & (jnp.cumsum(sched, axis=1)
                      <= (n0 - min_floor)[:, None])
        departed = departed | ex
        active = arrived & ~departed
        n_active = jnp.sum(active, axis=1)

        # -- large timescale: SAA cut re-selection (Alg. 2) ------------
        if use_saa:
            def saa_update(vx):
                ep = t // epoch_len
                eta = dyn(data["saa_eta"], ep)       # (P, J, 2, N)
                skey = dyn(data["saa_key"], ep)      # (P, J, R, N)
                sprop = dyn(data["saa_prop"], ep)    # (P, J, R, S, 5)
                muPf, muPs = mu_f[pi], mu_snr[pi]
                fJ = jnp.maximum(muPf[:, None] + f_sigma * eta[:, :, 0],
                                 _F_FLOOR)           # (P, J, N)
                rJ = bw * jnp.log2(1.0 + 10.0 ** (
                    (muPs[:, None] + snr_sigma * eta[:, :, 1]) / 10.0))
                G = P * V * J * Rs
                sh = (P, V, J, Rs)

                def bc(a, tail):
                    return jnp.broadcast_to(a, sh + tail).reshape(
                        (G,) + tail)

                f_c = bc(fJ[:, None, :, None], (N,))
                r_c = bc(rJ[:, None, :, None], (N,))
                a_c = bc(active[pi][:, None, None, None], (N,))
                k_c = bc(Ktgt[pi][:, None, None, None], ())
                key_c = bc(skey[:, None], (N,))
                prop_c = bc(sprop[:, None], (Ss, 5))
                cst_c = {k: bc(a[vC][None, :, None, None], ())
                         for k, a in cst_full.items()}
                _, _, _, _, tot = _gibbs_cells(
                    cst_c, f_c, r_c, a_c, k_c, key_c, prop_c, **gkw)
                tot = tot.reshape(sh).min(axis=3)    # best-of-chains
                means = _sum_left_to_right(tot) / J  # (P, V)
                vstar = vC[jnp.argmin(means, axis=1)]
                nP = jnp.sum(active[pi], axis=1)
                return vx.at[pi].set(jnp.where(nP > 0, vstar, vx[pi]))

            v_idx = jax.lax.cond(t % epoch_len == 0, saa_update,
                                 lambda vx: vx, v_idx)

        cstE = {k: a[v_idx] for k, a in cst_full.items()}    # (E,)
        cst3 = {k: a[:, None, None] for k, a in cstE.items()}

        # -- small timescale: balanced layout (equal/greedy arms) ------
        sortval = jnp.where(by_compute, f, perm_rank)
        order = jnp.argsort(jnp.where(active, sortval, jnp.inf), axis=1)
        dev, mask, csize = lay(order, n_active, Ktgt)

        # -- small timescale: Gibbs plan on the proposed rows ----------
        if P:
            gk = dyn(data["gkey"], t)                # (P, R, N)
            gp = dyn(data["gprop"], t)               # (P, R, Gi, 5)
            G2 = P * R
            f_c = jnp.broadcast_to(f[pi][:, None], (P, R, N)
                                   ).reshape(G2, N)
            r_c = jnp.broadcast_to(rate[pi][:, None], (P, R, N)
                                   ).reshape(G2, N)
            a_c = jnp.broadcast_to(active[pi][:, None], (P, R, N)
                                   ).reshape(G2, N)
            k_c = jnp.broadcast_to(Ktgt[pi][:, None], (P, R)).reshape(G2)
            cst_c = {k: jnp.broadcast_to(a[v_idx[pi]][:, None], (P, R)
                                         ).reshape(G2)
                     for k, a in cst_full.items()}
            dev_c, _, _, xs_c, tot_c = _gibbs_cells(
                cst_c, f_c, r_c, a_c, k_c, gk.reshape(G2, N),
                gp.reshape(G2, Gi, 5), **gkw)
            b = jnp.argmin(tot_c.reshape(P, R), axis=1)  # best chain
            ar = jnp.arange(P)
            # mask/csize equal the balanced layout's (swap-invariant)
            dev = dev.at[pi].set(dev_c.reshape(P, R, M, K)[ar, b])
            xs_p = xs_c.reshape(P, R, M, K)[ar, b]

        fd = f[e_idx, dev]
        rd = rate[e_idx, dev]
        xs = _equal_xs(csize, mask, C)
        if greedy_rows:
            # per-episode decisions are independent, so running greedy
            # on the greedy-policy rows alone is exact
            cst4g = {k: a[gi][:, None, None, None] for k, a in cstE.items()}
            xs = xs.at[gi].set(_greedy_xs(
                cst4g, fd[gi], rd[gi], mask[gi], csize[gi], B=B, L=L,
                C=C, f_server_kappa=f_server_kappa, kappa=kappa,
                chunk=cost_chunk))
        if P:
            xs = xs.at[pi].set(xs_p)

        # -- Bernoulli departures + in-slot repair ---------------------
        if use_churn:
            u_t = dyn(data["u_dep"], t)
            wants = active & (u_t < p_depart)
            gone = wants & (jnp.cumsum(wants, axis=1)
                            <= (n_active - min_floor)[:, None])
            departed = departed | gone
            member_gone = mask & gone[e_idx, dev]
            affected = member_gone.any(axis=-1)               # (E, M)
            mask = mask & ~member_gone
            csize = jnp.sum(mask, axis=-1)
            xs_rep = _equal_xs(csize, mask, C)
            if grr:
                cst4r = {k: a[gri][:, None, None, None]
                         for k, a in cstE.items()}
                xs_rep = xs_rep.at[gri].set(_greedy_xs(
                    cst4r, fd[gri], rd[gri], mask[gri], csize[gri],
                    B=B, L=L, C=C, f_server_kappa=f_server_kappa,
                    kappa=kappa, chunk=cost_chunk))
            xs = jnp.where(affected[:, :, None], xs_rep, xs)

        clat = _cluster_latency_j(cst3, fd, rd, xs, mask, csize, B=B,
                                  L=L, C=C, f_server_kappa=f_server_kappa,
                                  kappa=kappa)
        latency = _sum_left_to_right(clat)

        # -- energy drain of the executed round ------------------------
        if track_energy:
            fdk = fd * kappa
            t_comp = L * B * (cst3["gamma_dF"] + cst3["gamma_dB"]) / fdk
            t_tx = (L * B * cst3["xi_s"] + cst3["xi_d"]) / (xs * rd)
            j_slot = p_compute * t_comp + p_tx * t_tx
            j = jnp.zeros((E, N)).at[e_idx, dev].add(
                jnp.where(mask, j_slot, 0.0))
            if min_floor:
                # NetworkProcess.consume semantics: floor-pinned devices
                # stay active with the battery clamped at 0 and leave
                # (cause="energy_depleted") once the floor lifts; the
                # leave gate runs in gid order like the host loop
                executed = jnp.zeros((E, N), dtype=bool
                                     ).at[e_idx, dev].max(mask)
                n_alive2 = jnp.sum(arrived & ~departed, axis=1)
                pinned = executed & depleted
                drain = executed & ~depleted
                e_un = jnp.where(drain, energy - j, energy)
                newly = drain & (e_un <= 0.0)
                wants_leave = pinned | newly
                leave = wants_leave & (
                    jnp.cumsum(wants_leave, axis=1)
                    <= (n_alive2 - min_floor)[:, None])
                departed = departed | leave
                depleted_next = depleted | newly
                energy_next = jnp.where(drain, jnp.maximum(e_un, 0.0),
                                        energy)
            else:
                e_un = energy - j
                depleted_next = depleted | (active & (e_un <= 0.0))
                departed = departed | (active & (e_un <= 0.0))
                energy_next = jnp.maximum(e_un, 0.0)
        else:
            energy_next, depleted_next = energy, depleted

        # -- stochastic arrival (at most one per slot, next-slot) ------
        if use_arr:
            u_a = dyn(data["u_arr"], t)                       # (E,)
            cand = is_res[None, :] & ~arrdyn & ~departed
            arr_now = (u_a < p_arrive) & cand.any(axis=1)
            idxr = jnp.argmax(cand, axis=1)                   # lowest gid
            arrdyn = arrdyn | ((jnp.arange(N)[None, :] == idxr[:, None])
                               & arr_now[:, None])

        # -- AR(1) evolution for the next slot -------------------------
        snr_next = mu_snr + rho_snr * (snr - mu_snr) + coef_s * eps_s_t
        f_next = jnp.maximum(
            mu_f + rho_f * (f - mu_f) + coef_f * eps_f_t, _F_FLOOR)

        ys = {"f": f, "rate": rate, "active": active,
              "n_active": n_active, "dev": dev, "mask": mask, "xs": xs,
              "csize": csize, "cluster_latency": clat, "latency": latency,
              "energy": energy_next, "v": v_idx + 1}
        return ((f_next, snr_next, energy_next, depleted_next, departed,
                 arrdyn, v_idx), ys)

    init = (f0, snr0, data["energy0"], jnp.zeros((E, N), dtype=bool),
            departed0, jnp.zeros((E, N), dtype=bool), data["v0"])
    _, ys = jax.lax.scan(step, init,
                         (jnp.arange(T), data["eps_f"], data["eps_s"]))
    return ys


# --------------------------------------------------------------------------
# runner
# --------------------------------------------------------------------------

class SimFleetRunner:
    """Prices a ``SimFleetCfg`` grid of dynamic-network episodes in one
    jitted dispatch (``run``), with a decision-identical looped NumPy
    mirror (``run_reference`` / ``run_looped`` — the reference oracle
    and the bench baseline) and optional coupling to ``CPSL.run_fleet``
    (``train_curves``).

    Dynamics come from ``DynamicsCfg``: rho_snr / rho_f, the energy
    budget + power draws, ``forced_departures`` (converted to the
    per-device ``depart_slots`` schedule), and stochastic churn —
    ``p_depart`` Bernoulli departures (pre-drawn per-slot uniforms,
    decision-identical to ``NetworkProcess.sample_departures`` on shared
    draws) and ``p_arrive`` arrivals into ``SimFleetCfg.n_reserve``
    pre-provisioned reserve devices whose means are drawn host-side up
    front (``NetworkProcess`` draws them on the fly — the one remaining
    semantic difference). The ``min_devices`` floor applies when
    ``SimFleetCfg.min_devices_floor`` is set; otherwise every scheduled
    departure / depletion executes.

    The ``"proposed"`` policy runs the paper's full two-timescale
    controller in-jit: Gibbs + greedy (Algs. 3/4, best of
    ``gibbs_chains`` lockstep chains) every slot, SAA cut re-selection
    (Alg. 2) every ``epoch_len`` slots over ``saa_cuts`` (None = keep
    the spec's fixed cut, no SAA), and in-slot spectrum repair after
    Bernoulli departures. All its randomness is pre-drawn per episode
    SEED, so same-seed arms stay CRN-coupled and ``run_reference`` can
    replay the identical decisions through the host
    ``TwoTimescaleController`` ``draws=`` hooks.

    ``perms`` sets per-episode cluster orderings (default: device-id
    order): an (N,) / (E, N) array, or a ``{seed: permutation}`` dict —
    the dict form assigns each episode its seed's permutation without
    the caller having to know the runner's episode ordering (fig. 7
    keeps its per-run random clusters CRN-coupled across cuts this
    way); ``layout_modes`` (E,) selects rank (0, default) vs
    sort-by-current-compute (1) clustering; ``policy_overrides`` (E,)
    rewrites the grid's per-episode policy in place (fig. 8(b) builds
    its three arms over one seed axis this way); ``n_clusters`` caps
    the padded cluster axis M (default: worst-case ``ceil(N / k)``) —
    tightening it trips the capacity guard if the arrive/depart
    schedules could overflow ``M * cluster_size`` active devices.

    ``depart_slots`` / ``arrive_slots`` ((N,) or (E, N)) are explicit
    churn schedules; an explicit ``depart_slots`` WINS over
    ``DynamicsCfg.forced_departures`` (which is only consulted when no
    explicit schedule is given)."""

    def __init__(self, prof: CutProfile, ncfg: NetworkCfg,
                 dcfg: DynamicsCfg, fcfg: SimFleetCfg, *,
                 perms=None,
                 layout_modes: Optional[Sequence[int]] = None,
                 depart_slots: Optional[np.ndarray] = None,
                 arrive_slots: Optional[np.ndarray] = None,
                 policy_overrides: Optional[Sequence[str]] = None,
                 n_clusters: Optional[int] = None):
        self.prof, self.ncfg, self.dcfg, self.fcfg = prof, ncfg, dcfg, fcfg
        N_base, C, T = ncfg.n_devices, ncfg.n_subcarriers, fcfg.rounds
        for k in fcfg.cluster_sizes:
            assert 1 <= k <= C, f"cluster size {k} infeasible for C={C}"
        for p in fcfg.policies:
            assert p in ("equal", "greedy", "proposed"), p
        self.specs: List[dict] = [
            {"cut": int(v), "policy": p, "cluster_size": int(k),
             "seed": int(s)}
            for v in fcfg.cuts for p in fcfg.policies
            for k in fcfg.cluster_sizes for s in fcfg.seeds]
        if policy_overrides is not None:
            assert len(policy_overrides) == len(self.specs)
            for sp, p in zip(self.specs, policy_overrides):
                assert p in ("equal", "greedy", "proposed"), p
                sp["policy"] = p
        E = len(self.specs)
        n_res = int(fcfg.n_reserve) if dcfg.p_arrive > 0 else 0
        if dcfg.p_arrive > 0:
            assert fcfg.n_reserve > 0, \
                "stochastic arrivals need SimFleetCfg.n_reserve slots"
        N = N_base + n_res
        self.E, self.N, self.T = E, N, T
        self.N_base, self.n_reserve = N_base, n_res
        self.M = (int(n_clusters) if n_clusters is not None
                  else max(-(-N // k) for k in fcfg.cluster_sizes))
        self.K = max(fcfg.cluster_sizes)
        self.R = max(1, fcfg.gibbs_chains)
        self._min_floor = int(dcfg.min_devices) if fcfg.min_devices_floor \
            else 0
        seeds = sorted({sp["seed"] for sp in self.specs})

        means = {}
        for sp in self.specs:
            ms = fcfg.mean_seed if fcfg.mean_seed is not None else sp["seed"]
            if ms not in means:
                mu_f, mu_snr = device_means(ncfg, ms)
                if n_res:
                    # reserve-device means, pre-drawn (NetworkProcess
                    # draws arrivals' means from its live stream; the
                    # fleet fixes them up front, per mean seed)
                    r = streams.fleet_reserve_means_rng(ms)
                    if ncfg.homogeneous:
                        rf = np.full(n_res, float(ncfg.f_homog))
                        rs_ = np.full(n_res, float(ncfg.snr_homog_db))
                    else:
                        rf = r.uniform(*ncfg.f_mean_range, size=n_res)
                        rs_ = r.uniform(*ncfg.snr_mean_range_db,
                                        size=n_res)
                    mu_f = np.concatenate([mu_f, rf])
                    mu_snr = np.concatenate([mu_snr, rs_])
                means[ms] = (mu_f, mu_snr)
        self._mu_f = np.stack([means[fcfg.mean_seed if fcfg.mean_seed
                                     is not None else sp["seed"]][0]
                               for sp in self.specs]).astype(np.float64)
        self._mu_snr = np.stack([means[fcfg.mean_seed if fcfg.mean_seed
                                       is not None else sp["seed"]][1]
                                 for sp in self.specs]).astype(np.float64)

        # per-episode innovation streams keyed by the episode SEED (same
        # seed -> same realization: CRN coupling across cuts/policies)
        with jax.enable_x64(True):
            master = streams.fleet_master_key(dcfg.seed)
            draws = {}
            for sp in self.specs:
                s = sp["seed"]
                if s not in draws:
                    draws[s] = np.asarray(jax.random.normal(
                        jax.random.fold_in(master, s), (T + 1, 2, N),
                        dtype=jnp.float64))
        stk = np.stack([draws[sp["seed"]] for sp in self.specs])  # (E,T+1,2,N)
        self._eta_f0, self._eta_s0 = stk[:, 0, 0], stk[:, 0, 1]
        self._eps_f = np.ascontiguousarray(
            stk[:, 1:, 0].transpose(1, 0, 2))                    # (T, E, N)
        self._eps_s = np.ascontiguousarray(stk[:, 1:, 1].transpose(1, 0, 2))

        self._cst_full = {k: np.asarray(getattr(prof, k), np.float64)
                          for k in _CST_KEYS}
        self._v0 = np.array([sp["cut"] - 1 for sp in self.specs], np.int32)
        self._Ktgt = np.array([sp["cluster_size"] for sp in self.specs],
                              np.int32)
        self._policy = np.array(
            [POLICY_PROPOSED if sp["policy"] == "proposed"
             else POLICY_GREEDY if sp["policy"] == "greedy"
             else POLICY_EQUAL for sp in self.specs], np.int32)
        greedy_rows = tuple(
            np.flatnonzero(self._policy == POLICY_GREEDY).tolist())
        self._prows = tuple(
            np.flatnonzero(self._policy == POLICY_PROPOSED).tolist())
        self._mode = (np.zeros(E, np.int32) if layout_modes is None
                      else np.asarray(layout_modes, np.int32))
        assert self._mode.shape == (E,)

        if perms is None:
            perms = np.arange(N)
        elif isinstance(perms, dict):
            perms = np.stack([np.asarray(perms[sp["seed"]], np.int64)
                              for sp in self.specs])
        else:
            perms = np.asarray(perms, np.int64)
        if n_res and perms.shape[-1] == N_base:
            # caller permutations cover the base population; reserve
            # devices append in gid order
            ext = np.broadcast_to(np.arange(N_base, N),
                                  perms.shape[:-1] + (n_res,))
            perms = np.concatenate([perms, ext], axis=-1)
        perms = np.broadcast_to(perms, (E, N))
        rank = np.empty((E, N), np.float64)
        for e in range(E):
            rank[e, perms[e]] = np.arange(N)
        self._perm_rank = rank

        # churn schedules: an explicit depart_slots wins outright;
        # forced_departures is the fallback (satellite-1 fix — the old
        # np.minimum merge made later explicit slots unreachable)
        self._depart = np.full((E, N), T, np.int64)
        if depart_slots is not None:
            self._depart[:, :N_base] = np.broadcast_to(
                np.asarray(depart_slots, np.int64), (E, N_base))
        else:
            for slot, ids in dcfg.forced_departures.items():
                for gid in ids:
                    if gid < N_base:
                        self._depart[:, gid] = np.minimum(
                            self._depart[:, gid], slot)
        self._arrive = np.zeros((E, N), np.int64)
        if n_res:
            self._arrive[:, N_base:] = T        # reserve: arrival-only
        if arrive_slots is not None:
            self._arrive[:, :N_base] = np.broadcast_to(
                np.asarray(arrive_slots, np.int64), (E, N_base))
        self._energy0 = np.full((E, N), float(dcfg.energy_budget_j))

        # capacity guard (satellite 3): _layout_one silently truncates
        # clusters past M rows, so the worst-case active count per the
        # schedules must fit M * cluster_size. With the floor on,
        # blocked departures can keep everyone alive -> departs ignored.
        t_ar = np.arange(max(T, 1))[:, None]
        for e, sp in enumerate(self.specs):
            ab = self._arrive[e, :N_base][None, :]
            db = self._depart[e, :N_base][None, :]
            present = (ab <= t_ar) if self._min_floor \
                else ((ab <= t_ar) & (t_ar < db))
            worst = int(present.sum(axis=1).max()) + n_res
            cap = self.M * sp["cluster_size"]
            if worst > cap:
                raise ValueError(
                    f"episode {e}: worst-case {worst} active devices "
                    f"exceed the M*K layout capacity {cap} "
                    f"(M={self.M}, cluster_size={sp['cluster_size']}); "
                    "raise n_clusters or trim the arrive/depart schedules")

        # pre-drawn uniforms, per episode seed (CRN across same-seed
        # arms; distinct fixed stream ids keep them independent)
        if dcfg.p_depart > 0:
            ud = {s: streams.fleet_departures_rng(dcfg.seed, s)
                  .random((T, N)) for s in seeds}
            self._u_dep = np.stack([ud[sp["seed"]] for sp in self.specs],
                                   axis=1)                    # (T, E, N)
        if dcfg.p_arrive > 0:
            ua = {s: streams.fleet_arrivals_rng(dcfg.seed, s).random(T)
                  for s in seeds}
            self._u_arr = np.stack([ua[sp["seed"]] for sp in self.specs],
                                   axis=1)                    # (T, E)
        use_saa = fcfg.saa_cuts is not None and bool(self._prows)
        if self._prows:
            R, Gi = self.R, fcfg.gibbs_iters
            gd = {}
            for s in seeds:
                r = streams.fleet_gibbs_rng(dcfg.seed, s)
                gd[s] = (r.random((T, R, N)), r.random((T, R, Gi, 5)))
            self._gkey = np.stack(
                [gd[self.specs[e]["seed"]][0] for e in self._prows],
                axis=1)                                       # (T,P,R,N)
            self._gprop = np.stack(
                [gd[self.specs[e]["seed"]][1] for e in self._prows],
                axis=1)                                       # (T,P,R,Gi,5)
        if use_saa:
            n_ep = -(-T // fcfg.epoch_len)
            J, S = fcfg.saa_samples, fcfg.saa_gibbs_iters
            sd = {}
            for s in seeds:
                r = streams.fleet_saa_rng(dcfg.seed, s)
                sd[s] = (r.standard_normal((n_ep, J, 2, N)),
                         r.random((n_ep, J, self.R, N)),
                         r.random((n_ep, J, self.R, S, 5)))
            self._saa_eta = np.stack(
                [sd[self.specs[e]["seed"]][0] for e in self._prows],
                axis=1)                                   # (n_ep,P,J,2,N)
            self._saa_key = np.stack(
                [sd[self.specs[e]["seed"]][1] for e in self._prows],
                axis=1)                                   # (n_ep,P,J,R,N)
            self._saa_prop = np.stack(
                [sd[self.specs[e]["seed"]][2] for e in self._prows],
                axis=1)                                   # (n_ep,P,J,R,S,5)

        self._sim = jax.jit(functools.partial(
            _simulate, B=fcfg.batch_per_device, L=fcfg.local_epochs, C=C,
            M=self.M, K=self.K, T=T, bw=ncfg.subcarrier_bw,
            kappa=float(ncfg.kappa),
            f_server_kappa=ncfg.f_server * ncfg.kappa,
            f_sigma=float(ncfg.f_sigma), snr_sigma=float(ncfg.snr_sigma_db),
            rho_f=float(dcfg.rho_f), rho_snr=float(dcfg.rho_snr),
            coef_f=np.sqrt(1.0 - dcfg.rho_f ** 2) * ncfg.f_sigma,
            coef_s=np.sqrt(1.0 - dcfg.rho_snr ** 2) * ncfg.snr_sigma_db,
            p_compute=float(dcfg.p_compute_w), p_tx=float(dcfg.p_tx_w),
            track_energy=dcfg.energy_budget_j > 0,
            greedy_rows=greedy_rows, proposed_rows=self._prows,
            gibbs_delta=float(fcfg.gibbs_delta),
            p_depart=float(dcfg.p_depart), p_arrive=float(dcfg.p_arrive),
            min_floor=self._min_floor, epoch_len=int(fcfg.epoch_len),
            saa_cuts=tuple(fcfg.saa_cuts) if use_saa else (),
            n_reserve=n_res, cost_chunk=int(fcfg.cost_chunk)))

    # -- batched dispatch -----------------------------------------------------

    def sim_inputs(self) -> dict:
        """The ``_sim`` argument dict (x64 device arrays).  Split out of
        ``run`` so static tooling (``repro.analysis.jit_audit``) can
        lower the exact program ``run`` dispatches without executing it.
        Call under ``jax.enable_x64(True)`` — the cost model's contract
        dtype."""
        data = {"mu_f": jnp.asarray(self._mu_f),
                "mu_snr": jnp.asarray(self._mu_snr),
                "eta_f0": jnp.asarray(self._eta_f0),
                "eta_s0": jnp.asarray(self._eta_s0),
                "eps_f": jnp.asarray(self._eps_f),
                "eps_s": jnp.asarray(self._eps_s),
                "cst_full": {k: jnp.asarray(v)
                             for k, v in self._cst_full.items()},
                "Ktgt": jnp.asarray(self._Ktgt),
                "layout_mode": jnp.asarray(self._mode),
                "perm_rank": jnp.asarray(self._perm_rank),
                "depart": jnp.asarray(self._depart),
                "arrive": jnp.asarray(self._arrive),
                "energy0": jnp.asarray(self._energy0),
                "v0": jnp.asarray(self._v0)}
        for name in ("u_dep", "u_arr", "gkey", "gprop",
                     "saa_eta", "saa_key", "saa_prop"):
            arr = getattr(self, "_" + name, None)
            if arr is not None:
                data[name] = jnp.asarray(arr)
        return data

    def run(self) -> dict:
        """One jitted dispatch for the whole grid. Returns ``{"episodes":
        [spec + latency_s/sim_time_s/n_active curves], "trace": {episode-
        major arrays}, "wall_s"}``."""
        with jax.enable_x64(True):
            data = self.sim_inputs()
            t0 = time.monotonic()
            ys = self._sim(data)
            jax.block_until_ready(ys["latency"])
            wall = time.monotonic() - t0
        trace = {k: np.asarray(v).swapaxes(0, 1) for k, v in ys.items()}
        cum = np.cumsum(trace["latency"], axis=1)
        episodes = []
        for e, sp in enumerate(self.specs):
            episodes.append(dict(
                sp, latency_s=trace["latency"][e].tolist(),
                sim_time_s=cum[e].tolist(),
                n_active=trace["n_active"][e].tolist()))
        return {"episodes": episodes, "trace": trace, "wall_s": wall}

    # -- looped NumPy mirror (oracle + bench baseline) ------------------------

    def run_reference(self, e: int) -> List[dict]:
        """Episode ``e`` replayed as a host NumPy loop — identical
        innovations, pre-drawn churn/controller uniforms, and decision
        rules, host ``round_latency`` pricing (the proposed arm goes
        through the real ``TwoTimescaleController`` on its ``draws=``
        hooks). Returns SimEngine-style per-round records."""

        sp = self.specs[e]
        ncfg, prof, dcfg, fcfg = self.ncfg, self.prof, self.dcfg, self.fcfg
        B, L = fcfg.batch_per_device, fcfg.local_epochs
        v, Ktgt = sp["cut"], sp["cluster_size"]
        policy = sp["policy"]
        proposed = policy == "proposed"
        C, N, T, R = ncfg.n_subcarriers, self.N, self.T, self.R
        mu_f, mu_snr = self._mu_f[e], self._mu_snr[e]
        coef_f = np.sqrt(1.0 - dcfg.rho_f ** 2) * ncfg.f_sigma
        coef_s = np.sqrt(1.0 - dcfg.rho_snr ** 2) * ncfg.snr_sigma_db
        track = dcfg.energy_budget_j > 0
        floor = self._min_floor
        c = prof.at(v)
        ctrl = None
        saa_on = False
        if proposed:
            from repro.configs.base import SimCfg
            from repro.sim.controller import TwoTimescaleController
            saa_on = fcfg.saa_cuts is not None
            scfg = SimCfg(rounds=T, epoch_len=fcfg.epoch_len,
                          cluster_size=Ktgt,
                          saa_samples=fcfg.saa_samples,
                          saa_gibbs_iters=fcfg.saa_gibbs_iters,
                          gibbs_iters=fcfg.gibbs_iters, gibbs_chains=R,
                          cuts=(tuple(fcfg.saa_cuts) if saa_on else (v,)),
                          seed=0)
            ctrl = TwoTimescaleController(prof, ncfg, B, L, scfg)
            ctrl.v = v
            p_loc = self._prows.index(e)

        f = np.maximum(mu_f + ncfg.f_sigma * self._eta_f0[e], _F_FLOOR)
        snr = mu_snr + ncfg.snr_sigma_db * self._eta_s0[e]
        energy = self._energy0[e].copy()
        depleted = np.zeros(N, dtype=bool)
        arrdyn = np.zeros(N, dtype=bool)
        is_res = np.arange(N) >= N - self.n_reserve if self.n_reserve \
            else np.zeros(N, dtype=bool)
        departed = ((self._depart[e] <= self._arrive[e]) & ~is_res)
        recs, sim_time = [], 0.0
        for t in range(T):
            # scheduled churn at slot start (gid order, floor-gated)
            arrived = (self._arrive[e] <= t) | arrdyn
            n_alive = int((arrived & ~departed).sum())
            for gid in np.flatnonzero(arrived & ~departed
                                      & (self._depart[e] == t)):
                if n_alive > floor:
                    departed[gid] = True
                    n_alive -= 1
            active = arrived & ~departed
            ids = np.flatnonzero(active)
            n = len(ids)
            rate = ncfg.subcarrier_bw * np.log2(1.0 + 10.0 ** (snr / 10.0))
            net = NetworkState(f=f.copy(), rate=rate)

            # large timescale (proposed arm): SAA cut re-selection
            if proposed and saa_on and t % fcfg.epoch_len == 0 and n:
                ep = t // fcfg.epoch_len
                J = fcfg.saa_samples
                draws = {
                    "eta": self._saa_eta[ep, p_loc][:, :, ids],
                    "gibbs": [[(self._saa_key[ep, p_loc, j, r][ids],
                                self._saa_prop[ep, p_loc, j, r])
                               for r in range(R)] for j in range(J)]}
                ctrl.select_cut(mu_f[ids], mu_snr[ids], t, draws=draws)
            v_t = ctrl.v if proposed else v

            # small timescale: the slot plan
            clusters: List[List[int]] = []
            xs: List[np.ndarray] = []
            if n:
                if proposed:
                    net_act = NetworkState(f=f[ids].copy(),
                                           rate=rate[ids].copy())
                    pd = [(self._gkey[t, p_loc, r][ids],
                           self._gprop[t, p_loc, r]) for r in range(R)]
                    plan = ctrl.plan_slot(net_act, ids, t, draws=pd)
                    clusters = plan.global_clusters()
                    xs = [np.asarray(x) for x in plan.xs]
                else:
                    sortval = (f if self._mode[e] == LAYOUT_COMPUTE
                               else self._perm_rank[e])
                    order = np.argsort(np.where(active, sortval, np.inf),
                                       kind="stable")
                    sizes = balanced_sizes(n, Ktgt)
                    bounds = np.concatenate([[0], np.cumsum(sizes)])
                    clusters = [[int(d) for d in
                                 order[bounds[m]:bounds[m + 1]]]
                                for m in range(len(sizes))]
                    for cl in clusters:
                        if policy == "greedy":
                            x, _ = rs.SpectrumTable(
                                v_t, cl, net, ncfg, prof, B, L).greedy(cl)
                        else:
                            x = equal_split_x(len(cl), C)
                        xs.append(np.asarray(x))

            # Bernoulli departures + in-slot repair
            gone: set = set()
            if dcfg.p_depart > 0:
                u = self._u_dep[t, e]
                n_act = n
                for gid in ids:
                    if n_act <= floor:
                        break
                    if u[gid] < dcfg.p_depart:
                        departed[gid] = True
                        gone.add(int(gid))
                        n_act -= 1
            if gone and clusters:
                kept_c, kept_x = [], []
                for cl, x in zip(clusters, xs):
                    keep = [d for d in cl if d not in gone]
                    if not keep:
                        continue
                    if len(keep) == len(cl):
                        kept_c.append(cl)
                        kept_x.append(x)
                    else:
                        if policy in ("greedy", "proposed"):
                            x2, _ = rs.SpectrumTable(
                                v_t, keep, net, ncfg, prof, B, L).greedy(keep)
                        else:
                            x2 = equal_split_x(len(keep), C)
                        kept_c.append(keep)
                        kept_x.append(np.asarray(x2))
                clusters, xs = kept_c, kept_x

            latency = (lt.round_latency(v_t, clusters, xs, net, ncfg,
                                        prof, B, L) if clusters else 0.0)
            sim_time += latency
            recs.append({"round": t, "v": int(v_t), "n_active": n,
                         "clusters": clusters,
                         "xs": [np.asarray(x) for x in xs],
                         "f": f.copy(), "rate": rate,
                         "latency_s": float(latency),
                         "sim_time_s": float(sim_time)})
            if not clusters:
                recs[-1]["skipped"] = "no active devices"

            # energy drain of the executed round
            if track and clusters:
                cv = prof.at(v_t) if proposed else c
                j = np.zeros(N)
                for cl, x in zip(clusters, xs):
                    for i, kx in zip(cl, np.asarray(x, np.float64)):
                        fi = f[i] * ncfg.kappa
                        t_comp = L * B * (cv["gamma_dF"]
                                          + cv["gamma_dB"]) / fi
                        t_tx = (L * B * cv["xi_s"] + cv["xi_d"]) \
                            / (kx * rate[i])
                        j[i] = (dcfg.p_compute_w * t_comp
                                + dcfg.p_tx_w * t_tx)
                executed = sorted(d for cl in clusters for d in cl)
                if floor:
                    n_act2 = int((arrived & ~departed).sum())
                    for gid in executed:
                        if depleted[gid]:        # floor-pinned earlier
                            if n_act2 > floor:
                                departed[gid] = True
                                n_act2 -= 1
                            continue
                        energy[gid] -= j[gid]
                        if energy[gid] <= 0:
                            energy[gid] = 0.0
                            depleted[gid] = True
                            if n_act2 > floor:
                                departed[gid] = True
                                n_act2 -= 1
                else:
                    exec_mask = np.zeros(N, dtype=bool)
                    exec_mask[executed] = True
                    e_un = energy - j
                    newly = exec_mask & (e_un <= 0.0)
                    depleted |= newly
                    departed |= newly
                    energy = np.maximum(e_un, 0.0)

            # stochastic arrival (at most one; effective next slot)
            if dcfg.p_arrive > 0:
                cand = np.flatnonzero(is_res & ~arrdyn & ~departed)
                if self._u_arr[t, e] < dcfg.p_arrive and len(cand):
                    arrdyn[cand[0]] = True

            snr = mu_snr + dcfg.rho_snr * (snr - mu_snr) \
                + coef_s * self._eps_s[t, e]
            f = np.maximum(mu_f + dcfg.rho_f * (f - mu_f)
                           + coef_f * self._eps_f[t, e], _F_FLOOR)
        return recs

    def run_looped(self) -> dict:
        """All episodes through ``run_reference`` — the host baseline the
        bench compares against. Returns ``{"latency": (E, T), "records",
        "wall_s"}``."""
        t0 = time.monotonic()
        records = [self.run_reference(e) for e in range(self.E)]
        wall = time.monotonic() - t0
        lat = np.array([[r["latency_s"] for r in recs] for recs in records])
        return {"latency": lat, "records": records, "wall_s": wall}

    # -- CPSL coupling --------------------------------------------------------

    def train_curves(self, result: dict, xtr, ytr, ccfg, *, xte=None,
                     yte=None, model: str = "lenet",
                     samples_per_device: int = 180,
                     eval_every: int = 0) -> List[dict]:
        """Joint latency x accuracy: run ``CPSL.run_fleet`` on the
        episodes' slot-0 cluster layouts and merge the loss/acc curves
        with the priced ``sim_time_s``. Requires a static scenario (no
        churn, no energy depletion — layouts must not change across
        rounds) and a single cut layer across the grid; clusters are
        wrap-padded to rectangular layouts exactly like
        ``SimEngine._padded_clusters``."""
        from repro.core.cpsl import CPSL
        from repro.core.splitting import make_split_model
        from repro.data.pipeline import DeviceResidentDataset, fleet_plan
        from repro.data.synthetic import non_iid_split

        assert (self._depart >= self.T).all() and \
            (self._arrive <= 0).all() and self.dcfg.energy_budget_j == 0, \
            "train_curves needs a static scenario (layouts fixed per round)"
        assert self.dcfg.p_depart == 0 and self.dcfg.p_arrive == 0 and \
            not self._prows, \
            "train_curves needs a static scenario (no churn, no Gibbs)"
        cuts = {sp["cut"] for sp in self.specs}
        assert len(cuts) == 1, "one cut layer per coupled fleet"
        v = cuts.pop()
        assert ccfg.batch_per_device == self.fcfg.batch_per_device \
            and ccfg.local_epochs == self.fcfg.local_epochs, \
            "training and pricing must agree on (B, L)"

        trace = result["trace"]
        layouts = []
        for e in range(self.E):
            mask0, dev0 = trace["mask"][e, 0], trace["dev"][e, 0]
            lay = [[int(d) for d, mk in zip(dr, mr) if mk]
                   for dr, mr in zip(dev0, mask0) if mr.any()]
            Kp = max(len(cl) for cl in lay)
            layouts.append([[cl[i % len(cl)] for i in range(Kp)]
                            for cl in lay])
        seeds = [sp["seed"] for sp in self.specs]
        shards = {s: non_iid_split(ytr, n_devices=self.N,
                                   samples_per_device=samples_per_device,
                                   seed=s) for s in set(seeds)}
        plan = fleet_plan([shards[s] for s in seeds],
                          ccfg.batch_per_device, layouts, seeds, self.T,
                          ccfg.local_epochs)
        M_pad, K_pad = plan.idx.shape[2], plan.idx.shape[4]
        ccfg2 = dataclasses.replace(ccfg, cut_layer=v, n_clusters=M_pad,
                                    cluster_size=K_pad)
        cpsl = CPSL(make_split_model(model, v, conv_impl=ccfg2.conv_impl),
                    ccfg2)
        dsd = DeviceResidentDataset(xtr, ytr, shards[seeds[0]],
                                    ccfg.batch_per_device,
                                    eval_images=xte, eval_labels=yte)
        states = cpsl.init_fleet_state(plan.seeds)
        states, metrics = cpsl.run_fleet(
            states, dsd.data, plan.idx, plan.weights,
            eval_data=dsd.eval_data if eval_every else None,
            eval_every=eval_every, cluster_mask=plan.cluster_mask,
            client_mask=plan.client_mask)
        jax.block_until_ready(metrics["loss"])
        loss = np.asarray(metrics["loss"])
        evals = metrics.get("eval")
        out = []
        for e, ep in enumerate(result["episodes"]):
            rep = dict(ep, loss=[float(x) for x in loss[e]])
            if evals is not None:
                rep["acc"] = [float(x) for x in np.asarray(evals["acc"][e])]
                rep["eval_rounds"] = metrics["eval_rounds"]
            out.append(rep)
        return out


# --------------------------------------------------------------------------
# trace adapters (the NumPy oracle hooks)
# --------------------------------------------------------------------------

def fleet_trace_records(result: dict, e: int) -> List[dict]:
    """Episode ``e`` of a ``SimFleetRunner.run`` result as SimEngine-style
    per-round records — the format ``recompute_trace_latencies`` (and any
    JSONL trace consumer) already understands. Cluster entries are global
    device ids indexing the full-population ``f``/``rate`` rows; ``v``
    is the per-round traced cut (the proposed arm's SAA re-selects it
    at epoch boundaries)."""
    trace = result["trace"]
    v_tr = trace.get("v")
    v_fix = result["episodes"][e]["cut"]
    T = trace["latency"].shape[1]
    recs = []
    for t in range(T):
        mask, dev = trace["mask"][e, t], trace["dev"][e, t]
        clusters = [[int(d) for d, mk in zip(dr, mr) if mk]
                    for dr, mr in zip(dev, mask) if mr.any()]
        xs = [np.asarray([int(x) for x, mk in zip(xr, mr) if mk])
              for xr, mr in zip(trace["xs"][e, t], mask) if mr.any()]
        rec = {"round": t,
               "v": int(v_tr[e, t]) if v_tr is not None else int(v_fix),
               "clusters": clusters, "xs": xs,
               "f": trace["f"][e, t], "rate": trace["rate"][e, t],
               "latency_s": float(trace["latency"][e, t]),
               "n_active": int(trace["n_active"][e, t])}
        if not clusters:
            rec["skipped"] = "no active devices"
        recs.append(rec)
    return recs


def recompute_fleet_latencies(result: dict, prof: CutProfile,
                              ncfg: NetworkCfg, B: int, L: int
                              ) -> np.ndarray:
    """Re-derive every episode/round latency of a fleet result from its
    traced (f, rate, clusters, xs, v) with the NumPy
    ``core.latency.round_latency`` — the reference-oracle acceptance
    check for the jnp cost engine. Returns (E, T); rounds with no active
    devices recompute to 0."""
    E = result["trace"]["latency"].shape[0]
    out = []
    for e in range(E):
        row = []
        for rec in fleet_trace_records(result, e):
            if rec.get("skipped"):
                row.append(0.0)
                continue
            net = NetworkState(f=np.asarray(rec["f"], np.float64),
                               rate=np.asarray(rec["rate"], np.float64))
            row.append(lt.round_latency(rec["v"], rec["clusters"],
                                        rec["xs"], net, ncfg, prof, B, L))
        out.append(row)
    return np.asarray(out)
