"""Fault-tolerant CPSL training loop.

Each round (paper Alg. 1):
  1. draw the network state (device compute + channels),
  2. small-timescale resource management: Gibbs clustering + greedy
     spectrum (Alg. 3/4), multi-chain best-of-R Gibbs ("gibbs-mc", via
     the replicated planner in ``repro.sim.batched``) — or fixed/random
     clustering,
  3. run intra-cluster epochs + FedAvg per cluster, sequentially —
     either the looped reference path (one jitted step per epoch, host
     batch gather, eq.-8 weights from the dataset's shard sizes) or,
     with ``CPSLConfig.fused_round``, the whole round as ONE donated jit
     over a device-resident dataset (``CPSL.run_round_fused``; metrics
     sync every ``log_every`` rounds),
  4. accumulate the *simulated wireless latency* of the round (eqs. 15-25)
     next to the measured wall-clock,
  5. checkpoint every ``ckpt_every`` rounds (async, atomic, keep-k);
     auto-resume picks up the latest checkpoint including RNG/rounds.

Failure handling: ``fail_at_round`` injects a crash (tests restart the
trainer and verify bit-exact continuation); SIGTERM triggers a final
checkpoint before exit (preemption-safe).
"""
from __future__ import annotations

import copy
import dataclasses
import json
import time
from dataclasses import dataclass
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import streams, telemetry
from repro.configs.base import CPSLConfig, FleetConfig
from repro.core import latency as lt
from repro.core import resource as rs
from repro.core.channel import NetworkCfg, device_means, sample_network
from repro.core.compression import compression_ratio
from repro.core.cpsl import CPSL
from repro.core.latency import CutProfile
from repro.core.splitting import make_split_model
from repro.checkpoint.checkpointer import Checkpointer
from repro.data.pipeline import (DeviceResidentDataset, batch_seed,
                                 fleet_plan)
from repro.data.synthetic import non_iid_split
from repro.lifecycle import GracefulStop
from repro.sim.batched import gibbs_clustering_multichain


class SimulatedFailure(RuntimeError):
    pass


@dataclass
class TrainerCfg:
    ckpt_dir: str                     # required: ``run`` resumes from the
                                      # latest checkpoint found here, so
                                      # each run names its own directory
    rounds: int = 10
    ckpt_every: int = 5
    keep: int = 3
    async_ckpt: bool = True
    resource_mgmt: str = "gibbs"      # gibbs | gibbs-mc | random | heuristic | fixed
    gibbs_iters: int = 200
    gibbs_chains: int = 4             # lockstep replicas for "gibbs-mc"
                                      # (best-of-R; chain 0 == "gibbs")
    fail_at_round: Optional[int] = None
    log_path: Optional[str] = None
    log_every: int = 1                # fused rounds keep metrics on device;
                                      # host-sync + JSONL flush every this
                                      # many rounds (1 == every round)
    seed: int = 0


class CPSLTrainer:
    def __init__(self, cpsl: CPSL, dataset, prof: CutProfile,
                 ncfg: NetworkCfg, tcfg: TrainerCfg,
                 eval_fn: Optional[Callable] = None):
        self.cpsl, self.ds, self.prof = cpsl, dataset, prof
        self.ncfg, self.tcfg = ncfg, tcfg
        self.eval_fn = eval_fn
        self.ckpt = Checkpointer(tcfg.ckpt_dir, keep=tcfg.keep,
                                 async_save=tcfg.async_ckpt)
        self.mu_f, self.mu_snr = device_means(ncfg, tcfg.seed)
        # upload compression shrinks xi_d on the DMT uplink; the shrunk
        # profile is cut-independent, so build it once instead of per round
        cr = compression_ratio(cpsl.ccfg.compress_uploads,
                               cpsl.ccfg.compress_topk)
        if cr < 1.0:
            prof2 = copy.copy(prof)
            prof2.xi_d = prof.xi_d * cr
            self._prof_compressed: Optional[CutProfile] = prof2
        else:
            self._prof_compressed = None
        # fused-round path: mirror the dataset onto the device once; each
        # round then ships only an (M, L, K, B) index table into the jit
        self._ds_dev: Optional[DeviceResidentDataset] = (
            DeviceResidentDataset.coerce(dataset)
            if cpsl.ccfg.fused_round else None)
        self.history: List[dict] = []
        self._pending: List[dict] = []
        # SIGTERM => finish the round, checkpoint (blocking), exit clean
        # (preemption-safe; shared with the rt device workers)
        self.stop = GracefulStop().install()

    @property
    def _stop(self) -> bool:
        return self.stop.triggered

    # -- round-level resource management (paper small timescale) -------------

    def _plan_round(self, v: int, rnd: int):
        with telemetry.span("plan"):
            rng = streams.trainer_round_rng(self.tcfg.seed, rnd)
            with telemetry.span("network"):
                net = sample_network(self.ncfg, self.mu_f, self.mu_snr, rng)
            with telemetry.span("cluster"):
                clusters, xs, lat = self._cluster(v, rnd, net)
            if self._prof_compressed is not None:
                lat = lt.round_latency(v, clusters, xs, net, self.ncfg,
                                       self._prof_compressed,
                                       self.cpsl.ccfg.batch_per_device,
                                       self.cpsl.ccfg.local_epochs)
        return clusters, xs, lat

    def _cluster(self, v: int, rnd: int, net):
        """Clusters and spectrum by ``resource_mgmt``: (clusters, xs,
        priced latency)."""
        M, K = self.cpsl.ccfg.n_clusters, self.cpsl.ccfg.cluster_size
        kind = self.tcfg.resource_mgmt
        if kind == "gibbs":
            clusters, xs, lat = rs.gibbs_clustering(
                v, net, self.ncfg, self.prof, self.cpsl.ccfg.batch_per_device,
                self.cpsl.ccfg.local_epochs, M, K,
                iters=self.tcfg.gibbs_iters, seed=self.tcfg.seed + rnd)
        elif kind == "gibbs-mc":
            # best-of-R lockstep chains (chain 0 == the "gibbs" stream, so
            # this never plans worse than "gibbs" at the same seed)
            clusters, xs, lat = gibbs_clustering_multichain(
                v, net, self.ncfg, self.prof, self.cpsl.ccfg.batch_per_device,
                self.cpsl.ccfg.local_epochs, M, K,
                iters=self.tcfg.gibbs_iters, seed=self.tcfg.seed + rnd,
                chains=max(1, self.tcfg.gibbs_chains))
        elif kind == "heuristic":
            clusters, xs, lat = rs.heuristic_clustering(
                v, net, self.ncfg, self.prof,
                self.cpsl.ccfg.batch_per_device,
                self.cpsl.ccfg.local_epochs, M, K)
        else:   # random / fixed
            clusters, xs, lat = rs.random_clustering(
                v, net, self.ncfg, self.prof,
                self.cpsl.ccfg.batch_per_device,
                self.cpsl.ccfg.local_epochs, M, K,
                seed=(0 if kind == "fixed" else self.tcfg.seed + rnd))
        return clusters, xs, lat

    # -- main loop ------------------------------------------------------------

    def _restore(self, state):
        """(state, start round, sim time) from the latest checkpoint, or
        ``state`` at round 0. A helper so that no reference to the initial
        state outlives it: held for the whole run, it cost one more model
        copy in device memory (at qwen2-0.5b width, 3.2 GB of a v5e's 16)."""
        restored = self.ckpt.restore({"round": jnp.zeros((), jnp.int32),
                                      "sim_time": jnp.zeros(()),
                                      "state": state})
        if restored is None:
            return state, 0, 0.0
        return (restored["state"], int(restored["round"]),
                float(restored["sim_time"]))

    def run(self, key, v: Optional[int] = None):
        v = v if v is not None else self.cpsl.ccfg.cut_layer
        state, start_round, sim_time = self._restore(
            self.cpsl.init_state(key))

        try:
            for rnd in range(start_round, self.tcfg.rounds):
                if self.tcfg.fail_at_round is not None \
                        and rnd == self.tcfg.fail_at_round:
                    raise SimulatedFailure(f"injected failure at round {rnd}")
                telemetry.begin_round(rnd)
                t0 = time.monotonic()
                with telemetry.span("round"):
                    clusters, xs, lat = self._plan_round(v, rnd)
                    if self._ds_dev is not None:
                        # fused round: one donated jit, batches gathered
                        # on device from the precomputed index table; the
                        # loss stays a device scalar until the next log
                        # flush
                        idx = self._ds_dev.round_index_table(
                            clusters, self.tcfg.seed, rnd,
                            self.cpsl.ccfg.local_epochs)
                        state, metrics = self.cpsl.run_round_fused(
                            state, self._ds_dev.data, idx,
                            self._ds_dev.cluster_weights(clusters))
                        # dispatch is async — wait for the device compute
                        # so wall_s stays a real measurement (no host
                        # transfer; the metric sync still batches per
                        # log_every)
                        with telemetry.span("sync"):
                            jax.block_until_ready(state)
                        telemetry.count("syncs")
                    else:
                        def batch_fn(m, l, _clusters=clusters, _rnd=rnd):
                            with telemetry.span("gather"):
                                b = self.ds.cluster_batch(
                                    _clusters[m],
                                    seed=batch_seed(self.tcfg.seed, _rnd,
                                                    m, l))
                                telemetry.count("h2d_bytes", sum(
                                    t.nbytes for t in jax.tree.leaves(b)))
                                return jax.tree.map(jnp.asarray, b)

                        sizes = (np.stack([self.ds.data_sizes(c)
                                           for c in clusters])
                                 if hasattr(self.ds, "data_sizes") else None)
                        state, metrics = self.cpsl.run_round(
                            state, batch_fn, n_clusters=len(clusters),
                            data_sizes=sizes)
                sim_time += lat
                wall = time.monotonic() - t0
                rec = {"round": rnd, "loss": metrics["loss"],
                       "sim_latency_s": lat, "sim_time_s": sim_time,
                       "wall_s": wall}
                if self.eval_fn is not None:
                    rec["eval"] = self.eval_fn(self.cpsl, state)

                last = rnd == self.tcfg.rounds - 1
                if (rnd + 1) % self.tcfg.ckpt_every == 0 or last \
                        or self._stop:
                    with telemetry.span("save"):
                        self.ckpt.save(
                            {"round": jnp.asarray(rnd + 1, jnp.int32),
                             "sim_time": jnp.asarray(sim_time),
                             "state": state},
                            step=rnd + 1, block=last or self._stop)
                rec["phase_s"], rec["counts"] = telemetry.fold()
                self.history.append(rec)
                self._pending.append(rec)
                if (rnd + 1) % self.tcfg.log_every == 0 or last \
                        or self._stop:
                    self._flush_logs()
                if self._stop:
                    break
        finally:
            self._flush_logs()
        self.ckpt.wait()
        return state

    def _flush_logs(self):
        """Host-sync pending round metrics and append them to the JSONL
        log — the fused path's single sync point (every ``log_every``
        rounds)."""
        pending, self._pending = self._pending, []
        for rec in pending:
            rec["loss"] = float(rec["loss"])
            if self.tcfg.log_path:
                with open(self.tcfg.log_path, "a") as f:
                    f.write(json.dumps(rec) + "\n")


# --------------------------------------------------------------------------
# Experiment fleets: the sweep grid as one batched program
# --------------------------------------------------------------------------

class FleetRunner:
    """Multi-seed / multi-config CPSL experiment fleet: the full
    ``FleetConfig`` grid (seeds x cluster sizes x lr scales) runs as ONE
    batched XLA program (``CPSL.run_fleet``) over a shared
    device-resident dataset, with per-replica non-IID shard tables,
    padded cluster layouts, and in-jit test-set evaluation.

    Fixed round-robin clustering (the fig. 5/6 setting) — per-round
    Gibbs planning is host-interactive and stays on ``CPSLTrainer``.
    Wireless latency is priced per replica host-side from the same
    equal-spectrum model the fig benchmarks use, so extracted curves
    carry (round, loss, acc, sim time) like the sequential path's.

    Replica r reproduces the solo ``CPSL.run_training_fused`` run with
    its (seed, layout, lr) bit-exactly on int/rng leaves and ULP-equal
    on floats when the grid is homogeneous (tests/test_fleet.py)."""

    def __init__(self, xtr, ytr, fcfg: FleetConfig, ccfg: CPSLConfig,
                 xte=None, yte=None, model: str = "lenet",
                 prof: Optional[CutProfile] = None,
                 ncfg: Optional[NetworkCfg] = None, batch=None):
        self.fcfg, self.base_ccfg = fcfg, ccfg
        self.prof, self.ncfg = prof, ncfg
        B = batch or ccfg.batch_per_device
        lr_scales = fcfg.lr_scales or (1.0,)

        # the replica grid, row-major: cluster_size x lr_scale x seed
        self.specs: List[dict] = []
        for nm in fcfg.cluster_sizes:
            assert fcfg.n_devices % nm == 0, (fcfg.n_devices, nm)
            M = fcfg.n_devices // nm
            layout = [list(range(m * nm, (m + 1) * nm)) for m in range(M)]
            for ls in lr_scales:
                for seed in fcfg.seeds:
                    self.specs.append({"seed": int(seed),
                                       "cluster_size": int(nm),
                                       "n_clusters": M,
                                       "lr_scale": float(ls),
                                       "layout": layout})

        shards = {s: non_iid_split(
            ytr, n_devices=fcfg.n_devices,
            samples_per_device=fcfg.samples_per_device, seed=s)
            for s in {sp["seed"] for sp in self.specs}}
        self.plan = fleet_plan(
            [shards[sp["seed"]] for sp in self.specs], B,
            [sp["layout"] for sp in self.specs],
            [sp["seed"] for sp in self.specs],
            fcfg.rounds, ccfg.local_epochs)

        # the fleet CPSL is built at the PADDED shape: every grid variant
        # differs only in data (tables/masks/weights/lr), so one instance
        # — and therefore one compiled executable — serves the whole grid
        M_pad, K_pad = self.plan.idx.shape[2], self.plan.idx.shape[4]
        self.ccfg = dataclasses.replace(ccfg, n_clusters=M_pad,
                                        cluster_size=K_pad)
        self.cpsl = CPSL(make_split_model(model, self.ccfg.cut_layer,
                                          conv_impl=self.ccfg.conv_impl),
                         self.ccfg)
        self.dsd = DeviceResidentDataset(
            xtr, ytr, shards[self.specs[0]["seed"]], B,
            eval_images=xte, eval_labels=yte)
        self.lr_scale = (np.array([sp["lr_scale"] for sp in self.specs],
                                  np.float32)
                         if fcfg.lr_scales else None)

    def _price_latency(self, spec) -> List[float]:
        """Cumulative per-round wireless latency for one replica — the
        shared equal-spectrum loop (``core.latency.equal_split_curve``),
        priced at the replica's actual cut layer (the fig benchmarks
        keep their legacy v=1 convention on the same loop)."""
        if self.prof is None or self.ncfg is None:
            return []
        return lt.equal_split_curve(
            self.base_ccfg.cut_layer, spec["layout"], self.ncfg,
            self.prof, self.base_ccfg.batch_per_device,
            self.base_ccfg.local_epochs, self.fcfg.rounds, spec["seed"])

    def run(self) -> dict:
        """Dispatch the fleet (one batched program) and extract
        per-replica curves. Returns ``{"replicas": [...], "wall_s",
        "n_replicas", "eval_rounds"}``; each replica dict carries its
        grid coordinates plus ``loss`` (R,), ``acc``/``eval_loss`` at
        the eval rounds, and cumulative ``sim_time_s``."""
        fcfg = self.fcfg
        t0 = time.monotonic()
        states = self.cpsl.init_fleet_state(self.plan.seeds)
        eval_data = self.dsd.eval_data if fcfg.eval_every else None
        states, metrics = self.cpsl.run_fleet(
            states, self.dsd.data, self.plan.idx, self.plan.weights,
            lr_scale=self.lr_scale, eval_data=eval_data,
            eval_every=fcfg.eval_every,
            cluster_mask=self.plan.cluster_mask,
            client_mask=self.plan.client_mask)
        jax.block_until_ready(metrics["loss"])
        wall = time.monotonic() - t0

        loss = np.asarray(metrics["loss"])
        evals = metrics.get("eval")
        replicas = []
        for e, spec in enumerate(self.specs):
            rep = {k: spec[k] for k in ("seed", "cluster_size",
                                        "n_clusters", "lr_scale")}
            rep["loss"] = [float(x) for x in loss[e]]
            if evals is not None:
                rep["acc"] = [float(x) for x in np.asarray(evals["acc"][e])]
                rep["eval_loss"] = [float(x)
                                    for x in np.asarray(evals["loss"][e])]
            lat = self._price_latency(spec)
            if lat:
                rep["sim_time_s"] = lat
            replicas.append(rep)
        out = {"replicas": replicas, "wall_s": wall,
               "n_replicas": len(replicas)}
        if evals is not None:
            out["eval_rounds"] = metrics["eval_rounds"]
        self.states = states
        return out
