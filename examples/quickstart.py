"""Quickstart: the paper end-to-end in ~2 minutes on CPU.

Trains the paper's LeNet with CPSL on synthetic non-IID MNIST for a few
rounds, with the full control plane active: SAA cut-layer selection
(Alg. 2), Gibbs clustering + greedy spectrum (Algs. 3/4), the wireless
latency simulator, checkpointing, and FedAvg aggregation — then re-runs
the training as an experiment FLEET: a seed x cluster-size grid of
whole training curves compiled once and executed as one batched program
(``CPSL.run_fleet`` via ``FleetRunner``), with in-jit test-set eval.

    PYTHONPATH=src python examples/quickstart.py
"""
import shutil

import jax
import numpy as np

from repro import compile_cache
from repro.configs.base import CPSLConfig, FleetConfig
from repro.core.channel import NetworkCfg
from repro.core.cpsl import CPSL
from repro.core.profile import lenet_profile
from repro.core.resource import saa_cut_selection
from repro.core.splitting import make_split_model
from repro.data.pipeline import CPSLDataset
from repro.data.synthetic import non_iid_split, synthetic_mnist
from repro.models import lenet
from repro.train.trainer import CPSLTrainer, FleetRunner, TrainerCfg


def main():
    # 30 simulated wireless devices, 3 classes each (paper §VIII-A)
    xtr, ytr, xte, yte = synthetic_mnist(8000, 1500, seed=0)
    device_idx = non_iid_split(ytr, n_devices=30, samples_per_device=180)
    ds = CPSLDataset(xtr, ytr, device_idx, batch=16)
    ncfg = NetworkCfg(n_devices=30)
    prof = lenet_profile()

    # large timescale: SAA cut-layer selection (Alg. 2)
    v_star, means = saa_cut_selection(prof, ncfg, B=16, L=1, n_clusters=6,
                                      cluster_size=5, n_samples=3,
                                      gibbs_iters=60)
    print(f"SAA cut layer: v*={v_star} ({lenet.LAYERS[v_star-1]}); "
          f"mean per-round latency per cut: {np.round(means, 2)}")

    ccfg = CPSLConfig(cut_layer=v_star, n_clusters=6, cluster_size=5,
                      local_epochs=1)
    cpsl = CPSL(make_split_model("lenet", v_star), ccfg)
    ckpt_dir = "experiments/quickstart/ckpt"
    shutil.rmtree(ckpt_dir, ignore_errors=True)   # a fresh run, not a resume
    tcfg = TrainerCfg(rounds=8, ckpt_every=4, ckpt_dir=ckpt_dir,
                      resource_mgmt="gibbs", gibbs_iters=80)

    def eval_fn(cp, state):
        params, _ = cp.export_params(state)
        return lenet.accuracy(params, jax.numpy.asarray(xte),
                              jax.numpy.asarray(yte))

    trainer = CPSLTrainer(cpsl, ds, prof, ncfg, tcfg, eval_fn=eval_fn)
    trainer.run(jax.random.PRNGKey(0), v=v_star)
    for h in trainer.history:
        print(f"round {h['round']:2d}  loss {h['loss']:.3f}  "
              f"acc {h['eval']:.3f}  wireless latency {h['sim_latency_s']:.2f}s "
              f"(cum {h['sim_time_s']:.1f}s)")

    # -- experiment fleet: the sweep grid as ONE batched program ----------
    # 2 seeds x 2 cluster sizes = 4 whole training curves, padded to a
    # shared layout shape, compiled once, dispatched once; eval runs
    # in-jit every 4 rounds on the device-resident test split
    fleet_ccfg = CPSLConfig(cut_layer=v_star, conv_impl="im2col",
                            scan_rounds=True, fused_round_unroll=1)
    fcfg = FleetConfig(rounds=8, seeds=(0, 1), cluster_sizes=(5, 10),
                       n_devices=30, eval_every=4)
    fleet = FleetRunner(xtr, ytr, fcfg, fleet_ccfg, xte=xte, yte=yte,
                        prof=prof, ncfg=ncfg)
    result = fleet.run()
    print(f"\nfleet: {result['n_replicas']} replicas (seed x N_m grid) "
          f"in {result['wall_s']:.1f}s wall (one compile, one dispatch)")
    for rep in result["replicas"]:
        print(f"  N_m={rep['cluster_size']:2d} seed={rep['seed']}  "
              f"final loss {rep['loss'][-1]:.3f}  "
              f"acc {rep['acc'][-1]:.3f}  "
              f"sim time {rep['sim_time_s'][-1]:.1f}s")


if __name__ == "__main__":
    compile_cache.enable()
    main()
