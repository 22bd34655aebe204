"""CPSL training driver.

End-to-end: synthetic non-IID data -> resource-managed CPSL rounds with
checkpoints and the wireless-latency simulator.

    PYTHONPATH=src python -m repro.launch.train --model lenet --rounds 20 \
        --ckpt-dir experiments/lenet/ckpt
    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
        --reduced --rounds 3 --clusters 2 --cluster-size 2 \
        --ckpt-dir experiments/qwen2/ckpt

``--ckpt-dir`` is required: the trainer resumes from whatever checkpoint
that directory holds, so no two runs share one by default.
"""
from __future__ import annotations

import argparse
import json

import numpy as np

from repro import compile_cache, streams
from repro.configs import registry
from repro.configs.base import CPSLConfig
from repro.core.channel import NetworkCfg
from repro.core.cpsl import CPSL
from repro.core.profile import lenet_profile, lm_profile
from repro.core.resource import saa_cut_selection
from repro.core.splitting import make_split_model
from repro.data.pipeline import CPSLDataset, LMClusterData
from repro.data.synthetic import MarkovLM, non_iid_split, synthetic_mnist
from repro.models import lenet
from repro.train.trainer import CPSLTrainer, TrainerCfg


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="lenet")
    ap.add_argument("--arch", default=None, help="LM arch id (see registry)")
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale LM config (CPU-friendly)")
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--clusters", type=int, default=6)
    ap.add_argument("--cluster-size", type=int, default=5)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--local-epochs", type=int, default=1)
    ap.add_argument("--cut", type=int, default=None)
    ap.add_argument("--saa", action="store_true",
                    help="select the cut layer with Alg. 2 (SAA)")
    ap.add_argument("--resource", default="gibbs",
                    choices=["gibbs", "random", "heuristic", "fixed"])
    ap.add_argument("--ckpt-dir", required=True,
                    help="checkpoint directory; a run resumes from it")
    ap.add_argument("--log", default=None)
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def build(args):
    """The trainer ``args`` describe, and its cut layer."""
    n_devices = args.clusters * args.cluster_size
    ncfg = NetworkCfg(n_devices=n_devices)

    if args.arch:
        cfg = registry.get(args.arch)
        if args.reduced:
            cfg = registry.reduce_for_smoke(cfg)
        prof = lm_profile(cfg, seq=args.seq)
        lm = MarkovLM(cfg.vocab_size, seed=args.seed)
        ds = LMClusterData(lm, n_devices, args.batch, args.seq,
                           seed=args.seed)
        model_id = cfg
    else:
        _ = lenet  # paper model
        xtr, ytr, xte, yte = synthetic_mnist(seed=args.seed)
        idx = non_iid_split(ytr, n_devices=n_devices, seed=args.seed)
        ds = CPSLDataset(xtr, ytr, idx, batch=args.batch)
        prof = lenet_profile()
        model_id = "lenet"

    cut = args.cut
    if args.saa or cut is None:
        cut, means = saa_cut_selection(
            prof, ncfg, B=args.batch, L=args.local_epochs,
            n_clusters=args.clusters, cluster_size=args.cluster_size,
            n_samples=4, gibbs_iters=100, seed=args.seed)
        print(f"[SAA] optimal cut layer v* = {cut} "
              f"(per-cut mean latency: {np.round(means, 2).tolist()})")

    ccfg = CPSLConfig(cut_layer=cut, n_clusters=args.clusters,
                      cluster_size=args.cluster_size,
                      local_epochs=args.local_epochs,
                      batch_per_device=args.batch)
    split = make_split_model(model_id, cut)
    tcfg = TrainerCfg(rounds=args.rounds, ckpt_dir=args.ckpt_dir,
                      resource_mgmt=args.resource, log_path=args.log,
                      seed=args.seed)
    return CPSLTrainer(CPSL(split, ccfg), ds, prof, ncfg, tcfg), cut


def main(argv=None):
    args = parse_args(argv)
    compile_cache.enable()
    trainer, cut = build(args)
    trainer.run(streams.model_key(args.seed), v=cut)
    for h in trainer.history:
        print(json.dumps(h))


if __name__ == "__main__":
    main()
