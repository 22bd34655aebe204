#!/usr/bin/env python3
"""On-chip smoke check of the CPSL main path, on one TPU chip.

    python3 chip_smoke.py

Runs in one process, which holds the chip (rt workers are pinned to the
CPU). Phases, in order, each printing one JSON line with its name, wall
and compile seconds, persistent-cache hits, the checks it made and the
device's ``peak_bytes_in_use`` so far:

  paper_round    LeNet at the paper's size (N=30, M=6, K=5, B=16, L=1),
                 SAA cut (Alg. 2) + Gibbs/greedy plans (Algs. 3/4),
                 built by ``repro.launch.train``: looped rounds, fused
                 rounds, one round against the CPU backend, and the
                 quickstart's experiment fleet (``FleetRunner``);
  lm_split       qwen2-0.5b at its published widths through the same
                 launcher (fixed cut v=2, M=2, K=2, B=4, seq 512), plus
                 the compiled flash-attention kernel at its shapes;
  episode_fleet  ``SimFleetRunner._sim`` under float64 at a smoke size
                 (2 seeds x 2 policies), decisions against
                 ``run_reference``;
  rt_loopback    the ``examples/rt_loopback.py`` deployment with the
                 server on the chip and 4 CPU workers.

The TPU's default f32 matmul precision is not XLA:CPU's, so the CPU
bit-exactness contracts do not carry over: every comparison here states
its tolerance. The last line is ``{"ok": true, "device": {...}}``. Any
failure raises; with no TPU the script names the platform it found and
exits non-zero without a result. Outputs (checkpoints, the rt trace) go
to ``chip_smoke_out/`` next to this file, emptied at start.
"""
from __future__ import annotations

import dataclasses
import json
import math
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "chip_smoke_out"
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

SEED = 0

# Tolerances, each on ``rel_l2`` (below), set from a TPU v5e run. The
# LeNet round is chaotic: a ReLU whose input is a near-zero bias flips
# for every background pixel at once, so on XLA:CPU itself a 1e-7
# relative nudge of the start state moves the parameters by 7e-5 after
# one round and by 3.5e-3 after three. One step from the initial state
# (biases exactly 0) has no such flips. Measured on the chip: one step
# 1.0e-5 at "highest" (XLA:CPU f32 vs f64: 7.5e-9) and 2.9e-4 at the
# default precision; one round 1.1e-3 and 2.3e-3.
TOL_STEP_HIGHEST = 1e-4   # one step, chip at "highest" vs the CPU
TOL_STEP_DEFAULT = 5e-3   # one step, chip at default precision vs the CPU
TOL_ROUND_HIGHEST = 1e-2  # one round, chip at "highest" vs the CPU
TOL_ROUND_DEFAULT = 3e-2  # one round, chip at default precision vs the CPU
TOL_FUSED = 3e-2         # looped vs fused, both on the chip, three rounds
TOL_FLASH = 2e-2         # flash kernel vs chunked attention ("highest")
TOL_RT = 5e-2            # rt deployment vs the CPU reference, three rounds
TOL_LM_FIRST_LOSS = 1.5  # |first round loss - ln(vocab)|


def run_phase(counters, name, fn, *args):
    """Run one phase and print its line; exceptions propagate."""
    import jax
    c0, h0 = counters.compile_s, counters.cache_hits
    t0 = time.monotonic()
    checks = fn(*args)
    stats = jax.devices()[0].memory_stats() or {}
    line = {"phase": name, "wall_s": time.monotonic() - t0,
            "compile_s": counters.compile_s - c0,
            "cache_hits": counters.cache_hits - h0, "checks": checks,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")}
    print(json.dumps(line), flush=True)


def rel_l2(a, b) -> float:
    """``||a - b|| / ||b||`` over all float leaves taken as one vector;
    integer leaves (rng keys, step counters) must match exactly."""
    import jax
    import numpy as np
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    _check(len(la) == len(lb), "different tree structures")
    num = den = 0.0
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        _check(x.shape == y.shape and x.dtype == y.dtype, "leaf shape/dtype")
        if not np.issubdtype(x.dtype, np.floating):
            _check(np.array_equal(x, y), "integer leaf differs")
            continue
        x, y = x.astype(np.float64), y.astype(np.float64)
        _check(np.isfinite(x).all() and np.isfinite(y).all(),
               "non-finite leaf")
        num += float(np.square(x - y).sum())
        den += float(np.square(y).sum())
    return math.sqrt(num / max(den, 1e-300))


def platforms(tree) -> set:
    import jax
    return {d.platform for x in jax.tree.leaves(tree) for d in x.devices()}


def _check(ok: bool, what: str):
    if not ok:
        raise AssertionError(what)


# -- phases ------------------------------------------------------------------

def paper_round(rounds=3, fleet_rounds=8, fleet_n_train=8000):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import streams
    from repro.configs.base import CPSLConfig, FleetConfig
    from repro.core.channel import NetworkCfg
    from repro.core.cpsl import CPSL
    from repro.core.profile import lenet_profile
    from repro.data.pipeline import batch_seed
    from repro.data.synthetic import synthetic_mnist
    from repro.launch import train
    from repro.train.trainer import CPSLTrainer, FleetRunner

    looped, v = train.build(train.parse_args(
        ["--model", "lenet", "--clusters", "6", "--cluster-size", "5",
         "--batch", "16", "--local-epochs", "1", "--rounds", str(rounds),
         "--saa", "--seed", str(SEED),
         "--ckpt-dir", str(OUT / "ckpt_lenet_looped")]))
    # the same trainer with each round as one donated jit
    fused = CPSLTrainer(
        CPSL(looped.cpsl.split,
             dataclasses.replace(looped.cpsl.ccfg, fused_round=True)),
        looped.ds, looped.prof, looped.ncfg,
        dataclasses.replace(looped.tcfg,
                            ckpt_dir=str(OUT / "ckpt_lenet_fused")))
    key = streams.model_key(SEED)
    st_looped = looped.run(key, v=v)
    st_fused = fused.run(key, v=v)
    losses = [h["loss"] for h in looped.history + fused.history]
    _check(len(losses) == 2 * rounds and all(map(math.isfinite, losses)),
           f"non-finite or missing round losses {losses}")
    fused_rel = rel_l2(st_fused, st_looped)
    _check(fused_rel <= TOL_FUSED, f"fused vs looped {fused_rel}")

    # one step and one round from the same start state and plan, on the
    # chip at both precisions and on the CPU
    cpsl = looped.cpsl
    clusters, _, _ = looped._plan_round(v, 0)
    sizes = np.stack([looped.ds.data_sizes(c) for c in clusters])

    def batch_fn(m, l):
        b = looped.ds.cluster_batch(clusters[m],
                                    seed=batch_seed(SEED, 0, m, l))
        return jax.tree.map(jnp.asarray, b)

    def step_and_round(device=None):
        st = jax.device_put(cpsl.init_state(key), device)
        return {"step": cpsl.cluster_step(st, batch_fn(0, 0))[0],
                "round": cpsl.run_round(st, batch_fn,
                                        n_clusters=len(clusters),
                                        data_sizes=sizes)[0]}

    chip = {"default": step_and_round()}
    with jax.default_matmul_precision("highest"):
        chip["highest"] = step_and_round()
    cpu_dev = jax.devices("cpu")[0]
    with jax.default_device(cpu_dev):
        cpu = step_and_round(cpu_dev)
    _check(platforms(cpu) == {"cpu"}
           and platforms(chip["default"]) == {jax.devices()[0].platform},
           "the rounds did not run where they were placed")
    tols = {"step_highest": TOL_STEP_HIGHEST,
            "step_default": TOL_STEP_DEFAULT,
            "round_highest": TOL_ROUND_HIGHEST,
            "round_default": TOL_ROUND_DEFAULT}
    vs_cpu = {}
    for name, tol in tols.items():
        what, precision = name.split("_")
        vs_cpu[name] = rel_l2(chip[precision][what], cpu[what])
        _check(vs_cpu[name] <= tol, f"chip vs cpu, {name}: {vs_cpu[name]}")

    # the quickstart's experiment fleet: seeds x cluster sizes, one program
    xtr, ytr, xte, yte = synthetic_mnist(fleet_n_train, 1500, seed=SEED)
    fleet = FleetRunner(
        xtr, ytr,
        FleetConfig(rounds=fleet_rounds, seeds=(0, 1), cluster_sizes=(5, 10),
                    n_devices=30, eval_every=4),
        CPSLConfig(cut_layer=v, conv_impl="im2col", scan_rounds=True,
                   fused_round_unroll=1),
        xte=xte, yte=yte, prof=lenet_profile(), ncfg=NetworkCfg(n_devices=30))
    res = fleet.run()
    fl = [x for rep in res["replicas"] for x in rep["loss"]]
    accs = [x for rep in res["replicas"] for x in rep["acc"]]
    _check(res["n_replicas"] == 4 and all(map(math.isfinite, fl)),
           "fleet losses")
    _check(all(0.0 <= a <= 1.0 for a in accs), f"fleet accuracies {accs}")
    return {"cut": v, "round_losses_looped": [h["loss"] for h in
                                              looped.history],
            "round_losses_fused": [h["loss"] for h in fused.history],
            "fused_vs_looped_rel": fused_rel, "tol_fused": TOL_FUSED,
            "chip_vs_cpu_rel": vs_cpu, "tol_chip_vs_cpu": tols,
            "fleet_replicas": res["n_replicas"],
            "fleet_final_losses": [rep["loss"][-1] for rep in res["replicas"]],
            "fleet_final_acc": [rep["acc"][-1] for rep in res["replicas"]],
            "note": "TPU default f32 matmul precision differs from XLA:CPU; "
                    "bit-exactness is a CPU-only contract"}


def flash_check(cfg, batch, seq):
    """Forward and backward of the compiled flash-attention kernel at the
    model's attention shapes against ``chunked_attention``."""
    import jax
    import jax.numpy as jnp

    from repro import streams
    from repro.kernels.flash_attention import ops as fa_ops
    from repro.models.common import chunked_attention

    G, D = cfg.n_kv_heads, cfg.resolved_head_dim
    R = cfg.n_heads // G
    kq, kk, kv, kg = jax.random.split(streams.model_key(SEED), 4)
    q = jax.random.normal(kq, (batch, seq, G, R, D), jnp.float32)
    k = jax.random.normal(kk, (batch, seq, G, D), jnp.float32)
    v = jax.random.normal(kv, (batch, seq, G, D), jnp.float32)
    w = jax.random.normal(kg, q.shape, jnp.float32)

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v) * w)

    def kernel(q, k, v):
        return fa_ops.flash_attention(q, k, v, True, 0, 0.0, 0)

    def ref(q, k, v):
        return chunked_attention(q, k, v, True, 0, 0.0, 0)

    out = jax.jit(kernel)(q, k, v)
    grads = jax.jit(jax.grad(loss(kernel), argnums=(0, 1, 2)))(q, k, v)
    with jax.default_matmul_precision("highest"):
        out_ref = jax.jit(ref)(q, k, v)
        grads_ref = jax.jit(jax.grad(loss(ref), argnums=(0, 1, 2)))(q, k, v)
    fwd = rel_l2(out, out_ref)
    bwd = rel_l2(grads, grads_ref)
    _check(fwd <= TOL_FLASH and bwd <= TOL_FLASH,
           f"flash attention fwd {fwd} bwd {bwd}")
    return {"flash_shape_q": list(q.shape), "flash_fwd_rel": fwd,
            "flash_bwd_rel": bwd, "tol_flash": TOL_FLASH}


def lm_split(arch="qwen2-0.5b", reduced=False, cut=2, clusters=2,
             cluster_size=2, batch=4, seq=512, rounds=2):
    from repro import streams
    from repro.launch import train

    argv = ["--arch", arch, "--cut", str(cut), "--clusters", str(clusters),
            "--cluster-size", str(cluster_size), "--batch", str(batch),
            "--seq", str(seq), "--rounds", str(rounds), "--seed", str(SEED),
            "--ckpt-dir", str(OUT / "ckpt_lm")] + (["--reduced"] if reduced
                                                   else [])
    trainer, v = train.build(train.parse_args(argv))
    cfg = trainer.cpsl.split.cfg
    trainer.run(streams.model_key(SEED), v=v)
    losses = [h["loss"] for h in trainer.history]
    _check(len(losses) == rounds and all(map(math.isfinite, losses)),
           f"lm losses {losses}")
    ln_vocab = math.log(cfg.vocab_size)
    _check(abs(losses[0] - ln_vocab) <= TOL_LM_FIRST_LOSS,
           f"first loss {losses[0]} vs ln(vocab) {ln_vocab}")
    checks = {"arch": cfg.name, "d_model": cfg.d_model,
              "n_layers": cfg.n_layers, "vocab": cfg.vocab_size,
              "cut": v, "clusters": clusters, "cluster_size": cluster_size,
              "batch": batch, "seq": seq, "rounds": rounds,
              "losses": losses, "ln_vocab": ln_vocab,
              "tol_first_loss": TOL_LM_FIRST_LOSS,
              "wall_s_per_round": [h["wall_s"] for h in trainer.history]}
    del trainer
    checks.update(flash_check(cfg, cluster_size * batch, seq))
    return checks


def episode_fleet(seeds=2, rounds=8):
    import numpy as np

    from repro.configs.base import SimFleetCfg
    from repro.core.channel import NetworkCfg
    from repro.core.profile import lenet_profile
    from repro.sim.dynamics import DynamicsCfg
    from repro.sim.fleet import SimFleetRunner, fleet_trace_records

    # the paper's N=C=30, K=5 at cut 3, Gauss-Markov fading with churn
    prof = lenet_profile()
    ncfg = NetworkCfg(n_devices=30, n_subcarriers=30)
    dcfg = DynamicsCfg(rho_snr=0.9, rho_f=0.95, seed=SEED,
                       forced_departures={5: (2,), 12: (7, 9)},
                       energy_budget_j=400.0)
    fcfg = SimFleetCfg(rounds=rounds, seeds=tuple(range(seeds)),
                       policies=("greedy", "equal"), cluster_sizes=(5,),
                       cuts=(3,), batch_per_device=16, local_epochs=1)
    runner = SimFleetRunner(prof, ncfg, dcfg, fcfg)
    res = runner.run()
    worst = 0.0
    for e in range(runner.E):
        ref = runner.run_reference(e)
        got = fleet_trace_records(res, e)
        for t in range(runner.T):
            _check(got[t]["clusters"] == ref[t]["clusters"],
                   f"episode {e} slot {t}: clusters differ")
            for a, b in zip(got[t]["xs"], ref[t]["xs"]):
                _check(np.array_equal(a, b), f"episode {e} slot {t}: xs")
            want = ref[t]["latency_s"]
            worst = max(worst, abs(got[t]["latency_s"] - want)
                        / max(abs(want), 1e-30))
    _check(worst <= 1e-9, f"latency vs reference {worst}")
    return {"episodes": runner.E, "slots": runner.T, "dtype": "float64",
            "decisions_identical": True, "latency_rel_vs_reference": worst,
            "tol_latency_rel": 1e-9}


def rt_loopback():
    import jax

    from examples.rt_loopback import demo_config
    from repro.rt.orchestrator import Orchestrator, loopback_reference

    trace = OUT / "rt" / "trace.jsonl"
    trace.parent.mkdir(parents=True)
    cfg = demo_config(str(trace), seed=SEED)
    orch = Orchestrator(cfg)          # run_loopback, keeping the server
    try:
        orch.start()
        state, records = orch.run()
        worker_platforms = sorted(set(orch.server.platforms.values()))
    finally:
        orch.stop()
    _check(worker_platforms == ["cpu"], f"workers ran on {worker_platforms}")
    rounds = [r for r in records if r.get("kind") != "qos"]
    _check([r["round"] for r in rounds] == list(range(cfg.rounds)),
           f"rounds {[r['round'] for r in rounds]}")
    _check(all(math.isfinite(r["loss"]) for r in rounds), "rt losses")
    _check([r["dropped"] for r in rounds] == [[], [3], []],
           f"dropped {[r['dropped'] for r in rounds]}")
    _check(trace.stat().st_size > 0, "trace not written")
    # device 3 = cluster 1, slot 1 loses its upload in round 1 only
    with jax.default_device(jax.devices("cpu")[0]):
        ref, _ = loopback_reference(cfg, zero_weight=(1, 1), zero_rounds=(1,))
    _check(platforms(ref) == {"cpu"}, "the reference did not run on the CPU")
    keys = ("dev", "srv", "dev_opt", "srv_opt", "step")
    rel = rel_l2({k: state[k] for k in keys},
                       {k: ref[k] for k in keys})
    _check(rel <= TOL_RT, f"rt vs reference {rel}")
    return {"rounds": len(rounds), "losses": [r["loss"] for r in rounds],
            "dropped": [r["dropped"] for r in rounds],
            "server_platform": jax.default_backend(),
            "worker_platforms": worker_platforms,
            "vs_cpu_reference_rel": rel,
            "tol_rt": TOL_RT, "trace_records": len(records)}


def main():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform "
              f"{dev.platform!r}", file=sys.stderr)
        return 1

    from repro import compile_cache, telemetry
    cache_dir = compile_cache.enable()
    counters = telemetry.compile_counter()
    shutil.rmtree(OUT, ignore_errors=True)
    OUT.mkdir(parents=True)
    print(json.dumps({"device_kind": dev.device_kind,
                      "compile_cache": cache_dir}), flush=True)

    run_phase(counters, "paper_round", paper_round)
    run_phase(counters, "lm_split", lm_split)
    run_phase(counters, "episode_fleet", episode_fleet)
    run_phase(counters, "rt_loopback", rt_loopback)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
