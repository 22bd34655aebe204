"""CPSL correctness: fused step == explicit two-phase protocol, split ==
assembled model, FedAvg semantics, v=V degeneracy to FL, compression,
straggler dropout."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import CPSLConfig
from repro.core import compression as cmp
from repro.core.cpsl import CPSL, FLTrainer
from repro.core.splitting import make_lm_split, make_split_model
from repro.models import api, lenet

KEY = jax.random.PRNGKey(0)


def _lenet_batch(K, B, seed=0):
    k = jax.random.PRNGKey(seed)
    return {"image": jax.random.normal(k, (K, B, 28, 28, 1)),
            "label": jax.random.randint(k, (K, B), 0, 10)}


def test_fused_equals_protocol_lenet():
    """The fused autodiff step IS the paper's smashed-gradient protocol."""
    ccfg = CPSLConfig(cut_layer=3, cluster_size=4, local_epochs=1)
    split = make_split_model("lenet", 3)
    cp_f = CPSL(split, ccfg)
    cp_p = CPSL(split, ccfg.replace(fused_step=False)
                if hasattr(ccfg, "replace") else ccfg)
    import dataclasses
    cp_p = CPSL(split, dataclasses.replace(ccfg, fused_step=False))
    s_f, s_p = cp_f.init_state(KEY), cp_p.init_state(KEY)
    batch = _lenet_batch(4, 8)
    s_f, m_f = cp_f.cluster_step(s_f, batch)
    s_p, m_p = cp_p.cluster_step(s_p, batch)
    for a, b in zip(jax.tree.leaves(s_f["dev"]), jax.tree.leaves(s_p["dev"])):
        assert jnp.abs(a - b).max() < 1e-5
    for a, b in zip(jax.tree.leaves(s_f["srv"]), jax.tree.leaves(s_p["srv"])):
        assert jnp.abs(a - b).max() < 1e-5
    assert abs(float(m_f["loss"]) - float(m_p["loss"])) < 1e-5


def test_fused_equals_protocol_lm():
    import dataclasses
    cfg = registry.reduce_for_smoke(registry.get("qwen2-0.5b")).replace(
        dtype="float32")
    split = make_lm_split(cfg, 1)
    ccfg = CPSLConfig(cut_layer=1, cluster_size=2, local_epochs=1)
    cp_f = CPSL(split, ccfg)
    cp_p = CPSL(split, dataclasses.replace(ccfg, fused_step=False))
    s_f, s_p = cp_f.init_state(KEY), cp_p.init_state(KEY)
    b = registry.concrete_batch(KEY, cfg, batch=4, seq=12)
    batch = jax.tree.map(lambda t: t.reshape((2, 2) + t.shape[1:]), b)
    s_f, _ = cp_f.cluster_step(s_f, batch)
    s_p, _ = cp_p.cluster_step(s_p, batch)
    for a, b_ in zip(jax.tree.leaves(s_f["dev"]),
                     jax.tree.leaves(s_p["dev"])):
        assert jnp.abs(a - b_).max() < 1e-4


def test_single_device_cpsl_equals_centralized():
    """M=1, K=1, L=1 CPSL == centralized SGD on the same data (the split
    is just the chain rule)."""
    v = 4
    split = make_split_model("lenet", v)
    ccfg = CPSLConfig(cut_layer=v, cluster_size=1, local_epochs=1,
                      lr_device=0.05, lr_server=0.05)
    cp = CPSL(split, ccfg)
    state = cp.init_state(KEY)
    full = lenet.merge_params(
        jax.tree.map(lambda t: t[0], state["dev"]), state["srv"])
    batch = _lenet_batch(1, 16)
    state, _ = cp.cluster_step(state, batch)
    # centralized step
    flat = {"image": batch["image"][0], "label": batch["label"][0]}
    g = jax.grad(lenet.loss_fn)(full, flat)
    cent = jax.tree.map(lambda p, gg: p - 0.05 * gg, full, g)
    merged = lenet.merge_params(jax.tree.map(lambda t: t[0], state["dev"]),
                                state["srv"])
    for a, b in zip(jax.tree.leaves(cent), jax.tree.leaves(merged)):
        assert jnp.abs(a - b).max() < 1e-5


def test_split_forward_equals_full_forward():
    """Split at any v: device_apply + server path == assembled model."""
    cfg = registry.reduce_for_smoke(registry.get("gemma2-2b")).replace(
        dtype="float32")
    toks = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
    labels = jax.random.randint(jax.random.fold_in(KEY, 1), (2, 16), 0,
                                cfg.vocab_size)
    for v in range(1, cfg.n_layers):
        split = make_lm_split(cfg, v)
        dev = split.init_device(KEY)
        srv = split.init_server(jax.random.fold_in(KEY, 2))
        sm, _ = split.device_apply(dev, {"tokens": toks})
        loss_split, _ = split.server_loss(srv, sm, {"tokens": toks,
                                                    "labels": labels})
        params, out_cfg = split.export(dev, srv)
        from repro.models import transformer as tfm
        loss_exp = tfm.loss_fn(params, {"tokens": toks, "labels": labels},
                               out_cfg)
        assert abs(float(loss_split) - float(loss_exp)) < 1e-4, v


def test_fedavg_weighted_mean():
    split = make_split_model("lenet", 2)
    ccfg = CPSLConfig(cut_layer=2, cluster_size=3)
    cp = CPSL(split, ccfg)
    state = cp.init_state(KEY)
    # make client rows distinct
    state["dev"] = jax.tree.map(
        lambda t: t * jnp.arange(1., 4.).reshape((3,) + (1,) * (t.ndim - 1)),
        state["dev"])
    before = jax.tree.leaves(state["dev"])[0]
    sizes = jnp.array([1.0, 2.0, 1.0])
    state = cp.fedavg(state, data_sizes=sizes)
    after = jax.tree.leaves(state["dev"])[0]
    want = (before[0] * 1 + before[1] * 2 + before[2] * 1) / 4.0
    assert jnp.abs(after[0] - want).max() < 1e-6
    assert jnp.abs(after[1] - after[0]).max() == 0


def test_cut_at_V_equals_fl():
    """Paper: v = V degenerates CPSL to FL. FLTrainer reproduces one round
    of per-device SGD + averaging."""
    fl = FLTrainer(lenet.loss_fn, lambda k: lenet.init(k), n_devices=3,
                   lr=0.05, local_steps=2)
    state = fl.init_state(KEY)
    batch = {"image": jax.random.normal(KEY, (3, 2, 8, 28, 28, 1)),
             "label": jax.random.randint(KEY, (3, 2, 8), 0, 10)}
    state2, loss = fl.round(state, batch)
    assert jnp.isfinite(loss)
    # manual: per-device 2 sgd steps then mean
    p0 = lenet.init(KEY)
    outs = []
    for d in range(3):
        p = p0
        for s in range(2):
            b = {"image": batch["image"][d, s], "label": batch["label"][d, s]}
            g = jax.grad(lenet.loss_fn)(p, b)
            p = jax.tree.map(lambda a, b_: a - 0.05 * b_, p, g)
        outs.append(p)
    mean = jax.tree.map(lambda *ts: sum(ts) / 3.0, *outs)
    for a, b in zip(jax.tree.leaves(mean),
                    jax.tree.leaves(jax.tree.map(lambda t: t[0],
                                                 state2["params"]))):
        assert jnp.abs(a - b).max() < 1e-5


def test_straggler_dropout_keeps_at_least_one():
    import dataclasses
    split = make_split_model("lenet", 2)
    ccfg = CPSLConfig(cut_layer=2, cluster_size=4, straggler_dropout=0.99)
    cp = CPSL(split, ccfg)
    state = cp.init_state(KEY)
    state["dev"] = jax.tree.map(
        lambda t: t + jnp.arange(4.).reshape((4,) + (1,) * (t.ndim - 1)),
        state["dev"])
    state = cp.fedavg(state)   # must not NaN even with 99% dropout
    for leaf in jax.tree.leaves(state["dev"]):
        assert bool(jnp.isfinite(leaf).all())


def test_compression_error_feedback_unbiased_over_time():
    """topk+EF: cumulative compressed sum converges to cumulative true sum."""
    x = jax.random.normal(KEY, (64,))
    ef = jnp.zeros((64,))
    acc = jnp.zeros((64,))
    for i in range(30):
        comp, ef = cmp.apply_with_error_feedback(x, ef, "topk", 0.25)
        acc = acc + comp
    # after T rounds of constant signal: acc + ef == T * x exactly
    assert jnp.abs(acc + ef - 30 * x).max() < 1e-4


def test_topk_keeps_largest():
    x = jnp.array([0.1, -5.0, 2.0, 0.01, -0.5, 3.0, 0.0, 1.0])
    out = cmp.topk_mask(x, 0.25)
    assert float(out[1]) == -5.0 and float(out[5]) == 3.0
    assert float(jnp.count_nonzero(out)) == 2


def test_topk_exact_k_on_ties():
    """Tied magnitudes (quantized or zero-heavy deltas) must not inflate
    the kept count past k — the latency model prices the uplink with
    compression_ratio, which assumes exactly k entries survive."""
    x = jnp.array([1.0, -1.0, 1.0, 1.0, 0.5, -1.0, 1.0, 0.25])
    out = cmp.topk_mask(x, 0.5)                    # k = 4, six entries tie
    assert int(jnp.count_nonzero(out)) == 4
    assert bool(jnp.all(jnp.abs(out[out != 0]) == 1.0))
    # zero-heavy delta: the old >=-threshold rule kept ALL 16 entries
    z = jnp.zeros((16,)).at[3].set(2.0)
    out = cmp.topk_mask(z, 0.25)                   # k = 4, zeros tie
    assert int(jnp.count_nonzero(out)) == 1 and float(out[3]) == 2.0
    # multi-dim leaves keep their shape
    w = jnp.ones((4, 4))
    assert int(jnp.count_nonzero(cmp.topk_mask(w, 0.25))) == 4
    assert cmp.topk_mask(w, 0.25).shape == (4, 4)


def test_int8_quantization_bounded_error():
    x = jax.random.normal(KEY, (128,)) * 3
    q = cmp.compress_int8(x)
    assert jnp.abs(q - x).max() <= float(jnp.abs(x).max()) / 127.0 + 1e-6


def test_cpsl_loss_decreases_lenet():
    split = make_split_model("lenet", 3)
    ccfg = CPSLConfig(cut_layer=3, cluster_size=4, local_epochs=2,
                      lr_device=0.05, lr_server=0.05)
    cp = CPSL(split, ccfg)
    state = cp.init_state(KEY)
    losses = []
    batch = _lenet_batch(4, 16, seed=1)
    for i in range(30):
        state, m = cp.cluster_step(state, batch)
        losses.append(float(m["loss"]))
        state = cp.fedavg(state)
    assert losses[-1] < losses[0] - 0.15, losses[:3] + losses[-3:]
