"""Component-level units: MoE dispatch vs per-token oracle, SSD impls,
MLA absorption, chunked CE, norms, optimizers, data pipeline."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st

from repro import optim
from repro.configs import registry
from repro.configs.base import LayerSpec, MLACfg, MoECfg, ModelConfig, SSMCfg
from repro.models import common as cm
from repro.models import mamba2 as mb

KEY = jax.random.PRNGKey(0)


# -- MoE ---------------------------------------------------------------------

def _moe_cfg(E=8, k=2, shared=0):
    return ModelConfig(
        name="t", family="moe", d_model=32, n_layers=2, n_heads=2,
        n_kv_heads=2, d_ff=64, vocab_size=64, dtype="float32",
        moe=MoECfg(n_experts=E, top_k=k, d_ff_expert=32,
                   n_shared_experts=shared))


def test_moe_dispatch_matches_naive_when_capacity_ample():
    """The dropless grouped path against the dense oracle (no capacity:
    every token choice is computed)."""
    cfg = _moe_cfg()
    p = cm.moe_init(KEY, cfg)
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (2, 16, 32))
    y, aux = cm.moe_apply(p, x, cfg)
    y_ref = cm.moe_apply_naive(p, x, cfg)
    assert jnp.abs(y - y_ref).max() < 1e-4
    assert float(aux["loss"]) > 0
    assert float(aux["moe_routed"]) == 2 * 16 * 2


def test_moe_shared_experts_added():
    cfg = _moe_cfg(shared=2)
    p = cm.moe_init(KEY, cfg)
    x = jax.random.normal(KEY, (1, 16, 32))
    y, _ = cm.moe_apply(p, x, cfg)
    y_ref = cm.moe_apply_naive(p, x, cfg)
    assert jnp.abs(y - y_ref).max() < 1e-4


@pytest.mark.parametrize("arch", [a for a in registry.list_archs()
                                  if registry.get(a).moe is not None])
def test_moe_dropless_matches_naive_for_every_moe_config(arch):
    cfg = registry.reduce_for_smoke(registry.get(arch)).replace(
        dtype="float32")
    p = cm.moe_init(KEY, cfg)
    x = jax.random.normal(jax.random.fold_in(KEY, 2), (2, 16, cfg.d_model))
    y, aux = cm.moe_apply(p, x, cfg)
    assert jnp.abs(y - cm.moe_apply_naive(p, x, cfg)).max() < 1e-4
    assert 0 < float(aux["moe_routed"]) <= 2 * 16 * cfg.moe.top_k


def test_moe_gradients_match_naive():
    """The grouped path's backward (its masked grouped matmuls, the
    scatter-free permutations) against the dense oracle's."""
    cfg = _moe_cfg(shared=1)
    p = cm.moe_init(KEY, cfg)
    x = jax.random.normal(jax.random.fold_in(KEY, 5), (2, 16, 32))
    g = jax.random.normal(jax.random.fold_in(KEY, 6), (2, 16, 32))

    def grouped(p, x):
        return jnp.sum(cm.moe_apply(p, x, cfg)[0] * g)

    def naive(p, x):
        return jnp.sum(cm.moe_apply_naive(p, x, cfg) * g)

    got = jax.grad(grouped, argnums=(0, 1))(p, x)
    want = jax.grad(naive, argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert jnp.abs(a - b).max() <= 1e-4 * (1 + jnp.abs(b).max())


def test_moe_skewed_router_drops_nothing():
    """One expert is every token's first choice: a capacity dispatch
    would drop most of them; the grouped path computes them all."""
    cfg = _moe_cfg()
    p = cm.moe_init(KEY, cfg)
    p["router"] = p["router"].at[:, 3].set(0.0)
    x = jax.random.normal(jax.random.fold_in(KEY, 3), (2, 16, 32))
    x = x.at[..., 0].set(8.0)
    p["router"] = p["router"].at[0, 3].set(4.0)
    y, aux = cm.moe_apply(p, x, cfg)
    assert float(aux["moe_load_max"]) == 2 * 16
    assert jnp.abs(y - cm.moe_apply_naive(p, x, cfg)).max() < 1e-4


def test_moe_shares_sum_to_the_uncut_layer():
    """Four chips' shares of 8 experts (2 held each, the router over all
    8): their parts of the result summed, with the shared expert counted
    once, equal the layer with every expert held. A chip holds the first
    experts of its router's columns: share s rolls them to the front."""
    cfg = _moe_cfg(shared=1)
    p = cm.moe_init(KEY, cfg)
    x = jax.random.normal(jax.random.fold_in(KEY, 4), (2, 16, 32))
    full, _ = cm.moe_apply(p, x, cfg)
    shared = cm.mlp_apply(p["shared"], x, cfg)
    parts, routed = [], 0.0
    for s in range(4):
        held = slice(2 * s, 2 * s + 2)
        ps = dict(p, router=jnp.roll(p["router"], -2 * s, axis=1),
                  **{k: p[k][held] for k in ("w_gate", "w_up", "w_down")})
        cs = cfg.replace(moe=dataclasses.replace(cfg.moe, n_held=2))
        y, aux = cm.moe_apply(ps, x, cs)
        assert jnp.abs(y - cm.moe_apply_naive(ps, x, cs)).max() < 1e-4
        parts.append(y - shared)
        routed += float(aux["moe_routed"])
    assert jnp.abs(sum(parts) + shared - full).max() < 1e-4
    assert routed == 2 * 16 * 2


def test_rope_without_scaling_is_unchanged():
    """No rope scaling gives the bits of the plain half-rotation rope."""
    x = jax.random.normal(KEY, (2, 12, 3, 16)).astype(jnp.bfloat16)
    pos = jnp.arange(12)
    freqs = 1.0 / (1e6 ** (jnp.arange(0, 16, 2, dtype=jnp.float32) / 16))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    want = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)
    assert bool((cm.apply_rope(x, pos, 1e6) == want).all())


def test_yarn_frequencies_and_softmax_gain():
    """DeepSeek-V2-Lite's YaRN: rope dim 64, base 1e4, 4096 original
    positions, factor 40, betas 32 and 1, mscale = mscale_all_dim = 0.707."""
    cfg = registry.get("deepseek-v2-lite-16b")
    y = cfg.rope_scaling
    d = 64

    def corr(r):
        return d * np.log(4096 / (2 * np.pi * r)) / (2 * np.log(1e4))

    low, high = max(int(np.floor(corr(32))), 0), min(int(np.ceil(corr(1))),
                                                      d - 1)
    assert (low, high) == (10, 23)
    i = np.arange(32)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    extra = 1e4 ** (-2 * i / d)
    want = extra * (1 - ramp) + extra / 40 * ramp
    got = np.asarray(cm.rope_freqs(d, 1e4, y))
    assert np.allclose(got, want, rtol=1e-6)
    m = 0.1 * 0.707 * np.log(40) + 1
    assert cm.mla_softmax_gain(cfg) == pytest.approx(m * m)
    assert cm.mla_softmax_gain(cfg) == pytest.approx(1.5897, abs=1e-4)


def test_moe_grad_flows_to_router():
    cfg = _moe_cfg()
    p = cm.moe_init(KEY, cfg)
    x = jax.random.normal(KEY, (1, 16, 32))

    def loss(p):
        y, aux = cm.moe_apply(p, x, cfg)
        return (y ** 2).mean() + aux["loss"]

    g = jax.grad(loss)(p)
    assert float(jnp.abs(g["router"]).max()) > 0


# -- SSD ----------------------------------------------------------------------

def test_ssd_chunked_vs_scan_model_layout():
    B_, S, H, P, N = 2, 96, 2, 16, 8
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B_, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B_, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B_, S, H, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B_, S, H, N)) * 0.5
    y1, h1 = mb.ssd_chunked(x, dt, A, Bm, Cm, chunk=32)
    y2, h2 = mb.ssd_scan(x, dt, A, Bm, Cm)
    assert jnp.abs(y1 - y2).max() < 5e-5
    assert jnp.abs(h1 - h2).max() < 5e-5


def test_ssd_decode_step_continues_sequence():
    """scan over S == prefill(S-1) + one decode step."""
    B_, S, H, P, N = 1, 33, 2, 8, 4
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B_, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B_, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B_, S, H, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B_, S, H, N)) * 0.5
    y_full, _ = mb.ssd_scan(x, dt, A, Bm, Cm)
    _, h = mb.ssd_scan(x[:, :-1], dt[:, :-1], A, Bm[:, :-1], Cm[:, :-1])
    y_step, _ = mb.ssd_decode_step(h, x[:, -1], dt[:, -1], A, Bm[:, -1],
                                   Cm[:, -1])
    assert jnp.abs(y_step - y_full[:, -1]).max() < 1e-5


# -- MLA -----------------------------------------------------------------------

def test_mla_absorbed_equals_materialized():
    cfg = ModelConfig(
        name="t", family="moe", d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=4, d_ff=64, vocab_size=64, attn_kind="mla",
        dtype="float32", attn_impl="naive",
        mla=MLACfg(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                   v_head_dim=16))
    p = cm.mla_init(KEY, cfg)
    x = jax.random.normal(KEY, (2, 12, 64))
    y1 = cm.mla_apply(p, x, cfg, causal=True, absorbed=False)
    y2 = cm.mla_apply(p, x, cfg, causal=True, absorbed=True)
    assert jnp.abs(y1 - y2).max() < 1e-4


# -- losses ---------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(B=st.integers(1, 3), S=st.sampled_from([8, 16, 32]),
       V=st.sampled_from([11, 64]), chunk=st.sampled_from([4, 8]))
def test_chunked_ce_equals_full(B, S, V, chunk):
    cfg = ModelConfig(name="t", family="dense", d_model=16, n_layers=1,
                      vocab_size=V, dtype="float32", loss_chunk=chunk)
    x = jax.random.normal(KEY, (B, S, 16))
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (16, V))
    labels = jax.random.randint(jax.random.fold_in(KEY, 2), (B, S), 0, V)
    full = cm.lm_head_loss(w, x, labels, cfg.replace(loss_chunk=0))
    chunked = cm.lm_head_loss(w, x, labels, cfg)
    assert abs(float(full) - float(chunked)) < 1e-5


def test_chunked_ce_grad_matches():
    cfg = ModelConfig(name="t", family="dense", d_model=16, n_layers=1,
                      vocab_size=32, dtype="float32", loss_chunk=8)
    x = jax.random.normal(KEY, (2, 16, 16))
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (16, 32))
    labels = jax.random.randint(jax.random.fold_in(KEY, 2), (2, 16), 0, 32)
    g1 = jax.grad(lambda x: cm.lm_head_loss(w, x, labels,
                                            cfg.replace(loss_chunk=0)))(x)
    g2 = jax.grad(lambda x: cm.lm_head_loss(w, x, labels, cfg))(x)
    assert jnp.abs(g1 - g2).max() < 1e-5


def test_softcap_bounds_logits():
    x = jnp.linspace(-100, 100, 64)
    y = cm._soft_cap(x, 30.0)
    assert float(jnp.abs(y).max()) <= 30.0


# -- norms / optimizers ----------------------------------------------------------

def test_rmsnorm_unit_scale():
    p = cm.norm_init(16, "rmsnorm")
    x = jax.random.normal(KEY, (4, 16)) * 7
    y = cm.apply_norm(p, x, "rmsnorm")
    rms = jnp.sqrt(jnp.mean(y * y, axis=-1))
    assert jnp.abs(rms - 1.0).max() < 1e-3


def test_layernorm_zero_mean():
    p = cm.norm_init(16, "layernorm")
    x = jax.random.normal(KEY, (4, 16)) + 3
    y = cm.apply_norm(p, x, "layernorm")
    assert jnp.abs(y.mean(-1)).max() < 1e-4


def test_adamw_converges_quadratic():
    opt = optim.adamw(0.1)
    params = {"w": jnp.array([5.0, -3.0])}
    state = opt.init(params)
    for i in range(200):
        g = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
        params, state = opt.step(g, state, params, step=i)
    assert jnp.abs(params["w"]).max() < 1e-2


def test_momentum_vs_sgd_direction():
    opt = optim.momentum(0.1, 0.9)
    params = jnp.array([1.0])
    state = opt.init(params)
    for i in range(3):
        params, state = opt.step(jnp.array([1.0]), state, params, step=i)
    # momentum accumulates: 0.1*(1 + 1.9 + 2.71)
    assert float(params[0]) == pytest.approx(1 - 0.1 * (1 + 1.9 + 2.71),
                                             rel=1e-4)


def test_clip_by_global_norm():
    g = {"a": jnp.full((4,), 10.0)}
    clipped, n = optim.clip_by_global_norm(g, 1.0)
    assert float(optim.global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)


# -- data --------------------------------------------------------------------------

def test_non_iid_split_properties():
    from repro.data.synthetic import non_iid_split, synthetic_mnist
    _, ytr, _, _ = synthetic_mnist(4000, 10, seed=0)
    parts = non_iid_split(ytr, n_devices=10, classes_per_device=3,
                          samples_per_device=180, seed=0)
    assert len(parts) == 10
    for idx in parts:
        assert len(idx) == 180
        assert len(np.unique(ytr[idx])) <= 3


def test_markov_lm_learnable_structure():
    from repro.data.synthetic import MarkovLM
    lm = MarkovLM(1000, eff_vocab=16, seed=0)
    b = lm.sample(4, 64, np.random.default_rng(0))
    assert b["tokens"].shape == (4, 64)
    assert (b["tokens"] < 16).all()
    assert np.array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])
