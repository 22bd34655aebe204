"""Experiment-fleet equivalence: ``CPSL.run_training_fused`` (whole
R-round curve as one donated jit with in-jit eval) and ``CPSL.run_fleet``
(vmap of that curve over the replica axis) vs their looped / solo
references.

The contract has four layers, each pinned here:
  1. curve — the single-jit training curve reproduces R looped
     ``run_round_fused`` calls round-for-round (ints/rng bit-exact,
     floats ULP-equal per leaf), on both the default unrolled round axis
     and the ``scan_rounds`` + im2col lowering;
  2. fleet — replica r of a homogeneous fleet is bit-exact (int/rng)
     and ULP-equal (float) to the solo curve with seed r, including
     per-replica ``lr_scale`` applied as data (on the im2col lowering;
     direct convs under vmap hold a per-step ULP bound);
  3. padding — in a heterogeneous (padded + masked) fleet, padded slots
     never contribute: perturbing their index-table entries leaves every
     output bit-identical, padded metric slots come back NaN, and each
     replica still tracks its own-layout solo run;
  4. eval — the in-jit eval curve matches host-side evaluation of the
     exported params (``lenet.accuracy``).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import CPSLConfig
from repro.core.cpsl import CPSL
from repro.core.splitting import make_split_model
from repro.data.pipeline import (DeviceResidentDataset, fleet_plan,
                                 round_index_table)
from repro.data.synthetic import non_iid_split, synthetic_mnist
from repro.models import lenet

KEY = jax.random.PRNGKey(0)
M, K, B, L, R = 2, 3, 4, 2, 2
CLUSTERS = [[0, 1, 2], [3, 4, 5]]
ULP = float(np.finfo(np.float32).eps)

_XTR, _YTR, _XTE, _YTE = synthetic_mnist(400, 50, seed=0)
_SHARDS = non_iid_split(_YTR, n_devices=6, samples_per_device=60, seed=0)


def _dsd():
    return DeviceResidentDataset(_XTR, _YTR, _SHARDS, B,
                                 eval_images=_XTE, eval_labels=_YTE)


def _ccfg(**kw):
    base = dict(cut_layer=2, n_clusters=M, cluster_size=K, local_epochs=L,
                batch_per_device=B, unroll_clients=True)
    base.update(kw)
    return CPSLConfig(**base)


def _cpsl(ccfg):
    return CPSL(make_split_model("lenet", ccfg.cut_layer,
                                 conv_impl=ccfg.conv_impl), ccfg)


def _assert_states_match(s_a, s_b, ulps=32, pick=None):
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(s_a)[0],
            jax.tree_util.tree_flatten_with_path(s_b)[0],
            strict=True):
        if pick is not None:
            b = b[pick]
        name = jax.tree_util.keystr(pa)
        if jnp.issubdtype(a.dtype, jnp.floating):
            tol = ulps * ULP * max(1.0, float(jnp.abs(a).max()))
            d = float(jnp.abs(a.astype(jnp.float32)
                              - b.astype(jnp.float32)).max())
            assert d <= tol, f"diverged at {name}: {d} > {tol}"
        else:
            assert jnp.array_equal(a, b), f"diverged at {name}"


# --------------------------------------------------------------------------
# 1. single-jit curve vs looped rounds
# --------------------------------------------------------------------------

@pytest.mark.parametrize("lowering", ["unrolled", "scan_rounds"])
def test_training_curve_matches_looped_rounds(lowering):
    """run_training_fused == R x run_round_fused at the same lowering —
    default (direct convs, round axis unrolled at trace time) and the
    scanned round axis on the im2col lowering."""
    kw = ({} if lowering == "unrolled"
          else dict(conv_impl="im2col", scan_rounds=True,
                    fused_round_unroll=1, unroll_clients=False))
    cp = _cpsl(_ccfg(**kw))
    dsd = _dsd()
    w = dsd.cluster_weights(CLUSTERS)

    s_loop = cp.init_state(KEY)
    looped = []
    for r in range(R):
        s_loop, m = cp.run_round_fused(
            s_loop, dsd.data, dsd.round_index_table(CLUSTERS, 0, r, L), w)
        looped.append(np.asarray(m["losses"]))

    idx = dsd.training_index_table(CLUSTERS, 0, R, L)
    s_curve, mc = cp.run_training_fused(cp.init_state(KEY), dsd.data,
                                        idx, w)
    _assert_states_match(s_loop, s_curve)
    np.testing.assert_allclose(np.asarray(mc["losses"]),
                               np.stack(looped), rtol=1e-6)
    assert mc["loss"].shape == (R,)


# --------------------------------------------------------------------------
# 2. homogeneous fleet vs solo curves
# --------------------------------------------------------------------------

def test_fleet_replicas_match_solo_runs():
    """Replica r (its own seed, shard table, and lr_scale-as-data) vs
    the solo curve at seed r: int/rng leaves bit-exact, float leaves
    ULP-equal; loss curves agree. On the im2col lowering that fleets use:
    XLA:CPU's batched direct-conv backward is not bit-identical to the
    solo one (see test_fleet_direct_conv_step_ulp_bound), and over a
    curve a ReLU sign flip turns those ULPs into a real divergence."""
    cp = _cpsl(_ccfg(conv_impl="im2col"))
    seeds = [0, 1, 2]
    shards = [non_iid_split(_YTR, n_devices=6, samples_per_device=60,
                            seed=s) for s in seeds]
    plan = fleet_plan(shards, B, [CLUSTERS] * 3, seeds, R, L)
    assert plan.cluster_mask is None and plan.client_mask is None
    dsd = _dsd()
    lrs = np.array([1.0, 0.5, 2.0], np.float32)

    states = cp.init_fleet_state(seeds)
    states, mf = cp.run_fleet(states, dsd.data, plan.idx, plan.weights,
                              lr_scale=lrs)
    for e, seed in enumerate(seeds):
        solo, ms = cp.run_training_fused(
            cp.init_state(jax.random.PRNGKey(seed)), dsd.data,
            plan.idx[e], plan.weights[e],
            lr_scale=jnp.float32(lrs[e]))
        _assert_states_match(solo, states, pick=e)
        np.testing.assert_allclose(np.asarray(ms["loss"]),
                                   np.asarray(mf["loss"][e]), rtol=1e-6)


def test_lr_scale_matches_baked_lr():
    """lr_scale as data == the same lr baked into the trace: a power-of-
    two scale keeps the float product exact, so the states are
    bit-identical."""
    dsd = _dsd()
    w = dsd.cluster_weights(CLUSTERS)
    idx = dsd.training_index_table(CLUSTERS, 0, R, L)
    cp_scaled = _cpsl(_ccfg())
    s_scaled, _ = cp_scaled.run_training_fused(
        cp_scaled.init_state(KEY), dsd.data, idx, w,
        lr_scale=jnp.float32(0.5))
    cp_baked = _cpsl(_ccfg(lr_device=0.05 * 0.5, lr_server=0.25 * 0.5))
    s_baked, _ = cp_baked.run_training_fused(
        cp_baked.init_state(KEY), dsd.data, idx, w)
    for a, b in zip(jax.tree.leaves(s_scaled), jax.tree.leaves(s_baked),
                    strict=True):
        assert jnp.array_equal(a, b)


def test_fleet_direct_conv_step_ulp_bound():
    """One fused step with direct convs, vmapped over replicas vs solo:
    XLA:CPU lowers the batched conv backward differently from the solo
    one, so float leaves agree to 16 ULP of the leaf's magnitude, not
    bitwise (INVARIANTS.md, "XLA:CPU ULP bounds")."""
    cp = _cpsl(_ccfg())
    dsd = _dsd()
    states = cp.init_fleet_state([0, 1, 2])
    idx = dsd.round_index_table(CLUSTERS, 0, 0, L)[0, 0]
    batch = jax.tree.map(lambda a: a[idx], dsd.data)

    def step(s):
        return cp.fused_step_impl(s, batch)[0]

    fleet = jax.jit(jax.vmap(step))(states)
    for e in range(3):
        solo = jax.jit(step)(jax.tree.map(lambda t: t[e], states))
        for a, b in zip(jax.tree.leaves(solo), jax.tree.leaves(fleet)):
            b = b[e]
            if jnp.issubdtype(a.dtype, jnp.floating):
                tol = 16 * ULP * float(jnp.abs(a).max())
                assert float(jnp.abs(a - b).max()) <= tol
            else:
                assert jnp.array_equal(a, b)


# --------------------------------------------------------------------------
# 3. padded layouts
# --------------------------------------------------------------------------

def _hetero_fleet():
    """Two replicas with different layouts: (M=2, K=3) and (M=3, K=2)
    -> padded to (3, 3) with both masks present."""
    layouts = [CLUSTERS, [[0, 1], [2, 3], [4, 5]]]
    shards = [_SHARDS, non_iid_split(_YTR, n_devices=6,
                                     samples_per_device=60, seed=1)]
    ccfg = _ccfg(n_clusters=3, cluster_size=3, local_epochs=1)
    plan = fleet_plan(shards, B, layouts, [0, 1], R, 1)
    assert plan.cluster_mask is not None
    return _cpsl(ccfg), plan, layouts, shards


@pytest.mark.parametrize("default_weights", [False, True],
                         ids=["shard-weights", "uniform-weights"])
def test_padded_slots_never_contribute(default_weights):
    """Perturbing every padded slot's index entries leaves all outputs
    bit-identical (the masking promise of CPSL.run_fleet) — including
    when the caller leaves ``weights`` at the uniform default, where the
    client mask must still zero padded slots out of FedAvg."""
    cp, plan, _, _ = _hetero_fleet()
    dsd = _dsd()
    weights = None if default_weights else plan.weights

    def run(idx):
        states = cp.init_fleet_state(plan.seeds)
        states, m = cp.run_fleet(
            states, dsd.data, idx, weights,
            cluster_mask=plan.cluster_mask, client_mask=plan.client_mask)
        return states, m

    s_a, m_a = run(plan.idx)
    poked = plan.idx.copy()
    pad = ~np.broadcast_to(
        plan.client_mask[:, None, :, None, :, None], poked.shape)
    assert pad.sum() > 0
    poked[pad] = (poked[pad] + 7) % len(_XTR)
    s_b, m_b = run(poked)
    for a, b in zip(jax.tree.leaves(s_a), jax.tree.leaves(s_b),
                    strict=True):
        assert jnp.array_equal(a, b)
    np.testing.assert_array_equal(
        np.asarray(m_a["loss"]), np.asarray(m_b["loss"]))


def test_padded_metrics_masked_and_replicas_track_solo():
    """Padded cluster slots report NaN losses; real metrics stay finite;
    each replica tracks the solo run of its own (unpadded) layout —
    reduction shapes differ under masking, so the tolerance is looser
    than the homogeneous ULP bound but still far below any real
    divergence."""
    cp, plan, layouts, shards = _hetero_fleet()
    dsd = _dsd()
    states = cp.init_fleet_state(plan.seeds)
    states, mf = cp.run_fleet(
        states, dsd.data, plan.idx, plan.weights,
        cluster_mask=plan.cluster_mask, client_mask=plan.client_mask)
    losses = np.asarray(mf["losses"]).reshape(2, R, 3, 1)
    assert np.isnan(losses[0, :, 2]).all()      # replica 0 pads cluster 2
    assert np.isfinite(losses[0, :, :2]).all()
    assert np.isfinite(losses[1]).all()          # replica 1: 3 real clusters
    assert np.isfinite(np.asarray(mf["loss"])).all()

    for e in (0, 1):
        ccfg_e = dataclasses.replace(cp.ccfg,
                                     n_clusters=len(layouts[e]),
                                     cluster_size=len(layouts[e][0]))
        cp_e = _cpsl(ccfg_e)
        idx = np.stack([round_index_table(shards[e], B, layouts[e],
                                          plan.seeds[e], r, 1)
                        for r in range(R)])
        w = np.stack([[len(shards[e][d]) for d in c]
                      for c in layouts[e]]).astype(np.float32)
        solo, ms = cp_e.run_training_fused(
            cp_e.init_state(jax.random.PRNGKey(plan.seeds[e])),
            dsd.data, idx, w)
        # padded fleet rows: compare the real client slots of the dev
        # stacks (the only leaves whose leading dim is padded)
        for (pa, a), (_, b) in zip(
                jax.tree_util.tree_flatten_with_path(solo)[0],
                jax.tree_util.tree_flatten_with_path(states)[0],
                strict=True):
            b = b[e]
            if a.shape != b.shape:
                b = b[:a.shape[0]]
            name = jax.tree_util.keystr(pa)
            if jnp.issubdtype(a.dtype, jnp.floating):
                tol = 1e-4 * max(1.0, float(jnp.abs(a).max()))
                d = float(jnp.abs(a - b).max())
                assert d <= tol, f"replica {e} diverged at {name}: {d}"
            else:
                assert jnp.array_equal(a, b), f"replica {e} at {name}"
        np.testing.assert_allclose(np.asarray(ms["loss"]),
                                   np.asarray(mf["loss"][e]), rtol=1e-4)


def test_fleet_plan_tables():
    """fleet_plan real rows == per-replica round tables (prefix-stable
    draws); padded slots zero-indexed with zero eq.-8 weight."""
    cp, plan, layouts, shards = _hetero_fleet()
    for e in (0, 1):
        for r in range(R):
            real = round_index_table(shards[e], B, layouts[e],
                                     plan.seeds[e], r, 1)
            Me, Ke = len(layouts[e]), len(layouts[e][0])
            np.testing.assert_array_equal(
                plan.idx[e, r, :Me, :, :Ke], real)
        assert (plan.weights[e][~plan.client_mask[e]] == 0).all()
        assert (plan.weights[e][plan.client_mask[e]] > 0).all()
        assert plan.cluster_mask[e].sum() == len(layouts[e])


# --------------------------------------------------------------------------
# 4. in-jit eval
# --------------------------------------------------------------------------

def test_in_jit_eval_matches_host_eval():
    """The eval curve carried in the metrics stack equals host-side
    evaluation of the exported params at the same rounds."""
    cp = _cpsl(_ccfg())
    dsd = _dsd()
    w = dsd.cluster_weights(CLUSTERS)
    idx = dsd.training_index_table(CLUSTERS, 0, 3, L)

    # replay the curve round by round, evaluating on the host
    host_acc, host_loss = [], []
    state = cp.init_state(KEY)
    for r in range(3):
        state, _ = cp.run_round_fused(
            state, dsd.data, dsd.round_index_table(CLUSTERS, 0, r, L), w)
        if r in cp.eval_rounds(3, 2):
            params, _ = cp.export_params(state)
            host_acc.append(lenet.accuracy(params, jnp.asarray(_XTE),
                                           jnp.asarray(_YTE)))
            logits = lenet.forward(params, jnp.asarray(_XTE))
            logp = jax.nn.log_softmax(logits)
            nll = -jnp.take_along_axis(
                logp, jnp.asarray(_YTE)[:, None], axis=-1)
            host_loss.append(float(jnp.mean(nll)))

    _, mc = cp.run_training_fused(cp.init_state(KEY), dsd.data, idx, w,
                                  eval_data=dsd.eval_data, eval_every=2)
    assert mc["eval_rounds"] == [1, 2]
    np.testing.assert_allclose(np.asarray(mc["eval"]["acc"]), host_acc,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(mc["eval"]["loss"]), host_loss,
                               rtol=1e-5)


def test_im2col_conv_bit_identical():
    """The im2col lowering's forward pass equals the direct conv on both
    paddings up to summation order (the fleet's conv_impl swap changes
    lowering, not semantics). XLA:CPU's direct conv accumulates the 9C
    products in another order than the dot, so elementwise the two agree
    to 4 eps of sum |x||w| + |b| over the receptive field (measured
    2.7), not bitwise (INVARIANTS.md, "XLA:CPU ULP bounds")."""
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (2, 12, 12, 8))
    w = jax.random.normal(jax.random.PRNGKey(4), (3, 3, 8, 16)) * 0.1
    b = jax.random.normal(jax.random.PRNGKey(5), (16,))
    from jax import lax
    dn = ("NHWC", "HWIO", "NHWC")
    for pad in ("VALID", "SAME"):
        direct = lax.conv_general_dilated(x, w, (1, 1), pad,
                                          dimension_numbers=dn) + b
        scale = lax.conv_general_dilated(jnp.abs(x), jnp.abs(w), (1, 1),
                                         pad, dimension_numbers=dn) \
            + jnp.abs(b)
        im2col = jax.jit(lenet.conv_im2col, static_argnums=3)(x, w, b, pad)
        assert bool(jnp.all(jnp.abs(im2col - direct) <= 4 * ULP * scale))
