"""MLA's causal flash core (``repro.kernels.mla_attention``) against the
reference attention in float32 at "highest": the jnp blocked path over a
sweep of shapes, the splash kernels in interpret mode at one, the count
of blocks it computes, and a structural guard on the training step of a
tiny bfloat16 MLA config."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

from repro.configs import registry
from repro.configs.base import CPSLConfig
from repro.core.cpsl import CPSL
from repro.core.splitting import make_split_model
from repro.kernels.mla_attention import blocked, ops
from repro.models.common import naive_attention

KEY = jax.random.PRNGKey(0)


def _inputs(B, S, H, D, Dv, dtype=jnp.float32):
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, H, D), dtype)
    v = jax.random.normal(ks[2], (B, S, H, Dv), dtype)
    g = jax.random.normal(ks[3], (B, S, H, Dv), dtype)
    return q, k, v, g


def _reference(q, k, v):
    """The grouped reference at G = H, R = 1, scaling by 1/sqrt(D)."""
    return naive_attention(q[:, :, :, None], k, v, causal=True)[:, :, :, 0]


def _out_and_grads(f, q, k, v, g):
    with jax.default_matmul_precision("highest"):
        o, vjp = jax.vjp(f, q, k, v)
        return (o,) + vjp(g)


def _assert_close(got, want, rtol):
    for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
        assert a.shape == b.shape, name
        err = float(jnp.abs(a - b).max()) / float(jnp.abs(b).max())
        assert err < rtol, (name, err)


# (B, S, H, Dqk, Dv, q block, kv block): dv != dqk, G = H and R = 1,
# at least 3 blocks each way so that skipped blocks exist
SWEEP = [
    (2, 48, 3, 24, 16, 16, 16),
    (1, 96, 2, 32, 16, 16, 32),
    (1, 96, 2, 24, 8, 32, 16),
    (2, 72, 4, 12, 20, 24, 24),
    (1, 60, 2, 20, 12, 20, 12),
]


@pytest.mark.parametrize("B,S,H,D,Dv,bq,bkv", SWEEP)
def test_blocked_core_matches_the_reference(B, S, H, D, Dv, bq, bkv):
    q, k, v, g = _inputs(B, S, H, D, Dv)
    assert S // bq >= 3 and S // bkv >= 3
    scale = 1 / math.sqrt(D)
    got = _out_and_grads(
        lambda q, k, v: blocked.flash(q, k, v, scale, bq, bkv),
        q, k, v, g)
    want = _out_and_grads(_reference, q, k, v, g)
    _assert_close(got, want, 2e-5)


def test_splash_kernel_matches_the_reference(monkeypatch):
    """The TPU kernels in interpret mode, at MLA's widths (192 and 128)
    and blocks of 128 over 384 positions: 6 of 9 blocks computed."""
    monkeypatch.setattr(ops, "KERNEL_BLOCK", 128)
    q, k, v, g = _inputs(1, 384, 2, 192, 128)
    got = _out_and_grads(
        lambda q, k, v: ops.splash_flash(q, k, v, 1 / math.sqrt(192)),
        q, k, v, g)
    want = _out_and_grads(_reference, q, k, v, g)
    _assert_close(got, want, 2e-5)


def test_dispatch_on_the_cpu_is_the_blocked_core():
    """Off the TPU the call runs the jnp path."""
    q, k, v, _ = _inputs(1, 48, 2, 24, 16)
    assert not ops.kernel_applies(48)
    np.testing.assert_array_equal(
        ops.mla_attention(q, k, v, scale=0.2, bq=16, bkv=24),
        blocked.flash(q, k, v, 0.2, 16, 24))


@pytest.mark.parametrize("S,bq,bkv", [(2048, 512, 512), (2048, 512, 1024),
                                      (2048, 1024, 512), (384, 128, 128),
                                      (96, 16, 32), (96, 32, 16), (60, 20, 12),
                                      (512, 512, 512)])
def test_blocks_computed_is_a_brute_count(S, bq, bkv):
    causal = np.tril(np.ones((S, S), bool))
    brute = sum(bool(causal[i:i + bq, j:j + bkv].any())
                for i in range(0, S, bq) for j in range(0, S, bkv))
    assert ops.mla_blocks_computed(S, bq, bkv) == brute


def test_blocks_computed_at_the_cells_length():
    """S = 2048 in blocks of 512: 10 of the 16 blocks."""
    assert ops.mla_blocks_computed(2048, 512, 512) == 10


# -- the training step's MLA core, structurally ---------------------------------

def _ops_under(jaxpr, scope, prefix=""):
    """(primitive, eqn) of every equation whose name stack, with its
    enclosing equations', holds ``scope``."""
    for e in jaxpr.eqns:
        stack = f"{prefix}/{e.source_info.name_stack}"
        if scope in stack:
            yield e.primitive.name, e
        for p in e.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                if isinstance(sub, jcore.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jcore.Jaxpr):
                    yield from _ops_under(sub, scope, stack)


def _contracted(e):
    (lhs_contract, _), _ = e.params["dimension_numbers"]
    return math.prod(e.invars[0].aval.shape[d] for d in lhs_contract)


def test_training_step_runs_mla_at_its_widths_in_bf16():
    """The CPSL cluster step of a tiny bfloat16 DeepSeek share: no pad of
    v to the query-key width inside ``mla``, every dot there takes
    bfloat16 operands, and the core's score and value dots accumulate in
    float32."""
    cfg = registry.reduce_for_smoke(registry.get("deepseek-v2-lite-16b-ep8"))
    assert cfg.dtype == "bfloat16"
    m = cfg.mla
    dqk, dv = m.qk_nope_head_dim + m.qk_rope_head_dim, m.v_head_dim
    assert dqk != dv
    cp = CPSL(make_split_model(cfg, 1),
              CPSLConfig(cut_layer=1, cluster_size=2, batch_per_device=2,
                         local_epochs=1))
    state = cp.init_state(KEY)
    b = registry.concrete_batch(KEY, cfg, batch=4, seq=32)
    batch = jax.tree.map(lambda t: t.reshape((2, 2) + t.shape[1:]), b)
    jaxpr = jax.make_jaxpr(cp.cluster_step)(state, batch).jaxpr
    found = list(_ops_under(jaxpr, "mla"))

    pads = [e for name, e in found if name == "pad"]
    assert not [e for e in pads if e.invars[0].aval.shape[-1] == dv
                and e.outvars[0].aval.shape[-1] == dqk]
    dots = [e for name, e in found if name == "dot_general"]
    assert dots
    for e in dots:
        assert all(x.aval.dtype == jnp.bfloat16 for x in e.invars), e
    core = [e for e in dots
            if e.params["preferred_element_type"] == jnp.float32]
    scores = [e for e in core if _contracted(e) == dqk]
    # p.v (and p^T.do): dv wide out, contracted over a block of positions
    values = [e for e in core if dv in e.outvars[0].aval.shape
              and _contracted(e) not in (dqk, dv)]
    assert scores and values
