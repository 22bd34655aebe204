"""Per-architecture smoke tests (deliverable f): every assigned arch at a
REDUCED config runs forward + a CPSL train step on CPU with finite outputs
and correct shapes. Full configs appear here only as abstract shapes
(parameter counts)."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import registry
from repro.configs.base import CPSLConfig
from repro.core.cpsl import CPSL
from repro.core.splitting import make_split_model
from repro.models import api

KEY = jax.random.PRNGKey(0)
ARCHS = registry.list_archs()


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_smoke(arch):
    cfg = registry.reduce_for_smoke(registry.get(arch))
    p = api.init(KEY, cfg)
    batch = registry.concrete_batch(KEY, cfg, batch=2, seq=16)
    logits, aux = api.forward(p, batch, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ARCHS)
def test_loss_and_grad_smoke(arch):
    cfg = registry.reduce_for_smoke(registry.get(arch))
    p = api.init(KEY, cfg)
    batch = registry.concrete_batch(KEY, cfg, batch=2, seq=16)
    loss, grads = jax.value_and_grad(
        lambda p: api.loss_fn(p, batch, cfg))(p)
    assert bool(jnp.isfinite(loss))
    gnorm = sum(float(jnp.abs(g).sum()) for g in jax.tree.leaves(grads))
    assert gnorm > 0 and jnp.isfinite(gnorm)


@pytest.mark.parametrize("arch", ARCHS)
def test_cpsl_train_step_smoke(arch):
    """The paper's technique applied to every assigned architecture."""
    cfg = registry.reduce_for_smoke(registry.get(arch))
    split = make_split_model(cfg, 1)
    ccfg = CPSLConfig(cut_layer=1, cluster_size=2, batch_per_device=2,
                      local_epochs=1)
    cp = CPSL(split, ccfg)
    state = cp.init_state(KEY)
    b = registry.concrete_batch(KEY, cfg, batch=2 * 2, seq=16)
    batch = jax.tree.map(lambda t: t.reshape((2, 2) + t.shape[1:]), b)
    state, metrics = cp.cluster_step(state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    state = cp.fedavg(state)
    # after FedAvg every client row is identical
    for leaf in jax.tree.leaves(state["dev"]):
        assert jnp.allclose(leaf[0], leaf[1], atol=0, rtol=0)


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "mamba2-2.7b",
                                  "deepseek-v2-lite-16b", "gemma2-2b",
                                  "jamba-v0.1-52b", "whisper-small"])
def test_decode_matches_forward(arch):
    """Serving invariant: prefill+decode logits == full forward (f32)."""
    cfg = registry.reduce_for_smoke(registry.get(arch)).replace(
        dtype="float32", attn_impl="naive")
    p = api.init(KEY, cfg)
    S = 12
    batch = registry.concrete_batch(KEY, cfg, batch=2, seq=S)
    logits_full, _ = api.forward(p, batch, cfg)
    pre = {k: (v[:, :8] if k in ("tokens",) else v)
           for k, v in batch.items()}
    last, cache = api.prefill(p, pre, cfg, cap=S)
    errs = [float(jnp.abs(last - logits_full[:, 7]).max())]
    for i in range(8, S):
        last, cache = api.decode_step(p, cache, batch["tokens"][:, i], i,
                                      cfg)
        errs.append(float(jnp.abs(last - logits_full[:, i]).max()))
    assert max(errs) < 5e-3, errs


def test_full_config_param_counts():
    """Full (unreduced) configs match the assignment's claimed sizes."""
    import numpy as np
    expected = {
        "chameleon-34b": 34.3e9, "deepseek-v2-lite-16b": 15.7e9,
        "phi3.5-moe-42b-a6.6b": 41.9e9, "mamba2-2.7b": 2.70e9,
        "jamba-v0.1-52b": 51.5e9, "gemma2-2b": 2.61e9,
        "qwen2.5-14b": 14.8e9, "qwen3-32b": 32.8e9, "qwen2-0.5b": 0.49e9,
        "whisper-small": 0.24e9,
    }
    for arch, want in expected.items():
        cfg = registry.get(arch)
        shapes = jax.eval_shape(lambda k: api.init(k, cfg, ),
                                jax.random.PRNGKey(0))
        n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
        assert abs(n - want) / want < 0.02, (arch, n, want)


def test_long_ctx_assignment():
    assert registry.cells("mamba2-2.7b")[-1] == "long_500k"
    assert registry.cells("jamba-v0.1-52b")[-1] == "long_500k"
    assert "long_500k" not in registry.cells("qwen3-32b")
    assert "long_500k" not in registry.cells("gemma2-2b")
