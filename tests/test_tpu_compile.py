"""Compile the main path's kernels and the paper's fused round for a TPU
v5e chip that is described, not attached (the TPU compiler ships with
jaxlib). Nothing runs: these tests catch what XLA:TPU and Mosaic refuse
— unaligned block shapes, too much VMEM, a program that does not fit the
chip's 16 GB — which interpret mode on the CPU cannot show.

The topology is described inside a module-scoped fixture, never at
import, so every xdist worker collects the same tests and only the
worker given this file loads the TPU library."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.configs.base import CPSLConfig
from repro.core.cpsl import CPSL
from repro.core.splitting import make_split_model
from repro.kernels.flash_attention.kernel import flash_attention_flat
from repro.kernels.ssd.kernel import ssd_flat

V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits(compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES, total
    return total


@pytest.mark.parametrize("arch,softcap_attr", [("qwen2-0.5b", None),
                                               ("gemma2-2b", "attn_softcap")])
def test_flash_attention_compiles_for_v5e(one_chip, arch, softcap_attr):
    """The kernel at the model's head widths: qwen2-0.5b (D=64, GQA
    kv_repeat 7) and gemma2-2b (D=256, logit softcap 50); batch 8 and
    seq 512, the qwen2-0.5b split's server batch in chip_smoke.py."""
    cfg = registry.get(arch)
    G, D = cfg.n_kv_heads, cfg.resolved_head_dim
    R = cfg.n_heads // G
    B, S = 8, 512
    softcap = getattr(cfg, softcap_attr) if softcap_attr else 0.0

    def fwd(q, k, v):
        return flash_attention_flat(q, k, v, causal=True, softcap=softcap,
                                    kv_repeat=R)

    compiled = jax.jit(fwd).lower(
        _spec(one_chip, (B * G * R, S, D)), _spec(one_chip, (B * G, S, D)),
        _spec(one_chip, (B * G, S, D))).compile()
    _fits(compiled)
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_compiles_for_v5e(one_chip):
    """The SSD kernel at mamba2-2.7b widths (headdim 64, d_state 128,
    chunk 256, 80 heads), batch 2 and seq 512."""
    cfg = registry.get("mamba2-2.7b")
    ssm = cfg.ssm
    H = ssm.expand * cfg.d_model // ssm.headdim
    BH, S, P, N = 2 * H, 512, ssm.headdim, ssm.d_state

    def fwd(x, dt, A, Bm, Cm):
        return ssd_flat(x, dt, A, Bm, Cm, chunk=ssm.chunk_size)

    compiled = jax.jit(fwd).lower(
        _spec(one_chip, (BH, S, P)), _spec(one_chip, (BH, S)),
        _spec(one_chip, (BH,)), _spec(one_chip, (BH, S, N)),
        _spec(one_chip, (BH, S, N))).compile()
    _fits(compiled)
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_lenet_round_compiles_for_v5e(one_chip):
    """The paper's round as one donated program: LeNet, M=6 clusters of
    K=5, B=16, L=1, over the launcher's 50,000-sample resident dataset."""
    M, K, B, L, n = 6, 5, 16, 1, 50_000
    cpsl = CPSL(make_split_model("lenet", 2),
                CPSLConfig(cut_layer=2, n_clusters=M, cluster_size=K,
                           batch_per_device=B, local_epochs=L))
    state = jax.eval_shape(cpsl.init_state, jax.random.PRNGKey(0))
    state = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype), state)
    data = {"image": _spec(one_chip, (n, 28, 28, 1)),
            "label": _spec(one_chip, (n,), jnp.int32)}
    compiled = CPSL._run_round_fused.lower(
        cpsl, state, data, _spec(one_chip, (M, L, K, B), jnp.int32),
        _spec(one_chip, (M, K))).compile()
    _fits(compiled)
