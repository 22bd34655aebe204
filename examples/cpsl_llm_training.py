"""CPSL applied to an LM architecture (the framework generalization).

Runs the paper's cluster-parallel split training on a reduced qwen2-0.5b
(same family, CPU-sized), with the cut-layer profile priced from the real
architecture — showing the paper's resource management driving an LLM.

    PYTHONPATH=src python examples/cpsl_llm_training.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.configs import registry
from repro.configs.base import CPSLConfig
from repro.core.channel import NetworkCfg
from repro.core.cpsl import CPSL
from repro.core.profile import lm_profile
from repro.core.resource import saa_cut_selection
from repro.core.splitting import make_split_model
from repro.data.pipeline import LMClusterData
from repro.data.synthetic import MarkovLM


def main():
    cfg = registry.reduce_for_smoke(registry.get("qwen2-0.5b"))
    seq, batch = 64, 4
    n_clusters, cluster_size = 2, 3
    n_devices = n_clusters * cluster_size

    # price the cut layers from the FULL qwen2-0.5b architecture: the SAA
    # search sees real per-layer params/FLOPs/smashed sizes
    full_prof = lm_profile(registry.get("qwen2-0.5b"), seq=4096)
    ncfg = NetworkCfg(n_devices=n_devices, f_mean_range=(5e9, 50e9),
                      snr_mean_range_db=(15, 35))
    v_star, means = saa_cut_selection(full_prof, ncfg, B=batch, L=1,
                                      n_clusters=n_clusters,
                                      cluster_size=cluster_size,
                                      n_samples=2, gibbs_iters=40,
                                      cuts=range(1, 7))
    print(f"SAA over qwen2-0.5b cut layers 1..6: v*={v_star} "
          f"(means {np.round(means, 1)})")

    v = min(v_star, cfg.n_layers - 1)
    cpsl = CPSL(make_split_model(cfg, v),
                CPSLConfig(cut_layer=v, n_clusters=n_clusters,
                           cluster_size=cluster_size,
                           lr_device=0.3, lr_server=0.3))
    state = cpsl.init_state(jax.random.PRNGKey(0))
    data = LMClusterData(MarkovLM(cfg.vocab_size, seed=0), n_devices,
                         batch, seq)
    devices = list(range(n_devices))
    for rnd in range(6):
        def batch_fn(m, l):
            cluster = devices[m * cluster_size:(m + 1) * cluster_size]
            return jax.tree.map(jnp.asarray, data.cluster_batch(cluster))
        state, metrics = cpsl.run_round(state, batch_fn,
                                        n_clusters=n_clusters)
        print(f"round {rnd}: loss {metrics['loss']:.3f}")

    # the trained split model exports to a standard serving checkpoint
    params, out_cfg = cpsl.export_params(state)
    from repro.models import transformer as tfm
    toks = jnp.zeros((1, 8), jnp.int32)
    logits, _ = tfm.forward(params, toks, out_cfg)
    print("exported assembled model, logits:", logits.shape)


if __name__ == "__main__":
    compile_cache.enable()
    main()
