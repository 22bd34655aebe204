"""Batched LLM serving with the engine the decode-shape dry-runs lower.

Prefill + greedy decode on a reduced gemma2-2b (alternating local/global
attention, softcaps) and a reduced mamba2 (SSM state cache — O(1) decode).

    PYTHONPATH=src python examples/serve_llm.py
"""
import time

import jax
import jax.numpy as jnp

from repro import compile_cache
from repro.configs import registry
from repro.models import api
from repro.serving.engine import ServeEngine


def main():
    for arch in ("gemma2-2b", "mamba2-2.7b"):
        cfg = registry.reduce_for_smoke(registry.get(arch))
        params = api.init(jax.random.PRNGKey(0), cfg)
        eng = ServeEngine(cfg, params, cap=64)
        prompts = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                     cfg.vocab_size)
        t0 = time.time()
        out = eng.generate({"tokens": prompts}, steps=24)
        dt = time.time() - t0
        print(f"{arch}: generated {out.shape} tokens in {dt:.2f}s "
              f"({out.size / dt:.0f} tok/s on CPU); sample row: "
              f"{out[0, :8].tolist()}")
        # temperature sampling path
        out_t = eng.generate({"tokens": prompts}, steps=4, temperature=0.8,
                             key=jax.random.PRNGKey(2))
        assert out_t.shape == (4, 4)


if __name__ == "__main__":
    compile_cache.enable()
    main()
