"""MLA's causal flash-attention core for training: one call, chosen by
the backend and by what the call shows.

On the TPU, at a length the kernel's blocks divide: the
installed Pallas splash-attention kernels (a forward, a dq and a dkv
kernel), which take v narrower than q and k and skip every block above
the diagonal through their mask info. Elsewhere: ``blocked.flash``, the
same block arithmetic in jnp.

q, k (B, S, H, Dqk); v (B, S, H, Dv); ``softmax(scale q k^T) v``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import kernels
from repro.kernels.mla_attention import blocked

# The kernel's q and kv block, in every pass (tuned on a v5e chip at
# DeepSeek-V2-Lite's widths, S=2048; PERF.md).
KERNEL_BLOCK = 512


def kernel_applies(S: int) -> bool:
    return jax.default_backend() == "tpu" and S % KERNEL_BLOCK == 0


def mla_blocks_computed(S: int, bq: int, bkv: int) -> int:
    """(q block, kv block) pairs the causal core computes at length S:
    those holding an entry on or below the diagonal."""
    return sum(int(blocked.kv_blocks_for(i, bq, bkv, S // bkv))
               for i in range(S // bq))


def splash_flash(q, k, v, scale: float):
    """The splash kernels, causal, over batch by ``vmap`` (interpret mode
    on the CPU, as ``kernels.interpret`` says). They take q scaled: the
    scale is folded in at float32, one rounding to q's dtype."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash,
        splash_attention_mask as splash_mask)
    S, H = q.shape[1], q.shape[2]
    b = KERNEL_BLOCK
    kernel = splash.make_splash_mha(
        splash_mask.MultiHeadMask([splash_mask.CausalMask((S, S))] * H),
        block_sizes=splash.BlockSizes(
            block_q=b, block_kv=b, block_q_dkv=b, block_kv_dkv=b,
            block_q_dq=b, block_kv_dq=b),
        head_shards=1, q_seq_shards=1, interpret=kernels.interpret())
    heads_first = lambda x: jnp.swapaxes(x, 1, 2)          # noqa: E731
    q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    o = jax.vmap(kernel)(heads_first(q), heads_first(k), heads_first(v))
    return heads_first(o)


def mla_attention(q, k, v, *, scale: float, bq: int, bkv: int):
    """Causal self-attention over the whole sequence. ``bq`` and ``bkv``
    (divisors of S) are the jnp path's blocks."""
    S = q.shape[1]
    if kernel_applies(S):
        return splash_flash(q, k, v, scale)
    return blocked.flash(q, k, v, scale, bq, bkv)
