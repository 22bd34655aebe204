"""Pallas TPU kernels (flash attention, Mamba-2 SSD), each with a jnp
oracle (``ref.py``) and a jit-ready wrapper (``ops.py``)."""
import jax


def interpret() -> bool:
    """Whether the wrappers run their kernel in Pallas interpret mode:
    on the CPU (the tests' platform) they do, on the TPU they compile.
    Any other platform has no kernel and raises — interpret mode is never
    a silent fallback for an accelerator."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(f"no Pallas kernel for platform {platform!r}")
