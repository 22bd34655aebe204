"""``repro.compile_cache.enable``: JAX's persistent compile cache goes to
``$JAX_COMPILATION_CACHE_DIR`` when that is set, and nowhere else; else
to ``.jax_cache/`` in the checkout. Each case runs in its own process,
since the cache directory is process-wide JAX state."""
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

_PROBE = """
import jax, jax.numpy as jnp
from repro import compile_cache
used = compile_cache.enable()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: x * 2 + 1)(jnp.arange(3.0)).block_until_ready()
print(used)
print(jax.config.jax_compilation_cache_dir)
"""


def _probe(env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True).stdout.split("\n")
    return out[0], out[1]


def test_cache_follows_the_environment(tmp_path):
    used, configured = _probe(tmp_path)
    assert used == configured == str(tmp_path)
    assert any(tmp_path.iterdir())          # the compile was written there


def test_cache_defaults_to_the_checkout():
    from repro import compile_cache
    used, configured = _probe(None)
    assert used == configured == str(compile_cache.REPO_CACHE_DIR)
    assert compile_cache.REPO_CACHE_DIR == ROOT / ".jax_cache"


_SCOPED = """
import sys
import jax, jax.numpy as jnp
from repro import compile_cache
compile_cache.enable()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

def f(x):
    if sys.argv[1] == "scoped":
        with jax.named_scope("device_side"):
            return jnp.tanh(x @ x)
    return jnp.tanh(x @ x)

print("device_side" in jax.jit(f).lower(jnp.ones((8, 8))).compile().as_text())
"""


def test_cached_program_keeps_its_own_scopes(tmp_path):
    """A program that differs from a cached one only in its named scopes
    is compiled anew, so its ops carry its own scopes."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))

    def run(kind):
        return subprocess.run([sys.executable, "-c", _SCOPED, kind], env=env,
                              capture_output=True, text=True, timeout=120,
                              check=True).stdout.split()[-1]

    assert run("plain") == "False"
    assert any(tmp_path.iterdir())
    assert run("scoped") == "True"
