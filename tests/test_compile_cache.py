"""The entry points' persistent compilation cache: placed from outside
through ``JAX_COMPILATION_CACHE_DIR``, else at a fixed path in the
checkout.  Each case runs in a fresh interpreter, since JAX reads the
variable when it starts and the cache settings are process-wide."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROBE = """
import jax, jax.numpy as jnp
from repro.util.compile_cache import enable_compile_cache
path = enable_compile_cache()
if {compile}:
    jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()
print(path)
print(jax.config.jax_compilation_cache_dir)
print(jax.config.jax_persistent_cache_min_compile_time_secs)
"""


def _probe(env_extra, compile):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=os.path.join(REPO, "src"), JAX_PLATFORMS="cpu",
               **env_extra)
    r = subprocess.run(
        [sys.executable, "-c", PROBE.format(compile=compile)],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout.split()


def test_cache_dir_from_environment_is_used_as_is(tmp_path):
    cache = str(tmp_path / "cache")
    path, config_dir, min_secs = _probe(
        {"JAX_COMPILATION_CACHE_DIR": cache}, compile=True
    )
    assert path == config_dir == cache
    assert float(min_secs) == 0.0
    assert os.listdir(cache), "nothing was written to the cache directory"


@pytest.mark.parametrize("env_value", [None, ""])
def test_cache_dir_defaults_to_checkout(env_value):
    env_extra = {} if env_value is None else {
        "JAX_COMPILATION_CACHE_DIR": env_value
    }
    path, config_dir, _ = _probe(env_extra, compile=False)
    assert path == config_dir == os.path.join(REPO, ".jax_cache")
