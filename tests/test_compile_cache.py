"""`repro.runtime.compile_cache`: the persistent compile cache goes where
`JAX_COMPILATION_CACHE_DIR` says, else to the checkout's `.jax_cache/`."""
import os
import subprocess
import sys

from repro.runtime import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_default_is_fixed_checkout_dir(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    assert compile_cache.cache_dir() == os.path.join(REPO, ".jax_cache")
    monkeypatch.setenv(compile_cache.ENV, "/elsewhere/cache")
    assert compile_cache.cache_dir() == "/elsewhere/cache"


def test_env_dir_receives_the_cache(tmp_path):
    """With the variable set, `enable()` leaves the directory to JAX and
    a compile lands there."""
    env = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src"),
           "JAX_PLATFORMS": "cpu", compile_cache.ENV: str(tmp_path),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
           "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0"}
    code = ("from repro.runtime import compile_cache\n"
            "import jax, jax.numpy as jnp\n"
            "print(compile_cache.enable())\n"
            "print(jax.config.jax_compilation_cache_dir)\n"
            "jax.jit(lambda x: x * 3 + 1)(jnp.arange(5.0)).block_until_ready()\n")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [str(tmp_path), str(tmp_path)]
    assert any(n.endswith("-cache") for n in os.listdir(tmp_path))
