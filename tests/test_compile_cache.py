"""Where JAX's persistent compilation cache goes
(``repro.launch.serve.use_compile_cache``)."""

import os
import pathlib
import subprocess
import sys

import jax

from repro.launch import serve

ROOT = pathlib.Path(__file__).resolve().parent.parent


def test_fixed_dir_in_checkout_without_env(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        path = serve.use_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_env_dir_is_left_to_jax(tmp_path):
    """With the variable set nothing is set in code, and the entries of
    a compile land in that directory."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch import serve\n"
        f"assert serve.use_compile_cache() == {str(tmp_path)!r}\n"
        "jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()\n")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0",
               JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=120)
    assert any(tmp_path.iterdir())
