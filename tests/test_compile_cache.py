"""Where the persistent compilation cache goes (each case in a fresh
process: the cache directory is fixed at JAX's first compile)."""
import os
import subprocess
import sys

from repro.launch.compile_cache import DEFAULT_DIR

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SNIPPET = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
where = enable_compile_cache()
if {compile}:
    jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
print(where)
print(jax.config.jax_compilation_cache_dir)
"""


def _run(env_dir, compile_):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH", ""))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", SNIPPET.format(compile=compile_)],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()


def test_env_dir_is_left_to_jax_and_receives_the_entries(tmp_path):
    where, configured = _run(tmp_path / "cache", True)
    assert where == configured == str(tmp_path / "cache")
    assert any((tmp_path / "cache").iterdir())


def test_unset_env_uses_the_checkout_dir():
    where, configured = _run(None, False)
    assert where == configured == str(DEFAULT_DIR)
    assert (DEFAULT_DIR.parent / "chip_smoke.py").exists()
