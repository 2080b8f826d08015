"""Where the persistent compilation cache lands (repro/compile_cache.py).

Each case runs in a fresh interpreter, because JAX reads
``JAX_COMPILATION_CACHE_DIR`` once, when it is imported.
"""
import os
import pathlib
import subprocess
import sys

from repro import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]

PROBE = """
import jax, jax.numpy as jnp
from repro import compile_cache
used = compile_cache.enable()
jax.jit(lambda x: x * 2 + 1)(jnp.arange(8)).block_until_ready()
print(used)
print(jax.config.jax_compilation_cache_dir)
"""


def _probe(env_dir, compile_min_secs="0"):
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV}
    env.update(JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=compile_min_secs,
               PYTHONPATH=os.pathsep.join(
                   [str(REPO / "src")]
                   + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                      if p]))
    if env_dir is not None:
        env[compile_cache.ENV] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", PROBE], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    return out.stdout.split()[-2:]


def test_env_dir_is_used_and_nothing_else(tmp_path):
    used, configured = _probe(tmp_path)
    assert used == configured == str(tmp_path)
    assert any(tmp_path.iterdir()), "no cache entry was written"


def test_default_is_fixed_dir_inside_checkout():
    # a huge compile-time floor keeps this probe from writing entries
    used, configured = _probe(None, compile_min_secs="1e9")
    assert used == configured == str(REPO / ".jax_cache")
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
