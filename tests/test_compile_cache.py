"""The entry points' persistent compilation cache lands where it should:
``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``.

Each case runs in a child process so the cache setting never leaks into
this test process."""
import os
import pathlib
import subprocess
import sys

from repro import compile_cache

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"

_SCRIPT = """
import jax, jax.numpy as jnp
from repro import compile_cache
print(compile_cache.enable())
print(jax.config.jax_compilation_cache_dir)
if {compile}:
    jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)).block_until_ready()
"""


def _child(env_dir, compile_):
    env = dict(os.environ, PYTHONPATH=str(SRC), JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop(compile_cache.ENV, None)
    if env_dir is not None:
        env[compile_cache.ENV] = str(env_dir)
    out = subprocess.run([sys.executable, "-c",
                          _SCRIPT.format(compile=compile_)],
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()


def test_cache_follows_env_var(tmp_path):
    helper, config = _child(tmp_path, compile_=True)
    assert helper == config == str(tmp_path)
    assert any(tmp_path.iterdir()), "no compiled program was cached"


def test_cache_defaults_to_fixed_repo_path():
    helper, config = _child(None, compile_=False)
    want = str(pathlib.Path(__file__).resolve().parents[1] / ".jax_cache")
    assert helper == config == want == str(compile_cache.REPO_CACHE)
