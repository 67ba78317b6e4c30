"""Where the persistent compilation cache goes.

``benchmarks/_env.use_compile_cache`` is what entry points call at
start-up: ``$JAX_COMPILATION_CACHE_DIR`` where set, else ``.jax_cache/``
at the checkout root.  Each case runs in a fresh interpreter, so the
cache it turns on never reaches the compiles of other tests.
"""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = """
import sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
from benchmarks._env import bench_env, use_compile_cache
print("RETURNED", use_compile_cache())
if len(sys.argv) > 2:
    jax.jit(lambda x: jnp.sin(x) * 2 + 1)(jnp.ones(8)).block_until_ready()
print("CONFIG", jax.config.jax_compilation_cache_dir)
env = bench_env()
print("STAMPED", env["compile_cache"])
print("WARM", env["compile_cache_entries_at_start"] > 0)
"""


def _run(env: dict, *extra: str) -> dict:
    env = {**env, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "-c", _CHILD, ROOT, *extra],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    return dict(line.split(" ", 1) for line in out.stdout.splitlines())


def test_cache_dir_from_environment_only(tmp_path):
    """With the variable set, the helper sets nothing and the compiled
    program lands in that directory; bench records stamp the directory
    and whether it was warm when the process started."""
    cache = str(tmp_path / "cache")
    env = {**os.environ, "JAX_COMPILATION_CACHE_DIR": cache,
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
           "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0"}
    got = _run(env, "compile")
    assert got == {"RETURNED": cache, "CONFIG": cache, "STAMPED": cache,
                   "WARM": "False"}
    assert os.listdir(cache), "no cache entry was written"
    assert _run(env)["WARM"] == "True"


def test_cache_dir_defaults_to_checkout():
    """Unset, the cache is the fixed ``.jax_cache/`` of the checkout."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    want = os.path.join(ROOT, ".jax_cache")
    got = _run(env)
    assert (got["RETURNED"], got["CONFIG"], got["STAMPED"]) == (want,) * 3
