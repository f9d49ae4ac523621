"""``repro.compile_cache``: where JAX's persistent compilation cache goes.

Each case runs in a fresh CPU process: JAX opens the cache once per process,
and this test process must keep its own configuration.
"""
import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent

SNIPPET = """
import sys
import jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
if sys.argv[1] == "compile":
    jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(4)).block_until_ready()
"""


def _run(action: str, **env_extra) -> list[str]:
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(
        JAX_PLATFORMS="cpu",
        PYTHONPATH=os.pathsep.join([str(REPO / "src"), env.get("PYTHONPATH", "")]),
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        **env_extra,
    )
    proc = subprocess.run(
        [sys.executable, "-c", SNIPPET, action], env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.split()


def test_cache_goes_where_the_environment_says(tmp_path):
    where = tmp_path / "jax-cache"
    returned, configured = _run("compile", JAX_COMPILATION_CACHE_DIR=str(where))
    assert returned == configured == str(where)
    assert any(where.iterdir()), "nothing was written to JAX_COMPILATION_CACHE_DIR"


def test_cache_defaults_to_the_fixed_repo_directory():
    returned, configured = _run("configure-only")
    assert returned == configured == str(REPO / ".jax_cache")
