"""The persistent compilation cache lands in exactly one directory.

Each case runs in a child process, so the cache is never turned on in
the test process itself.
"""

import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent

_SNIPPET = """
import pathlib, sys
import jax, jax.numpy as jnp
from repro.launch import compile_cache
compile_cache.CHECKOUT_CACHE = pathlib.Path(sys.argv[1])
print(compile_cache.enable_compile_cache())
jax.jit(lambda x: x * 2 + 1)(jnp.arange(8)).block_until_ready()
"""


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "checkout"])
def test_compiled_programs_land_in_one_directory(tmp_path, from_env):
    env_dir, checkout_dir = tmp_path / "env", tmp_path / "checkout"
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _SNIPPET, str(checkout_dir)],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    used, unused = ((env_dir, checkout_dir) if from_env
                    else (checkout_dir, env_dir))
    assert out.stdout.strip() == str(used)
    assert any(used.iterdir()), "no compiled program was cached"
    assert not unused.exists()


def test_checkout_cache_is_a_fixed_path_in_the_checkout():
    from repro.launch import compile_cache
    assert compile_cache.CHECKOUT_CACHE == REPO / ".jax_cache"
