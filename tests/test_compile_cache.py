"""Where ``enable_compile_cache`` puts JAX's persistent compilation cache.

Each case runs a fresh CPU process (the cache directory is fixed once a
process has compiled) that enables the cache, compiles one program, and
reports back; the default directory is redirected into ``tmp_path`` so the
test writes nothing into the checkout.
"""
import os
import pathlib
import subprocess
import sys

import pytest

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"

_PROGRAM = """
import pathlib, sys
import jax, jax.numpy as jnp
import repro.launch.compile_cache as cc
cc.DEFAULT_DIR = pathlib.Path(sys.argv[1])
print(cc.enable_compile_cache())
jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(4)).block_until_ready()
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_lands_in_one_place(tmp_path, from_env):
    env_dir, default_dir = tmp_path / "from_env", tmp_path / "default"
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(SRC),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _PROGRAM, str(default_dir)],
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    want, other = (env_dir, default_dir) if from_env else (default_dir, env_dir)
    assert out.stdout.split() == [str(want)]
    assert any(want.iterdir()), "no compiled program was cached"
    assert not other.exists()
