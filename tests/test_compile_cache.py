"""Where compiled programs land: ``JAX_COMPILATION_CACHE_DIR`` when it is
set, else the fixed ``<checkout>/.jax_cache`` (run on CPU, each case in
a fresh process — JAX fixes its cache directory at the first compile)."""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

from repro.compile_cache import CACHE_DIR, ENV_VAR

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# stands the default directory in for <checkout>/.jax_cache so the test
# never writes into the real checkout, then compiles one program
_PROBE = """
import sys
import jax, jax.numpy as jnp
import repro.compile_cache as cc
cc.CACHE_DIR = sys.argv[1]
print(cc.enable_compile_cache())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: x * 2 + 1)(jnp.arange(8)).block_until_ready()
"""


def _run(default_dir, env_dir=None) -> str:
    env = dict(os.environ)
    env.pop(ENV_VAR, None)
    if env_dir is not None:
        env[ENV_VAR] = str(env_dir)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", _PROBE, str(default_dir)],
        env=env, capture_output=True, text=True, timeout=300, check=True,
    )
    return out.stdout.strip().splitlines()[-1]


def _entries(path) -> list[str]:
    return sorted(os.listdir(path)) if os.path.isdir(path) else []


def test_default_dir_is_fixed_in_the_checkout():
    assert CACHE_DIR == os.path.join(ROOT, ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()


@pytest.mark.parametrize("env_set", [True, False])
def test_compiled_entries_land_in_one_place(tmp_path, env_set):
    default_dir = tmp_path / "checkout_cache"
    env_dir = tmp_path / "env_cache"
    used = _run(default_dir, env_dir if env_set else None)
    want, other = (env_dir, default_dir) if env_set else (default_dir, env_dir)
    assert used == str(want)
    assert any(e.endswith("-cache") for e in _entries(want))
    assert _entries(other) == []
