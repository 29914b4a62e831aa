"""Where the entry points put JAX's persistent compilation cache."""

import os
import subprocess
import sys
from pathlib import Path

import jax

from repro import compile_cache

CHECKOUT = Path(__file__).resolve().parents[1]


def test_default_cache_is_fixed_dir_in_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    assert compile_cache.cache_dir() == CHECKOUT / ".jax_cache"
    assert compile_cache.cache_dir() == compile_cache.cache_dir()


def test_env_var_wins_and_nothing_is_set_in_code(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.cache_dir() is None
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_importing_repro_sets_no_cache():
    env = {k: v for k, v in os.environ.items() if k != compile_cache.ENV}
    env.update(PYTHONPATH=str(CHECKOUT / "src"), JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c",
         "import repro, repro.core.frontier, repro.infer.service, jax;"
         "print(jax.config.jax_compilation_cache_dir)"],
        capture_output=True, text=True, check=True, env=env)
    assert out.stdout.strip() == "None"
