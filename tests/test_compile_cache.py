"""Where the persistent compilation cache goes (repro.compile_cache)."""
import os
import pathlib
import random
import subprocess
import sys

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.compile_cache import REPO_CACHE_DIR, enable_compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def test_placed_cache_dir_is_left_to_jax(monkeypatch, tmp_path,
                                         restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_cache_dir_is_fixed_in_repo(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert REPO_CACHE_DIR == str(ROOT / ".jax_cache")
    assert enable_compile_cache() == REPO_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == REPO_CACHE_DIR


@pytest.mark.parametrize("placed", [True, False])
def test_cache_entries_land(tmp_path, placed):
    """A fresh process's compile is written to the placed directory, or
    to ``<repo>/.jax_cache`` when none is placed."""
    cache = tmp_path if placed else pathlib.Path(REPO_CACHE_DIR)
    before = set(cache.iterdir()) if cache.exists() else set()
    # a constant of its own gives this run's program a key of its own
    code = ("from repro.compile_cache import enable_compile_cache\n"
            "enable_compile_cache()\n"
            "import jax\n"
            f"jax.jit(lambda x: x * 2 + {random.randrange(1 << 24)})(1.0)"
            ".block_until_ready()\n")
    env = {**os.environ, "PYTHONPATH": str(ROOT / "src"),
           "JAX_PLATFORMS": "cpu",
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
           "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if placed:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=300)
    assert set(cache.iterdir()) - before
