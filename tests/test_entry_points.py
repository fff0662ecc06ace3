"""The entry points' device plumbing: the persistent compile cache's
location, and ``chip_smoke.py`` refusing to run without a TPU."""
import os
import subprocess
import sys

import jax
import pytest

from conftest import ROOT


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_uses_env_dir(monkeypatch, restore_cache_dir, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, the cache lives there and the
    helper sets no directory of its own."""
    from repro.launch.compile_cache import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_dir_in_checkout(monkeypatch, restore_cache_dir):
    """Without the variable, the cache is the same directory inside the
    checkout on every call."""
    from repro.launch.compile_cache import enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = enable_compile_cache()
    assert first == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    assert enable_compile_cache() == first


def test_chip_smoke_fails_without_tpu():
    """On the CPU backend the smoke script exits non-zero before any phase
    and prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120, env=env,
                       cwd=ROOT)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr
