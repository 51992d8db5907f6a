"""The chip entry points off the chip: where the persistent compilation cache
goes, and that ``chip_smoke.py`` refuses a host without a TPU."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

jax = pytest.importorskip("jax")

from repro.launch import compile_cache  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_config():
    """Restores JAX's cache directory after a test sets it."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize("env", [None, "elsewhere"])
def test_use_compile_cache(monkeypatch, tmp_path, cache_dir_config, env):
    sentinel = str(tmp_path / "untouched")
    jax.config.update("jax_compilation_cache_dir", sentinel)
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert compile_cache.use_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    else:
        # JAX reads the variable itself; the program sets no other directory
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / env))
        assert compile_cache.use_compile_cache() == str(tmp_path / env)
        assert jax.config.jax_compilation_cache_dir == sentinel


def test_chip_smoke_refuses_without_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    for line in proc.stdout.splitlines():     # no result line
        assert "ok" not in json.loads(line)
