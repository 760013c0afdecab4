"""The chip entry points off the chip: where the compile cache goes, and
``chip_smoke.py`` refusing to run (and to report a result) without a TPU."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import jax

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_leaves_env_dir_to_jax(monkeypatch, tmp_path,
                                              cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_repo_dir(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(ROOT / ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert compile_cache.enable_compile_cache() == want
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()


def _run(args, env_extra=None, cwd=ROOT, timeout=120):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")}
    env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT / "src")})
    env.update(env_extra or {})
    return subprocess.run([sys.executable] + args, capture_output=True,
                          text=True, timeout=timeout, cwd=cwd, env=env)


def test_compile_cache_entries_land_in_env_dir(tmp_path):
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "enable_compile_cache()\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: x * 3 + 1)(jnp.ones(5)).block_until_ready()\n")
    out = _run(["-c", code], {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert out.returncode == 0, out.stderr[-2000:]
    assert any(tmp_path.iterdir()), "no cache entry written"


def test_chip_smoke_refuses_cpu():
    out = _run([str(ROOT / "chip_smoke.py")])
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


def test_chip_smoke_refuses_outside_checkout(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    out = _run([str(tmp_path / "chip_smoke.py")], cwd=tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
