"""Launcher set-up: the compile cache, the per-chip peak table, and the
chip smoke script's refusal to run without a TPU."""
import os
import subprocess
import sys

import jax
import pytest

from repro.launch import mesh as meshlib

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture
def cache_dir_restored():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_defaults_to_checkout(monkeypatch, cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    meshlib.enable_compile_cache()
    got = jax.config.jax_compilation_cache_dir
    assert got == str(meshlib.CACHE_DIR)
    assert os.path.samefile(os.path.dirname(got), ROOT)
    assert os.path.basename(got) == ".jax_cache"


def test_compile_cache_env_var_wins(monkeypatch, cache_dir_restored):
    """Where the variable is set JAX reads it itself: nothing is set in
    code, so the cache lands there and nowhere else."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    before = jax.config.jax_compilation_cache_dir
    meshlib.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == before


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v9 imaginary"])
def test_chip_peaks_known_or_raise(kind):
    if kind in meshlib.CHIP_PEAKS:
        peaks = meshlib.chip_peaks(kind)
        assert peaks.bf16_flops > 0 and peaks.hbm_bytes_per_s > 0
    else:
        with pytest.raises(KeyError, match="no published peaks"):
            meshlib.chip_peaks(kind)


def test_chip_smoke_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr
