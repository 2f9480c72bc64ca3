"""The device facts: interpret-mode choice, the peaks table, and the
placement of the persistent compilation cache."""
from __future__ import annotations

import jax
import pytest

from repro import device
from repro.kernels import common


@pytest.mark.parametrize("backend,want", [("cpu", True), ("tpu", False)])
def test_default_interpret_follows_backend(monkeypatch, backend, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert common.default_interpret() is want


@pytest.mark.parametrize("backend", ["gpu", "rocm"])
def test_default_interpret_refuses_other_backends(monkeypatch, backend):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    with pytest.raises(RuntimeError, match=backend):
        common.default_interpret()


def test_peaks_table_names_its_source():
    p = device.peaks(device.V5E)
    assert (p.flops, p.hbm_bw) == (197e12, 819e9)
    assert "TPU v5e" in p.source


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError, match="cpu"):
        device.peaks("cpu")


def test_compile_cache_defaults_to_checkout(monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        path = device.use_compile_cache(tmp_path)
        assert path == str(tmp_path.resolve() / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_follows_environment(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    was = jax.config.jax_compilation_cache_dir
    assert device.use_compile_cache(tmp_path) == str(tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == was
