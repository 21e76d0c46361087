"""The one backend decision, and the checks that keep a run from hiding
the device: interpret mode on the CPU only, no silently shrunk mesh, no
CPU fallback in the chip smoke test, one fixed compile-cache path."""
import importlib.util
from argparse import Namespace
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.core.quantization import QuantConfig
from repro.core.winograd import WinogradSpec, make_matrices
from repro.kernels import backend
from repro.kernels.wino_transform import input_transform
from repro.launch import compile_cache, mesh, serve

ROOT = Path(__file__).resolve().parents[1]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_cpu_backend_resolves_to_interpret_mode():
    assert jax.default_backend() == "cpu"
    assert backend.interpret_mode() is True
    spec = WinogradSpec(m=4, r=3, base="legendre", quant=QuantConfig.off())
    mats = make_matrices(spec)
    tiles = jnp.ones((36, 8, 16), jnp.float32)
    text = input_transform.lower(tiles, mats.CinvT, mats.BPT,
                                 jnp.ones((36, 1))).as_text()
    assert "tpu_custom_call" not in text      # emulated, not Mosaic


@pytest.mark.parametrize("platform,expect", [("tpu", False), ("gpu", None)])
def test_other_backends_never_interpret(monkeypatch, platform, expect):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    if expect is None:
        with pytest.raises(RuntimeError, match="gpu"):
            backend.interpret_mode()
    else:
        assert backend.interpret_mode() is expect


def test_mesh_larger_than_visible_devices_raises():
    n = len(jax.devices())
    assert len(mesh.serving_devices(n)) == n
    with pytest.raises(ValueError, match="jax sees"):
        mesh.serving_devices(n + 1)
    args = Namespace(mesh_devices=n + 1, model_devices=2, ckpt_dir="unused")
    with pytest.raises(ValueError, match="serving mesh"):
        serve.make_served_engine(args, None, None)


def test_host_devices_is_a_cpu_only_knob(monkeypatch):
    mesh.require_host_devices(0)
    with pytest.raises(ValueError, match="jax sees"):
        mesh.require_host_devices(len(jax.devices()) + 1)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="tpu"):
        mesh.require_host_devices(2)


def test_chip_smoke_refuses_the_cpu(capsys):
    smoke = _chip_smoke()
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert "platform 'cpu'" in str(exc.value.code)
    assert capsys.readouterr().out == ""       # no result line


def test_compile_cache_dir(monkeypatch):
    prev = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == prev  # JAX reads it
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev_min)
