"""Entry-point set-up: the persistent compile cache's location rule and
the TPU-host probe the multi-host launcher runs before any child."""
from pathlib import Path

import jax
import pytest

from repro import runtime

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_leaves_the_choice_to_the_env_var(
        monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert runtime.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_a_fixed_ignored_checkout_dir(
        monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = runtime.enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def _pci(root, name, vendor, device):
    d = root / name
    d.mkdir()
    (d / "vendor").write_text(vendor + "\n")
    (d / "device").write_text(device + "\n")


def test_tpu_chips_counts_google_tpu_devices_only(tmp_path):
    assert runtime.tpu_chips_attached(str(tmp_path)) == 0
    _pci(tmp_path, "0000:00:04.0", "0x1ae0", "0x0063")  # v5e chip
    _pci(tmp_path, "0000:00:05.0", "0x1ae0", "0x0063")  # v5e chip
    _pci(tmp_path, "0000:00:06.0", "0x1ae0", "0x0042")  # Google NIC
    _pci(tmp_path, "0000:00:07.0", "0x8086", "0x0063")  # other vendor
    assert runtime.tpu_chips_attached(str(tmp_path)) == 2


def test_compile_count_reads_the_jit_cache():
    f = jax.jit(lambda x: x + 1)
    assert runtime.compile_count(f) == 0
    f(jax.numpy.ones(3))
    f(jax.numpy.zeros(3))
    assert runtime.compile_count(f) == 1
    f(jax.numpy.ones(4))
    assert runtime.compile_count(f) == 2
    assert runtime.compile_count(lambda x: x) is None
