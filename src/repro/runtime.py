"""Process-level set-up shared by the repo's entry points.

`enable_compile_cache` gives every entry point (``chip_smoke.py``, the
examples, ``benchmarks/run.py``) one persistent XLA compilation cache, so
a second process compiling the same program reads it back instead of
compiling again.  `tpu_chips_attached` tells a launcher that must stay
off JAX whether this host has TPU chips.

Importing this module imports nothing from JAX.
"""
from __future__ import annotations

import glob
import os
from pathlib import Path

# A fixed path inside the checkout: a cache whose directory moves
# between runs is never found again.
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

_GOOGLE_PCI_VENDOR = "0x1ae0"
# PCI device ids of TPU chips (v3, plc, v4, v5p, v5e, v6e, 7x), the same
# table JAX's own hardware probe uses.
_TPU_PCI_DEVICES = frozenset(
    {"0x0027", "0x0056", "0x005e", "0x0062", "0x0063", "0x006f", "0x0076"})


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    no other path is set here.  Otherwise the cache is the checkout's
    ``.jax_cache/``.  Call before the process compiles anything: JAX
    fixes the cache location at its first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def tpu_chips_attached(pci_devices: str = "/sys/bus/pci/devices") -> int:
    """Number of TPU chips on this host's PCI bus, read from sysfs
    without importing JAX (so the caller does not take the chips)."""
    n = 0
    for vendor_path in glob.glob(os.path.join(pci_devices, "*", "vendor")):
        dev_dir = os.path.dirname(vendor_path)
        try:
            with open(vendor_path) as f:
                vendor = f.read().strip()
            with open(os.path.join(dev_dir, "device")) as f:
                device = f.read().strip()
        except OSError:
            continue
        n += vendor == _GOOGLE_PCI_VENDOR and device in _TPU_PCI_DEVICES
    return n
