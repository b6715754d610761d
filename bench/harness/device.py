"""The chip a run is on: the device check and the table of peaks."""
from __future__ import annotations

# Published peaks per chip, keyed by `device_kind` as JAX reports it.
# TPU v5e: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
# 819 GB/s HBM, 16 GB HBM per chip).
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def check(chips: int):
    """The devices of a run: the first `chips` TPU chips.  There is no
    fallback to the CPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform "
                     f"{devices[0].platform!r}); nothing was run")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips and JAX sees "
                     f"{len(devices)}")
    return devices[:chips]


def peaks(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {kind!r}; add it to "
                       "bench/harness/device.py with its source")
    return PEAKS[kind]


def describe(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip (0 where the backend does
    not report it)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
