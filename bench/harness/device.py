"""The chip the run is on: its description, its published peaks, and its
memory high-water mark."""

from __future__ import annotations

#: Published per-chip peaks, keyed by JAX's `device_kind`. Source: Google
#: Cloud documentation, "TPU v5e" (system architecture page): 197 TFLOP/s
#: bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud TPU documentation, TPU v5e"},
}


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def describe(chips: int, *, require: bool = True) -> dict:
    """{"platform", "kind", "count"} of the devices JAX sees; raises
    `NoDevice` when `require` and they are not `chips` TPUs."""
    import jax

    devices = jax.devices()
    d = devices[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}
    if require:
        if d.platform != "tpu":
            raise NoDevice(f"JAX found no TPU (platform {d.platform!r})")
        if len(devices) < chips:
            raise NoDevice(f"the cell asks for {chips} chips; JAX sees "
                           f"{len(devices)}")
    return info


def peaks(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}; add "
                       "it to bench/harness/device.py with its source")
    return PEAKS[kind]


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the first `chips` devices (0
    where the backend keeps no statistics)."""
    import jax

    best = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        best = max(best, int(stats.get("peak_bytes_in_use", 0)))
    return best
