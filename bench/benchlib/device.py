"""The device gate and the table of peaks.

A run needs the accelerator its cell asks for; there is no fallback to the
CPU. Peaks are keyed by ``device_kind`` as JAX reports it, and a kind that is
not in the table is an error.
"""
from __future__ import annotations

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


class NoDevice(SystemExit):
    """The run cannot measure here: exit non-zero, print no result."""


def gate(chips: int) -> dict:
    """The device record of the result line; raises ``NoDevice`` unless JAX
    finds at least ``chips`` TPU chips of a kind in ``PEAKS``."""
    import jax

    devs = jax.devices()
    d = devs[0]
    found = f"{len(devs)} x {d.platform} ({d.device_kind})"
    if d.platform != "tpu":
        raise NoDevice(f"bench: needs a TPU, JAX found {found}")
    if len(devs) < chips:
        raise NoDevice(f"bench: needs {chips} TPU chips, JAX found {found}")
    if d.device_kind not in PEAKS:
        raise NoDevice(f"bench: no peaks for device kind {d.device_kind!r}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def peaks(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise NoDevice(f"bench: no peaks for device kind {kind!r}") from None


def memory_peak_bytes(n_chips: int):
    """Peak bytes in use on the fullest of the first ``n_chips`` devices, or
    None where the backend keeps no such count."""
    import jax

    peaks_ = []
    for d in jax.devices()[:n_chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks_.append(int(stats["peak_bytes_in_use"]))
    return max(peaks_) if peaks_ else None
