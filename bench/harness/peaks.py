"""Published peaks per chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 819 GB/s HBM bandwidth, 16 GB HBM per chip. A device
kind that is not here is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} (known: {sorted(PEAKS)})"
        ) from None


def roofline_share(flops: float, nbytes: float, seconds: float, device_kind: str) -> float | None:
    """The least time the chip could take for ``flops`` and ``nbytes``
    over the measured ``seconds``, in percent; ``None`` with nothing to
    divide by."""
    if seconds <= 0 or (flops <= 0 and nbytes <= 0):
        return None
    p = peaks(device_kind)
    least = max(flops / p["flops_per_s"], nbytes / p["hbm_bytes_per_s"])
    return 100.0 * least / seconds
