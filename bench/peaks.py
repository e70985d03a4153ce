"""Published peaks of the accelerators the benchmark runs on, keyed by
the ``device_kind`` JAX reports. A device that is not listed is an
error, never a default."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 394e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 394 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def roofline_share(flops: float, nbytes: float, seconds: float,
                   device_kind: str, *, flops_key: str = "bf16_flops"):
    """Least time the chip could take for the work (the larger of
    operations over peak and bytes over bandwidth), as a percentage of
    the measured time; None when nothing was measured."""
    if seconds <= 0 or (flops <= 0 and nbytes <= 0):
        return None
    p = peaks(device_kind)
    least = max(flops / p[flops_key], nbytes / p["hbm_bytes_per_s"])
    return 100.0 * least / seconds
