"""Hardware peak-FLOPs lookup for MFU derivation.

The model-side FLOPs estimate lives in ``models/config.py``
(``train_flops_per_step``); this module owns the hardware side — peak dense
bf16 matmul throughput per chip, keyed by a substring of ``device_kind``.
Sources: public TPU spec sheets (v5e: Google Cloud documentation, "TPU v5e",
197 TFLOP/s bf16; JAX reports that chip as ``"TPU v5 lite"``). ``bench.py``
and the telemetry hub both read THIS table so a benchmark and a live run can
never disagree about what "MFU 0.4" means. A TPU that is not in the table is
an error, not a default: an MFU against a guessed peak is not a measurement.
"""

from __future__ import annotations

from typing import Optional

PEAK_BF16_FLOPS = {
    "v4": 275e12,
    "v5e": 197e12,
    "v5 lite": 197e12,
    "v5p": 459e12,
    "v6e": 918e12,
}


def device_peak_flops() -> Optional[float]:
    """Peak bf16 FLOPs/sec of one local device, or None when the backend has
    no meaningful peak (CPU — MFU would be noise, not signal). Raises on a
    TPU whose ``device_kind`` the table does not know."""
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        return None
    kind = device.device_kind.lower()
    for key, flops in PEAK_BF16_FLOPS.items():
        if key in kind:
            return flops
    raise ValueError(
        f"no peak FLOP/s recorded for device_kind {device.device_kind!r}: add it to "
        "telemetry.flops.PEAK_BF16_FLOPS with its source"
    )
