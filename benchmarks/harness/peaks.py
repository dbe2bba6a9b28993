"""Published peaks of the chips the benchmark may run on, keyed by the
`device_kind` jax reports. One table; a device that is not in it is an
error, never a default."""

from __future__ import annotations

# device_kind -> peak bf16 FLOP/s, HBM bytes/s. Source: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s, 16 GB HBM).
PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


class UnknownDeviceError(KeyError):
    pass


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDeviceError(
            f"device_kind {device_kind!r} is not in benchmarks/harness/peaks.py "
            f"(known: {sorted(PEAKS)}); add its published peaks with their source"
        ) from None
