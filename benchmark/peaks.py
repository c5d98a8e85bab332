"""The benchmark's own table of peaks, keyed by ``device_kind`` as JAX
reports it.  A device that is not listed is an error, never a default.

Copied from ``deep_vision_tpu/obs/mfu.py`` (bf16 column) so that no program
PR can move a peak; the bandwidth and memory columns are added here.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e: 197 TFLOP/s bf16, "
                  "819 GB/s, 16 GB HBM per chip",
    },
}


def lookup(platform: str, device_kind: str) -> dict:
    if platform != "tpu" or device_kind not in PEAKS:
        raise LookupError(
            f"no peaks on record for platform {platform!r}, device kind "
            f"{device_kind!r} (have {sorted(PEAKS)}): the benchmark measures "
            f"on a listed chip only")
    return PEAKS[device_kind]
