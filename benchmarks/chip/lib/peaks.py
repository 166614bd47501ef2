"""Published peaks of the chips the benchmark may run on, keyed by the
`device_kind` the sandbox reports. A kind that is not here is an error."""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 16 GB HBM at 819 GB/s.
    "TPU v5 lite": {
        "source": "cloud.google.com/tpu/docs/v5e (System architecture, TPU v5e)",
        "bytes_per_s": 819e9,
        # The same page: 197 TFLOP/s in bfloat16, its only published
        # floating-point peak (a float32 `highest` matmul is several bfloat16
        # passes and has none of its own). For a `floor` stated in flops.
        "bf16_flops_per_s": 197e12,
    },
}


def peaks_of(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}: add it to "
            "benchmarks/chip/lib/peaks.py with its source"
        ) from None
