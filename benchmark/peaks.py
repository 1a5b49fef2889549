"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" system architecture page:
197 TFLOP/s in bf16, 16 GB of HBM at 819 GB/s per chip. Since PR 30 this is
the repository's one table of peaks (the two it was copied from went with
the scripts that held them). A kind that is not here is an error, never a
default.
"""

PEAKS = {
    # the runtime's name for a v5e chip
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "memory_bytes": 16e9},
    "TPU v5e": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                "memory_bytes": 16e9},
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device kind {device_kind!r}: add a row "
            f"to benchmark/peaks.py with its source") from None
