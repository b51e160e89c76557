"""Published peaks of each accelerator the benchmark may run on, keyed by
the ``device_kind`` that JAX reports.

A device that is not in this table is an error, never a default: a share
of a peak read against the wrong chip's peak is a wrong number.
"""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": per chip 197 TFLOP/s bf16,
    # 393 TOP/s int8, 16 GB HBM at 819 GB/s.
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "ops_int8": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peak table of ``device_kind``; raises KeyError for any other."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
