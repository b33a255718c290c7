"""Published peaks of one chip, keyed by ``device_kind`` as jax reports it.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(197 TFLOP/s bf16, 819 GB/s HBM, 16 GB HBM per chip).  A device that is not
in the table is an error, never a default: a share of an unknown peak means
nothing.  There is deliberately no CPU entry.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device kind {device_kind!r}; add it to "
            f"chipbench/peaks.py with its source") from None


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take for ``flops`` operations over
    ``nbytes`` bytes of HBM traffic: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
