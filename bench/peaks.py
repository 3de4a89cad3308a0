"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A kind that is not listed is an error: a
share of a peak is never taken against a guess.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB HBM at 819 GB/s, 1,600 Gbit/s
of inter-chip interconnect per chip.  The chip publishes no float32
figure; shares of float32 work are taken against the bf16 peak, which
makes them lower bounds.
"""

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,       # bf16
        "hbm_bytes_per_s": 819e9,
        "ici_bytes_per_s": 1600e9 / 8,
        "hbm_bytes": 16 * 2**30,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/peaks.py "
                       f"with their source") from None
