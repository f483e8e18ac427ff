"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports.  A device that is not here is an error, not
a default: a roofline share against a guessed peak means nothing."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": one chip
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit("benchmark: no published peaks for device kind %r"
                         % device_kind) from None
