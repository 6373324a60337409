"""Published peaks of the devices the benchmark may run on, keyed by the
``device_kind`` JAX reports. A kind that is not here is an error, never a
default: a roofline share against a guessed peak means nothing."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB HBM2e at 819 GB/s, 1600 Gbit/s chip-to-chip interconnect.
    # A v5e reports itself as "TPU v5 lite".
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_s": 200e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"benchmarks: no peaks row for device_kind {device_kind!r}; "
            f"known kinds: {sorted(PEAKS)}. Add a row with its published "
            f"source before measuring on this device.") from None
