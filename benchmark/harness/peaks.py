"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.

The benchmark's OWN copy (the program has one in starway_tpu/utils/chip.py):
a later PR may change the program, not the yardstick.

Source: Google Cloud documentation, "TPU v5e" system architecture page:
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s of
chip-to-chip interconnect per chip (4 ICI ports; about 45 GB/s one way per
link by that figure, not stated per link by the source).
"""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 200e9,
    },
}


def peaks(kind: str) -> dict:
    """The peaks of ``kind``; a device that is not in the table is an
    error, not a default."""
    try:
        return PEAKS[kind]
    except KeyError:
        raise SystemExit(
            f"benchmark: no published peaks for device_kind {kind!r}; add "
            f"them to benchmark/harness/peaks.py with their source") from None
