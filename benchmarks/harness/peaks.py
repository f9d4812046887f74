"""Published peaks per chip, keyed by ``device_kind`` as jax reports it.

Source: Google Cloud TPU documentation, "TPU v5e" system architecture:
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s
inter-chip interconnect. The benchmark keeps its own copy (the program
has ``plan/costs.py::DEVICE_PEAKS``): a later PR may change the
program's table, not the yardstick's. A device that is not here is an
error, never a default."""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add it "
            "to benchmarks/harness/peaks.py with its source"
        )
    return PEAKS[device_kind]
