"""Published peaks of one chip, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s of chip-to-chip
interconnect. jax reports a v5e chip as ``TPU v5 lite``. A device that is
not in the table is an error, not a default: a share of a guessed peak is
a wrong number.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9, "ici_bits_per_s": 1600e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks on record for device_kind {device_kind!r}: add it "
            f"to perfbench/harness/peaks.py with its source") from None
