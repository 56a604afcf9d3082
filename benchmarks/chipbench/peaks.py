"""Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.

The benchmark's own copy of ``deepspeed_tpu/utils/device.py: PEAKS`` (a later
PR may change the program, not the yardstick). Source: Google Cloud
documentation, "TPU v5e" system architecture page: 197 TFLOP/s bf16, 393 TOP/s
int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s chip-to-chip. A kind that is not
here is an error, never a default.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197.0e12, "int8_ops_per_s": 393.0e12,
                    "hbm_bytes_per_s": 819.0e9, "hbm_bytes": 16.0e9,
                    "ici_bits_per_s": 1600.0e9},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind {device_kind!r} "
                       f"in chipbench.peaks.PEAKS (has {sorted(PEAKS)})")
    return PEAKS[device_kind]
