"""Published per-chip peaks, keyed by ``device_kind`` as JAX reports it.

Source: Google Cloud TPU documentation, "TPU v5e" system architecture page:
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s. (Copied from the
program's ``veomni_tpu/utils/device.py::_PEAKS`` so that a later PR cannot
move the yardstick.) A device that is not listed is an error, never a
default: the benchmark then prints no result.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
    "TPU v5e": {"bf16_flops": 197e12, "int8_ops": 393e12,
                "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"device_kind {device_kind!r} is not in benchmark/peaks.py; add it "
            "with its published source before measuring on it") from None
