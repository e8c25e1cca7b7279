"""Peak rates of each accelerator the benchmark may run on, keyed by the
`device_kind` JAX reports. A kind that is not in the table is an error:
a roofline or MFU share against a guessed peak means nothing.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB HBM at 819 GB/s per chip.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2**30,
        "source": "Google Cloud TPU v5e documentation",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
