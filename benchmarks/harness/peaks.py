"""Published peaks, keyed by ``device_kind`` as JAX reports it.  The
benchmark's own table: later PRs may change the program's
(``paddle_tpu/parallel/roofline.CHIP_SPECS``) and may not change this
yardstick.  A device that is not here is an error, not a default."""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "chip": "TPU v5e",
        "bf16_flops_per_s": 197e12,     # 197 TFLOP/s bf16
        "int8_ops_per_s": 393e12,       # 393 TOP/s int8
        "hbm_bytes": 16e9,              # 16 GB HBM2e
        "hbm_bytes_per_s": 819e9,       # 819 GB/s
        "ici_bits_per_s": 1600e9,       # 1,600 Gbit/s chip to chip
        "source": "Google Cloud documentation, 'TPU v5e' system "
                  "architecture table (per-chip figures)",
    },
}


def peaks_for(device_kind: str) -> Dict[str, object]:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
