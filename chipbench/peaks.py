"""Published peaks of the chips the benchmark may run on, by `device_kind`.

Copied from `federated_pytorch_test_tpu/obs/roofline.py CHIP_PEAKS` so a
later PR to the program cannot move the yardstick. Source: Google Cloud
TPU documentation, the per-chip spec tables ("TPU v5e": 197 TFLOP/s
bf16, 16 GB HBM at 819 GB/s; 'TPU v5 lite' is what jax calls a v5e).
"""

from __future__ import annotations

# device_kind prefix -> (peak dense bf16 TFLOP/s, peak HBM GB/s)
CHIP_PEAKS = {
    "TPU v5 lite": (197.0, 819.0),
    "TPU v5e": (197.0, 819.0),
    "TPU v5p": (459.0, 2765.0),
    "TPU v4": (275.0, 1228.0),
    "TPU v6 lite": (918.0, 1640.0),
    "TPU v6e": (918.0, 1640.0),
}


def chip_peaks(device_kind: str):
    """The table row for `device_kind`; an unlisted kind raises — a share
    of a guessed peak is worse than none, and a new chip is one row."""
    for prefix, row in CHIP_PEAKS.items():
        if device_kind.startswith(prefix):
            return row
    raise ValueError(
        f"no published peaks on record for device kind {device_kind!r}; "
        f"add its row to chipbench/peaks.py (have {sorted(CHIP_PEAKS)})"
    )
