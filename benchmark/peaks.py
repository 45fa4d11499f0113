"""The table of peaks, kept with the benchmark so that no PR that claims a
gain can change the yardstick (a copy of the table of
``tpu9/benchsuite/physics.py``). What a step NEEDS of them — operations and
bytes from shapes — is each architecture's own arithmetic, in its family
file (``families/<f>.py``).

Peaks per chip, Google Cloud documentation ("TPU v5e": 197 TFLOP/s bf16,
16 GB HBM at 819 GB/s; "TPU v6e": 918 TFLOP/s, 32 GB at 1,640 GB/s). A device
that is not in the table is an error, not a default.
"""

from __future__ import annotations

# keyed on substrings of jax's Device.device_kind, lowercased
PEAKS = (
    ("v5 lite", {"chip": "tpu-v5e", "bf16_tflops": 197.0, "hbm_gbps": 819.0,
                 "hbm_bytes": 16e9}),
    ("v5e", {"chip": "tpu-v5e", "bf16_tflops": 197.0, "hbm_gbps": 819.0,
             "hbm_bytes": 16e9}),
    ("v6 lite", {"chip": "tpu-v6e", "bf16_tflops": 918.0, "hbm_gbps": 1640.0,
                 "hbm_bytes": 32e9}),
    ("v6e", {"chip": "tpu-v6e", "bf16_tflops": 918.0, "hbm_gbps": 1640.0,
             "hbm_bytes": 32e9}),
)

BF16, F32 = 2, 4


def chip_peaks(device_kind: str) -> dict:
    kind = (device_kind or "").lower()
    for needle, peaks in PEAKS:
        if needle in kind:
            return peaks
    raise KeyError(f"no peak figures for device kind {device_kind!r}: add "
                   "it to benchmark/peaks.py with its source")
