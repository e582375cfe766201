"""Published peaks of the chips the benchmark may run on, keyed by the
exact ``device_kind`` string JAX reports. A device that is not in the
table is an error, not a default: a share of an assumed peak is not a
measurement. Copied from ``gelly_streaming_tpu/utils/profiling.py``
(``_CHIP_PEAKS``), so that no later PR can move the yardstick."""

from __future__ import annotations

PEAKS = {
    # one TPU v5e chip: 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s
    # (Google Cloud documentation, "TPU v5e"); the kind string is what
    # chip_smoke.py printed on the chip in PR 21
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; add a "
            f"row with its source (known: {sorted(PEAKS)})"
        ) from None
