"""Published peaks of the chips this benchmark may run on, keyed by the
``device_kind`` string JAX reports.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(one chip: 197 TFLOP/s bf16; its 393 TOP/s int8, 16 GB HBM2e at 819 GB/s
and 1,600 Gbit/s interconnect come in with the first metric that reads
them). Copied from ``fedrec_tpu/obs/perf.py: CHIP_PEAKS`` at commit
4ba1c0d, keyed here by the exact kind string, not by a fragment.

A device that is not in the table is an error, never a default: every
share of a peak would silently be computed against the wrong chip.
"""

from __future__ import annotations

# device_kind -> peak numbers of ONE chip
CHIP_PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
    },
}


def chip_peaks(device_kind: str) -> dict[str, float]:
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add it to "
            "chipbench/peaks.py with its source"
        ) from None
