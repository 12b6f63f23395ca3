"""Published memory bandwidth of the chips this benchmark may run on, keyed
by the ``device_kind`` string JAX reports, beside ``peaks.py`` (which holds
the bf16 peak and is an accepted file).

Source: Google Cloud documentation, "TPU v5e" system architecture page, the
page ``peaks.py`` names (one chip: 16 GB HBM2e at 819 GB/s). It comes in
with the first metric that reads it: ``latent_experts_roofline_pct``.

A device that is not in the table is an error, never a default.
"""

from __future__ import annotations

# device_kind -> bytes per second between one chip and its HBM
HBM_BYTES_PER_S: dict[str, float] = {
    "TPU v5 lite": 819e9,
}


def hbm_bytes_per_s(device_kind: str) -> float:
    try:
        return HBM_BYTES_PER_S[device_kind]
    except KeyError:
        raise KeyError(
            f"no published memory bandwidth for device kind {device_kind!r}; add it to "
            "chipbench/peaks_memory.py with its source"
        ) from None
