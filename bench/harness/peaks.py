"""Published peaks of the chips this benchmark may run on, keyed by
`device_kind` as JAX reports it. A device that is not here is an error,
never a default."""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": per chip
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
