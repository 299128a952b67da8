"""Published peaks of the chips the benchmark runs on, keyed by JAX's
`device_kind`.  A device that is not in the table is an error: a share of
a peak is never computed against a guess."""
from __future__ import annotations

SOURCE = "Google Cloud documentation, \"TPU v5e\""

PEAKS = {
    # one TPU v5e chip
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
    },
}


def peak(device_kind: str, what: str) -> float:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (known: {sorted(PEAKS)}; source: "
                       f"{SOURCE})")
    return PEAKS[device_kind][what]
