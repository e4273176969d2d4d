"""Peak rates of one chip, keyed by JAX's ``device_kind``.

HBM and bf16 figures copied from ``accl_tpu/bench/harness.py`` (whose
tables are sound, PR 22's inventory); ICI added. Source: Google Cloud
documentation, "TPU v5e" (system architecture): 16 GB HBM2 at
819 GB/s, 197 TFLOP/s bf16, 393 TOP/s int8, 1,600 Gbit/s interchip
interconnect per chip. Whether the ICI figure counts one direction or
both is not stated there, so ``work.py`` counts only the bytes a chip
sends, which keeps a share of it at or under 100% either way. A device
kind that is not here is an error, never a default.
"""
from __future__ import annotations

_V5E = {
    "hbm_bytes_per_s": 819e9,
    "bf16_flops_per_s": 197e12,
    "int8_ops_per_s": 393e12,
    "ici_bytes_per_s": 1600e9 / 8,
    "hbm_bytes": 16e9,
    "source": "Google Cloud TPU v5e documentation (system architecture)",
}

PEAKS = {
    "TPU v5 lite": dict(_V5E, device_kind="TPU v5 lite"),
    "TPU v5e": dict(_V5E, device_kind="TPU v5e"),
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks on record for device kind "
                         f"{device_kind!r} (have {sorted(PEAKS)})") from None
