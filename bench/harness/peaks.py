"""Published per-chip peaks, keyed by ``jax.Device.device_kind``
(copied from the program's ``launch/roofline.py``, so that a change
there cannot move the benchmark's rooflines).

TPU v5e, which JAX names "TPU v5 lite": Google Cloud documentation,
"TPU v5e" -- 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at
819 GB/s.  No integer peak of the vector unit is published, so the
64-bit integer hashing and sorting this generator runs has no compute
roofline here; its rooflines are bounded by HBM bytes alone."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
