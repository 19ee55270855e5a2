"""Blocked all-pairs distance threshold (RGG edges) — the ``euclid``
tile of the unified pair-mask kernel.

TPU adaptation of the paper's GPGPU edge kernel (§5.3): one thread block
per cell-pair on the GPU becomes one VMEM-resident (bm x bn) tile per
grid step.  The tile math (and why it runs on the VPU, not the MXU)
lives in :mod:`repro.kernels.pairmask.pairmask`; this module is the
RGG-facing facade kept for its established import path and signature.
"""
from __future__ import annotations

import jax

from ..pairmask.pairmask import pair_mask


def pairdist_mask(
    a: jax.Array,
    b: jax.Array,
    r2: jax.Array,
    *,
    dim: int,
    block_m: int = 128,
    block_n: int = 128,
) -> jax.Array:
    """int8 mask[M, N], 1 where ||a_i - b_j||^2 <= r2.

    a: (M, dpad) f32, b: (N, dpad) f32 — caller pads M, N to block
    multiples and dpad to the sublane-friendly width; only the first
    `dim` coordinates are used.
    """
    return pair_mask(a, b, r2, tile="euclid", dim=dim,
                     block_m=block_m, block_n=block_n)
