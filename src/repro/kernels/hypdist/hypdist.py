"""Trig-free hyperbolic adjacency (paper §7.2.1, Eq. 9) — the ``hyp``
tile of the unified pair-mask kernel.

After the per-vertex precompute [cos θ, sin θ, coth r, 1/sinh r] the
adjacency test  dist_H(p, q) < R  becomes the sign of a 4-term fused
inner product — exactly the paper's Vc-vectorized check.  The tile math
lives in :mod:`repro.kernels.pairmask.pairmask`; this module is the
RHG-facing facade kept for its established import path and signature.
"""
from __future__ import annotations

import jax

from ..pairmask.pairmask import pair_mask


def hypdist_mask(
    q: jax.Array,
    c: jax.Array,
    cosh_r: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 128,
) -> jax.Array:
    """int8 mask[M, N]: 1 where dist_H(q_i, c_j) < R (Eq. 9 form).

    q: (M, 8), c: (N, 8) feature blocks (padded); cosh_r: scalar cosh(R).
    Self-pairs are NOT excluded here (gid comparison happens outside).
    """
    return pair_mask(q, c, cosh_r, tile="hyp",
                     block_m=block_m, block_n=block_n)
