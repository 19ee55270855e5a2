"""Pallas TPU kernel: one blocked pair-mask primitive for both geometric
adjacency tests.

The RGG Euclidean threshold (``pairdist``) and the RHG hyperbolic Eq. 9
test (``hypdist``) were two structurally identical kernels: one
VMEM-resident (bm x bn) tile of A-side x B-side pairs per grid step, a
per-kind tile test on the VPU, an int8 mask out.  They now share this
single ``pallas_call`` harness with a kind-specific *tile function* —
the kernel-level mirror of the engine's kind-tagged ``PairPlan``
(GEOM_TORUS / GEOM_HYP are just tiles of the same sweep).

Tile kinds:

``euclid`` — accumulate squared coordinate differences one axis at a
  time (d in {2, 3}; an MXU matmul would waste 125/128 of the systolic
  array, so the VPU broadcast-subtract-square is the roofline-correct
  form on TPU) and compare ``acc <= r^2`` inclusively in float32.

``hyp`` — the paper's §7.2.1 trig-free precompute: four broadcast FMAs
  ``cosθ·cosθ' + sinθ·sinθ' − coth·coth' + coshR·(1/sinh)(1/sinh')``
  and the sign test ``acc > 0``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import interpret_mode

TILES = ("euclid", "hyp")


def _euclid_tile(a_ref, b_ref, s_ref, out_ref, *, dim: int):
    # a_ref: (bm, dpad) f32, b_ref: (bn, dpad) f32, out: (bm, bn) int8
    acc = jnp.zeros((a_ref.shape[0], b_ref.shape[0]), jnp.float32)
    for d in range(dim):  # static tiny loop: d in {2, 3}
        diff = a_ref[:, d][:, None] - b_ref[:, d][None, :]
        acc = acc + diff * diff
    out_ref[...] = (acc <= s_ref[0, 0]).astype(jnp.int8)


def _hyp_tile(q_ref, c_ref, coshr_ref, out_ref):
    # q_ref: (bm, 8), c_ref: (bn, 8) — features in cols 0..3
    coshR = coshr_ref[0, 0]
    acc = q_ref[:, 0][:, None] * c_ref[:, 0][None, :]
    acc += q_ref[:, 1][:, None] * c_ref[:, 1][None, :]
    acc -= q_ref[:, 2][:, None] * c_ref[:, 2][None, :]
    acc += coshR * (q_ref[:, 3][:, None] * c_ref[:, 3][None, :])
    out_ref[...] = (acc > 0).astype(jnp.int8)


@functools.partial(
    jax.jit, static_argnames=("tile", "dim", "block_m", "block_n")
)
def pair_mask(
    a: jax.Array,
    b: jax.Array,
    scalar: jax.Array,
    *,
    tile: str,
    dim: int = 2,
    block_m: int = 128,
    block_n: int = 128,
) -> jax.Array:
    """int8 mask[M, N] of the tile test over all (a_i, b_j) pairs.

    a: (M, F), b: (N, F) — caller pads M, N to block multiples and F to
    the sublane-friendly width per kind.  ``scalar`` is the tile's
    threshold (r^2 for ``euclid``, cosh R for ``hyp``); ``dim`` is only
    read by ``euclid``.  Self-pairs are NOT excluded here (gid
    comparison happens outside).
    """
    if tile not in TILES:
        raise ValueError(f"unknown tile {tile!r}; know {TILES}")
    m, f = a.shape
    n = b.shape[0]
    assert m % block_m == 0 and n % block_n == 0, (m, n, block_m, block_n)
    grid = (m // block_m, n // block_n)
    if tile == "euclid":
        kernel = functools.partial(_euclid_tile, dim=dim)
        s = jnp.asarray(scalar, jnp.float32).reshape(1, 1)
    else:
        kernel = _hyp_tile
        s = jnp.asarray(scalar, a.dtype).reshape(1, 1)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, f), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, f), lambda i, j: (j, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int8),
        interpret=interpret_mode(),
    )(a, b, s)
