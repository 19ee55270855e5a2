"""Dispatch facade for the batched device-side Delaunay triangulation.

:func:`batched_delaunay` is what the RDG plan emitter calls once per
halo round: every pending chunk's padded point row triangulates in one
device batch.  The triangulator is the jitted, vmapped XLA program of
:mod:`.ref` on every backend.  It is float64 so that its in-sphere test
is the very Cramer predicate the certificates use; Mosaic has no f64,
so a Pallas version would need a different (f32) predicate.

Capacities are emitter-derived and static per (padded size, dim)
bucket, so recompiles stay bounded across halo rounds:

* ``simplex_capacity(N, dim)`` — slot budget.  2d retriangulation is
  Euler-exact (+2 simplices per insertion, killed slots reused), so
  ``2N + O(1)`` suffices; 3d cavity retriangulation can leak slots
  (fewer new simplices than killed), so the budget carries the
  expected ~6.8N complexity (measured high-water ~6.5N on uniform
  rows) with slack.
* ``cavity_capacity(dim)`` — max simplices deleted by one insertion;
  overflow clears the row's ``ok`` flag, and the emitter expands the
  halo and retries (a different point set reshuffles insertion order).
"""
from __future__ import annotations

import jax.numpy as jnp

from .ref import delaunay_ref


def simplex_capacity(n: int, dim: int) -> int:
    return 2 * n + 16 if dim == 2 else 8 * n + 64


def cavity_capacity(dim: int) -> int:
    """Max simplices one insertion may delete.  Sized from measured
    high-water marks on uniform rows (2d ~10-15, 3d ~40-60) with slack;
    the cavity-derived compaction widths (union cavity = 3*CAV, boundary
    budget ~ (d-1)*CAV) dominate the per-trip sort/einsum cost, so the
    budget stays as tight as safety allows — at the production 2d shape
    CAV 64 -> 32 alone is a ~30% kernel cut.  Overflow is never wrong:
    it clears the row's ``ok`` and the emitter expands the halo."""
    return 32 if dim == 2 else 96


def group_size(dim: int) -> int:
    """Insertion-group width per loop trip.  Measured at the production
    row shapes ([16, 1024] 2d, [8, 1280] 3d): the group-quadratic
    acceptance scans grow faster than the per-trip fixed costs shrink,
    so the narrow group wins in both dims (G=8/16/24 cost 1.4x/2.4x/5x
    the G=4 wall time in 2d)."""
    return 4


def batched_delaunay(points, counts, *, dim: int):
    """Triangulate ``B`` padded point rows in one dispatch.

    points: [B, N, d] float64, counts: [B] int.  Returns
    ``(simp [B, S, d+1] int32, alive [B, S] bool, ok [B] bool)``:
    alive slots triangulate each row's points plus its super-simplex
    (vertex ids >= N); ``ok=False`` rows must be rebuilt with a larger
    halo.  Padding rows (count 0) are inert and cost no loop trips.
    """
    pts = jnp.asarray(points, jnp.float64)
    cnt = jnp.asarray(counts, jnp.int32)
    B, N, d = pts.shape
    if d != dim:
        raise ValueError(f"points are {d}-dimensional, expected {dim}")
    S = simplex_capacity(N, dim)
    CAV = cavity_capacity(dim)
    return delaunay_ref(pts, cnt, dim=dim, num_simplices=S, cavity=CAV,
                        group=group_size(dim))
