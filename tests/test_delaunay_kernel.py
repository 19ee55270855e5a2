"""Batched device Delaunay kernel vs the scipy-Qhull oracle.

The kernel is the RDG emitter's production triangulator
(:func:`repro.kernels.delaunay.batched_delaunay`); Qhull survives only
as the test oracle here and in :mod:`repro.core.rdg`'s retained host
paths.  The contract under test:

* alive simplices of a row == the Delaunay triangulation of that row's
  points + its bounding super-simplex (super-free simplices match
  Qhull's exactly as sets of vertex-id frozensets);
* padded rows (count 0) stay inert;
* degenerate/cocircular inputs clear ``ok`` instead of emitting a wrong
  triangulation (the emitter then expands the halo);
* the Cramer circumsphere predicate is bit-identical between the
  kernel-side certificates, the host planner (`rdg.circumspheres`), and
  the engine's GEOM_CERT re-check (`engine._circumsphere_in_box`).
"""
import itertools

import numpy as np
import pytest
from scipy.spatial import Delaunay

from repro.core import rdg
from repro.kernels.delaunay import (batched_delaunay, cavity_capacity,
                                    group_size, simplex_capacity)


def _interior_sets(simp, alive, nb):
    """Super-free alive simplices as a set of vertex-id frozensets."""
    live = np.asarray(simp)[np.asarray(alive).astype(bool)]
    live = live[(live < nb).all(axis=1)]
    return {frozenset(map(int, s)) for s in live}


def _qhull_sets(pts):
    return {frozenset(map(int, s)) for s in Delaunay(pts).simplices}


def _rows(seed, B, nmax, dim):
    rng = np.random.default_rng(seed)
    counts = rng.integers(dim + 2, nmax + 1, size=B)
    pts = rng.random((B, nmax, dim))
    for i in range(B):
        pts[i, counts[i]:] = 0.0
    return pts, counts


# ------------------------------------------------------------- DT parity

@pytest.mark.parametrize("dim", [2, 3])
def test_batched_rows_match_qhull(dim):
    """Every row's super-free simplex set == Qhull on that row's points.

    (Random uniform rows: no exact cosphericality, so the floating
    Qhull triangulation is unique and comparable set-wise.)"""
    pts, counts = _rows(20 + dim, B=5, nmax=48 if dim == 2 else 32, dim=dim)
    simp, alive, ok = batched_delaunay(pts, counts, dim=dim)
    assert np.asarray(ok).all()
    for i in range(len(counts)):
        got = _interior_sets(simp[i], alive[i], counts[i])
        want = _qhull_sets(pts[i, : counts[i]])
        # the kernel drops super-incident simplices; Qhull has no super
        # point, so its hull-adjacent simplices may exceed `got` only by
        # ones whose circumsphere leaves the unit box (never certified)
        assert got <= want
        missing = want - got
        if missing:
            arr = np.array([pts[i, sorted(s)] for s in missing])
            _, rad = rdg.circumspheres(arr.reshape(-1, dim + 1, dim))
            assert (rad > 0.02).all()  # all near the hull, none interior


@pytest.mark.parametrize("dim", [2, 3])
def test_padded_rows_inert(dim):
    """count-0 rows stay ok and contribute nothing: only the bounding
    super-simplex stays alive, and every emitter-visible (super-free)
    simplex set is empty."""
    pts, counts = _rows(7, B=4, nmax=24, dim=dim)
    counts[1] = 0
    counts[3] = 0
    simp, alive, ok = batched_delaunay(pts, counts, dim=dim)
    assert np.asarray(ok).all()
    for i in (1, 3):
        assert _interior_sets(simp[i], alive[i], 0) == set()
        assert int(np.asarray(alive[i]).sum()) == 1  # the super simplex
    for i in (0, 2):  # real rows unaffected by their dead neighbors
        assert _interior_sets(simp[i], alive[i], counts[i]) == \
            _qhull_sets(pts[i, : counts[i]])


# ------------------------------------------------- degenerate inputs

def test_cocircular_square_fails_closed():
    """Four exactly-cocircular points: the in-sphere tie is undecidable
    in the abort-on-tie kernel, so the row must clear ``ok`` (the
    emitter's cue to expand the halo), never emit a wrong DT."""
    sq = np.array([[0.2, 0.2], [0.8, 0.2], [0.8, 0.8], [0.2, 0.8]])
    pts = sq[None, :, :]
    _, _, ok = batched_delaunay(pts, np.array([4]), dim=2)
    assert not np.asarray(ok).any()


def test_collinear_points_fail_closed():
    """A degenerate (zero-area) configuration cannot triangulate."""
    line = np.stack([np.linspace(0.1, 0.9, 5), np.full(5, 0.5)], axis=1)
    _, _, ok = batched_delaunay(line[None], np.array([5]), dim=2)
    assert not np.asarray(ok).any()


def test_coplanar_3d_emits_nothing():
    """All-coplanar 3d input: any super-free tetrahedron would be
    degenerate, so none may form — every alive simplex keeps a super
    vertex and the emitter-visible set stays empty (certification can
    then never accept a wrong simplex; the halo expands instead)."""
    rng = np.random.default_rng(0)
    flat = rng.random((8, 3))
    flat[:, 2] = 0.5
    simp, alive, _ = batched_delaunay(flat[None], np.array([8]), dim=3)
    assert _interior_sets(simp[0], alive[0], 8) == set()


# ------------------------------------- predicate bit-parity (3 sites)

@pytest.mark.parametrize("dim", [2, 3])
def test_circumsphere_predicate_bit_parity(dim):
    """kernel predicate == host planner == engine GEOM_CERT re-check,
    bit for bit: the communication-free invariant that lets the device
    re-certify host-planned simplices without disagreement."""
    import jax.numpy as jnp

    from repro.distrib import engine
    from repro.kernels.delaunay import circumsphere

    rng = np.random.default_rng(5 + dim)
    simp = rng.random((64, dim + 1, dim))
    c_host, r_host = rdg.circumspheres(simp)
    c_dev, r2_dev, nondeg = circumsphere(jnp.asarray(simp))
    assert np.asarray(nondeg).all()
    np.testing.assert_array_equal(c_host, np.asarray(c_dev))
    np.testing.assert_array_equal(r_host, np.sqrt(np.asarray(r2_dev)))

    lo, hi = np.zeros(dim), np.ones(dim)
    for s, c, r in zip(simp, c_host, r_host):
        want = bool(((c - r >= lo).all() & (c + r <= hi).all()))
        geom_a = np.zeros((dim + 1) * dim)
        geom_a[:] = s.ravel()
        geom_b = np.concatenate([lo, hi, np.ones((dim + 1) * dim - 2 * dim)])
        got = bool(np.asarray(engine._circumsphere_in_box(
            jnp.asarray(geom_a), jnp.asarray(geom_b), dim)))
        assert got == want


def test_degenerate_certificate_fails_containment():
    """det == 0 simplices get radius inf on the host and a cleared
    nondeg flag on device: both sides fail containment, forcing halo
    expansion rather than shipping an uncertifiable simplex."""
    import jax.numpy as jnp

    from repro.kernels.delaunay import circumsphere

    flat = np.array([[[0.1, 0.1], [0.5, 0.5], [0.9, 0.9]]])
    _, rad = rdg.circumspheres(flat)
    assert np.isinf(rad).all()
    _, _, nondeg = circumsphere(jnp.asarray(flat))
    assert not np.asarray(nondeg).any()


# --------------------------------------- emitter-level device-DT parity

@pytest.mark.parametrize("P", [1, 2, 8])
@pytest.mark.parametrize("dim,n", [(2, 512), (3, 128)], ids=["2d", "3d"])
def test_emitter_device_dt_matches_qhull_oracle(dim, n, P):
    """End-to-end: the device-DT plan's executed edge set at P in
    {1, 2, 8} == the P=1 Qhull host-loop union.  2d n=512 runs the
    batched-kernel rounds; 3d n=128 wraps the torus and exercises the
    Qhull-resume fallback, so both protocol paths are covered.

    The reference is the P=1 union because the device edge set is
    P-invariant (the chunk grid is P-independent) while the host union
    at P > 1 need not be: Qhull lacks exact predicates, so a
    near-cocircular quad can flip with the PE's local point set (at
    seed 29, 2d n=512, the P=8 union gains three such edges).  In 2d
    the count is pinned too: a Delaunay triangulation of n points on
    the torus has exactly 3n edges (Euler)."""
    from repro.distrib import runtime

    seed = 29
    plan = rdg.rdg_pair_plan(seed, n, P, dim)
    payload, valid, _ = runtime.run(plan, check=False)
    got = np.asarray(payload)[np.asarray(valid).astype(bool)].reshape(-1, 2)
    want = set(map(tuple, rdg.rdg_union(seed, n, 1, dim).tolist()))
    assert set(map(tuple, got.tolist())) == want and len(want) > 0
    if dim == 2:
        assert len(got) == 3 * n


def test_emitter_halo_expansion_on_failed_certification():
    """A chunk whose first device round fails certification expands and
    converges (the level-synchronous analog of the oracle's expansion
    loop); max_expand=0 turns the same instance into the convergence
    error."""
    st = rdg.RdgStructure(512, 2, 2, max_expand=8)
    # ring-2 start certifies in one round at this shape; shrink the
    # start to chunk+1 ring to force at least one in-protocol expansion
    st._init_regions = [set(c) | rdg._ring(c, 2) for c in st.chunk_cells]
    plan = st.emit(31)
    from repro.distrib import runtime
    payload, valid, _ = runtime.run(plan, check=False)
    got = set(map(tuple, np.asarray(payload)[
        np.asarray(valid).astype(bool)].reshape(-1, 2).tolist()))
    want = set(map(tuple, rdg.rdg_union(31, 512, 2, 2).tolist()))
    assert got == want

    tight = rdg.RdgStructure(512, 2, 2, max_expand=0)
    tight._init_regions = [set(c) | rdg._ring(c, 2) for c in tight.chunk_cells]
    with pytest.raises(RuntimeError, match="halo did not converge"):
        tight.emit(31)


def test_too_few_points_raises():
    with pytest.raises(ValueError, match="too few points"):
        rdg.rdg_pair_plan(0, 4, 1, 3)


# ----------------------------------------------------- reseed fast path

def test_rdg_reseed_equals_cold_field_by_field():
    """structure.emit is the plan's reseed_fn; reseeding to a new seed
    must equal the cold plan for that seed in every array field (the
    serve PlanCache contract), with no host re-triangulation beyond the
    device passes."""
    import dataclasses

    spec_plan = rdg.rdg_pair_plan(3, 256, 4, 2)
    reseeded = spec_plan.reseed_fn(9)
    cold = rdg.RdgStructure(256, 4, 2).emit(9)
    for f in dataclasses.fields(cold):
        if f.name == "reseed_fn":
            continue
        a, b = getattr(cold, f.name), getattr(reseeded, f.name)
        if isinstance(a, np.ndarray):
            np.testing.assert_array_equal(a, b, err_msg=f.name)
        else:
            assert a == b, f.name
