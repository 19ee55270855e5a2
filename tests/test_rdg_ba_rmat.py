"""RDG halo protocol vs periodic-DT oracle, BA chain resolution vs the
sequential Batagelj-Brandes fill, R-MAT distribution sanity."""
import itertools

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import ba, rdg, rgg, rmat
from repro.core.prng import device_key


def _points_of(seed, n, P, dim):
    # same grid rdg_pe defaults to: the instance is a function of the
    # chunk grid, which is sized by default_chunk_P, not by P
    grid = rdg.rdg_grid(n, rdg.default_chunk_P(P, dim), dim)
    counter = rgg.CellCounter(seed, grid, n)
    cells = [tuple(c) for c in np.ndindex(*([grid.g] * dim))]
    pos, counts, offsets, _ = rgg.points_for_cells(seed, grid, counter, cells)
    pts = np.zeros((n, dim))
    for i, c in enumerate(cells):
        pts[offsets[i]: offsets[i] + counts[i]] = pos[i][: counts[i]]
    return pts


@pytest.mark.parametrize("n,P,dim", [(200, 1, 2), (300, 4, 2), (400, 9, 2), (200, 1, 3), (220, 8, 3)])
def test_rdg_union_matches_periodic_oracle(n, P, dim):
    """Exact equality up to Delaunay float non-uniqueness: Qhull lacks
    exact predicates (unlike the paper's CGAL), so near-cospherical quads
    may flip between the local and global triangulations.  We require the
    symmetric difference to be tiny (<= 0.3% of edges)."""
    seed = 101 + n + P
    pts = _points_of(seed, n, P, dim)
    brute = {tuple(x) for x in rdg.rdg_brute_edges(pts, dim)}
    union = {tuple(x) for x in rdg.rdg_union(seed, n, P, dim)}
    sym = brute ^ union
    assert len(sym) <= max(2, int(0.003 * len(brute))), (len(sym), len(brute))


def test_rdg_exact_match_typical_case():
    seed, n, P, dim = 318, 300, 4, 2
    pts = _points_of(seed, n, P, dim)
    brute = {tuple(x) for x in rdg.rdg_brute_edges(pts, dim)}
    union = {tuple(x) for x in rdg.rdg_union(seed, n, P, dim)}
    assert brute == union


def test_rdg_every_vertex_covered_and_degree_sane():
    seed, n, P, dim = 7, 400, 4, 2
    e = rdg.rdg_union(seed, n, P, dim)
    deg = np.bincount(e.ravel(), minlength=n)
    assert (deg >= 2).all()          # torus DT: no boundary, min degree >= 2
    assert abs(deg.mean() - 6.0) < 0.3  # Euler: avg degree -> 6 on the torus


def test_rdg_halo_rarely_expands():
    seed, n, P = 9, 500, 4
    expansions = [rdg.rdg_pe(seed, n, P, pe, 2)[2] for pe in range(P)]
    assert max(expansions) <= 1  # paper: "usually no repetitions at all"


# ----------------------------------------------------------------- BA

@pytest.mark.parametrize("n,d", [(64, 1), (128, 2), (200, 3)])
def test_ba_parallel_equals_sequential(n, d):
    seed = 5
    par = ba.ba_union(seed, n, d, P=4)
    seq = ba.ba_sequential_reference(seed, n, d)
    np.testing.assert_array_equal(par, seq)


def test_ba_pe_partition():
    seed, n, d, P = 3, 100, 2, 5
    pes = [ba.ba_pe(seed, n, d, P, pe) for pe in range(P)]
    allp = np.concatenate(pes)
    assert len(allp) == n * d
    assert (np.sort(allp[:, 0] * d + np.arange(len(allp)) % 1) >= 0).all()
    # sources partition [0, n)
    srcs = np.concatenate([np.unique(p[:, 0]) for p in pes])
    assert len(np.unique(srcs)) == n


def test_ba_degree_distribution_power_law():
    n, d = 3000, 2
    e = ba.ba_union(11, n, d, P=1)
    deg = np.bincount(e.ravel(), minlength=n)
    # preferential attachment: early vertices dominate
    assert deg[:10].mean() > 8 * deg[n // 2:].mean()
    tail = np.sort(deg[deg >= 8])
    assert len(tail) > 20


# ----------------------------------------------------------------- R-MAT

def test_rmat_shapes_and_partition():
    e = rmat.rmat_union(1, log_n=10, m=5000, P=4)
    assert e.shape == (5000, 2)
    assert e.min() >= 0 and e.max() < 1024


def test_rmat_quadrant_distribution():
    """The top level of the descent picks quadrant (a, b, c, d), read on
    the labels with Graph 500's permutation undone."""
    probs = (0.57, 0.19, 0.19, 0.05)
    e = rmat.rmat_union(2, log_n=12, m=40000, P=1, probs=probs)
    consts = rmat.perm_constants(device_key(2, rmat._TAG_RMAT), 12)
    pi = np.asarray(rmat.permute(jnp.arange(1 << 12), consts, 12))
    e = np.argsort(pi)[e]
    half = 1 << 11
    q = 2 * (e[:, 0] >= half) + (e[:, 1] >= half)
    freq = np.bincount(q, minlength=4) / len(e)
    for i, p in enumerate([probs[0], probs[1], probs[2], probs[3]]):
        assert abs(freq[i] - p) < 0.01, (i, freq[i], p)


def test_rmat_determinism_across_P():
    a = rmat.rmat_union(4, log_n=8, m=1000, P=1)
    b = rmat.rmat_union(4, log_n=8, m=1000, P=7)
    # P only deals the chunks of edge ids: the same edges, in another order
    order = lambda e: e[np.lexsort(e.T[::-1])]
    np.testing.assert_array_equal(order(a), order(b))
