"""Random Delaunay graphs on the unit torus [0,1)^d, d in {2,3} (paper §6).

Point generation reuses the RGG grid/recursion with cell side
c ≈ ((d+1)/n)^(1/d) (mean (d+1)-th-nearest-neighbor distance).  Each PE
triangulates its chunk plus an expanding *halo* of recomputed neighbor
cells, and accepts the result only when

  (a) no convex-hull vertex of the local triangulation is chunk-local, and
  (b) every simplex containing a chunk-interior point has its
      circumsphere fully inside the chunk+halo region,

which guarantees those simplices belong to the global periodic Delaunay
triangulation (any point that could invalidate them would lie inside the
generated region and therefore has been generated).  Otherwise the halo
is expanded by one cell ring and the DT recomputed (paper: update).

Periodicity: halo cells are *unwrapped* — a cell may enter multiple
times under different ±1 translations, which also covers the P=1 case
(a chunk neighboring its own copies).

Division of labor: nothing stays on the host.  The local DT engine is
the batched Bowyer-Watson kernel (:mod:`repro.kernels.delaunay`): each
halo round, *every* pending chunk's chunk+halo point row triangulates
in one device dispatch (:class:`RdgStructure`), and certification is
one vectorized Cramer solve across all pending chunks
(:func:`circumspheres`).  The edge phase ships every certified simplex
through the engine's GEOM_CERT PairPlan executor
(:func:`rdg_pair_plan`), which re-derives the certificates on device —
the same Cramer arithmetic as the kernel's in-sphere predicate, so
planning-time and execution-time certificates agree bit-for-bit — and
emits the canonical edge set.  Qhull (scipy) is demoted to the test
oracle (:func:`rdg_pe` per-PE host loop, :func:`rdg_pair_plan_specs`
scalar designation walk, :func:`rdg_brute_edges` global tiling).
"""
from __future__ import annotations

import functools
import itertools
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
from scipy.spatial import Delaunay

from .rgg import (CellCounter, CellGrid, CellSplitTree, local_cells_for_pe,
                  make_grid, points_for_cells)

Cell = Tuple[int, ...]


def rdg_grid(n: int, P: int, dim: int) -> CellGrid:
    c = ((dim + 1) / n) ** (1.0 / dim)
    return make_grid(n, c, P, dim)


def default_chunk_P(P: int, dim: int) -> int:
    """Default virtual-chunk count for the RDG grid.

    Fewer, fatter chunks cut halo duplication (each chunk recomputes its
    one-ring; at K=64 chunks a 3d region re-generates ~12x the chunk's
    own points, at K=8 only ~3.5x), which is what the batched device DT's
    cost tracks.  2d keeps the legacy 16 (instance-compatible with the
    old ``DEFAULT_CHUNKS`` grid); 3d drops to 8, where the round's
    [B, N] work area is smallest.  Never below P so every PE owns work.
    """
    return max(P, 16 if dim == 2 else 8)


def rdg_point_plan(seed: int, n: int, P: int, dim: int = 2,
                   rng_impl: str = "threefry2x32", chunk_P: int = 0):
    """PointPlan for the sharded engine over the RDG cell grid (the
    RGG grid with cell side ~ the (d+1)-th-nearest-neighbor distance);
    the triangulation phase consumes these cells via the halo protocol."""
    from .. import obs
    from .rgg import grid_point_plan

    with obs.trace("plan/rdg", phase="plan", family="rdg", reseed=False, P=P):
        grid = rdg_grid(n, chunk_P or default_chunk_P(P, dim), dim)
        return grid_point_plan(seed, grid, CellCounter(seed, grid, n), P, rng_impl)


def _torus_canonical(cell: Cell, g: int) -> Tuple[Cell, Tuple[int, ...]]:
    canon = tuple(c % g for c in cell)
    shift = tuple((c - cc) // g for c, cc in zip(cell, canon))
    return canon, shift


def _ring(cells: set, dim: int) -> set:
    """All unwrapped cells adjacent to the given set (excluded)."""
    out = set()
    offs = [o for o in itertools.product((-1, 0, 1), repeat=dim) if any(o)]
    for c in cells:
        for o in offs:
            nb = tuple(a + b for a, b in zip(c, o))
            if nb not in cells:
                out.add(nb)
    return out


def circumspheres(simp: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Batched circumcenters + radii of [S, d+1, d] simplices.

    Thin host wrapper over the *shared* device predicate
    (:func:`repro.kernels.delaunay.circumsphere`): planning-time
    certificates, the insertion kernel's in-sphere test, and the
    engine's GEOM_CERT re-check (:func:`repro.distrib.engine.\
_circumsphere_in_box`) all execute the one jitted Cramer solve, so
    they agree bit-for-bit by construction.  A numpy twin with the same
    operation *order* is not enough — XLA may contract multiply-adds
    into FMAs, drifting an ulp from numpy's rounding, and an ulp at a
    region-box boundary is an edge lost to a host/device certificate
    disagreement.  Degenerate slivers (det == 0) get radius = inf,
    which fails every containment test and forces a halo expansion.

    The batch is padded to a power-of-two bucket (>= 256) so the jit
    cache stays small across rounds of varying simplex counts.
    """
    from ..kernels.delaunay import circumsphere

    S = len(simp)
    if S == 0:
        d = simp.shape[2] if simp.ndim == 3 else 2
        return np.zeros((0, d), simp.dtype), np.zeros(0, simp.dtype)
    cap = 1 << max(8, (S - 1).bit_length())
    pad = np.zeros((cap,) + simp.shape[1:], simp.dtype)
    pad[:S] = simp
    center, r2, nondeg = circumsphere(pad)
    center, r2, nondeg = (np.asarray(center)[:S], np.asarray(r2)[:S],
                          np.asarray(nondeg)[:S])
    rad = np.where(nondeg, np.sqrt(r2), np.inf)
    return center, rad


class _PointBank:
    """Deterministic point lookup per unwrapped cell (recompute-on-demand)."""

    def __init__(self, seed: int, grid: CellGrid, counter: CellCounter,
                 rng_impl: str | None = None):
        self.seed, self.grid, self.counter = seed, grid, counter
        self.rng_impl = rng_impl
        self._cache: Dict[Cell, Tuple[np.ndarray, np.ndarray]] = {}

    def get(self, cell: Cell) -> Tuple[np.ndarray, np.ndarray]:
        """(positions (k,d) unwrapped, gids (k,)) for one unwrapped cell."""
        if cell not in self._cache:
            self.prefetch([cell])
        return self._cache[cell]

    def prefetch(self, cells: Sequence[Cell]) -> None:
        """Batch-generate every uncached cell in one device dispatch
        (the per-slot draws are capacity-independent, so batching cells
        of different counts yields the identical per-cell streams)."""
        missing = [c for c in cells if c not in self._cache]
        if not missing:
            return
        canon_shift = [_torus_canonical(c, self.grid.g) for c in missing]
        pos, counts, offsets, _ = points_for_cells(
            self.seed, self.grid, self.counter,
            [cs[0] for cs in canon_shift], self.rng_impl
        )
        for i, (cell, (_, shift)) in enumerate(zip(missing, canon_shift)):
            k = counts[i]
            p = pos[i][:k] + np.asarray(shift, dtype=np.float64)
            self._cache[cell] = (p, offsets[i] + np.arange(k))


class _GridBank:
    """Whole-grid point bank: one tight-capacity device dispatch per
    seed generates *every* canonical cell's points at once, and
    unwrapped halo images are served as a numpy lattice shift of the
    cached canonical row.

    Bit-compatible with :class:`_PointBank` (the per-slot draws of
    :func:`repro.core.rgg._points_for_cells` are keyed by cell id and
    capacity-independent, so a tight pad and the 128-padded on-demand
    path yield identical first-k slots) but without its per-request
    Python count loop, 128-slot overgeneration, or per-canonical-cell
    duplicate regeneration — the prefetch cost that used to rival the
    triangulation itself.  Memory is counts.max()-padded over g^dim
    cells, fine for any grid the batched DT itself can handle.
    """

    def __init__(self, seed: int, grid: CellGrid, n: int,
                 tree: CellSplitTree, rng_impl: str | None = None):
        import jax.numpy as jnp

        from .prng import device_key
        from .rgg import _TAG_PTS, _points_for_cells

        self.seed, self.grid = seed, grid
        counts, offsets = tree.counts_offsets(seed, n)
        cap = _round_up(max(1, int(counts.max())), 8)
        g, dim = grid.g, grid.dim
        coords = np.stack(np.meshgrid(*([np.arange(g)] * dim), indexing="ij"),
                          axis=-1).reshape(-1, dim)
        pos, _ = _points_for_cells(
            device_key(seed, _TAG_PTS, impl=rng_impl),
            jnp.arange(g ** dim, dtype=jnp.int64), jnp.asarray(coords),
            jnp.asarray(counts), cap, dim, g)
        self._pos = np.asarray(pos)
        self._counts, self._offsets = counts, offsets
        self._cache: Dict[Cell, Tuple[np.ndarray, np.ndarray]] = {}

    def get(self, cell: Cell) -> Tuple[np.ndarray, np.ndarray]:
        """(positions (k,d) unwrapped, gids (k,)) for one unwrapped cell."""
        hit = self._cache.get(cell)
        if hit is None:
            canon, shift = _torus_canonical(cell, self.grid.g)
            cid = self.grid.cell_id(canon)
            k = int(self._counts[cid])
            hit = self._cache[cell] = (
                self._pos[cid, :k] + np.asarray(shift, np.float64),
                self._offsets[cid] + np.arange(k))
        return hit

    def region(self, cells: Sequence[Cell], local: set) -> \
            Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(pts, gids, is_local) for a whole cell sequence in one numpy
        gather — identical concatenation order to per-cell :meth:`get`
        calls, without the per-cell Python cost (a 2d bench region is
        ~300 cells x 16 chunks, where per-cell calls are ~0.1s/plan)."""
        g, dim = self.grid.g, self.grid.dim
        arr = np.asarray(cells, np.int64)              # [R, d]
        canon = np.mod(arr, g)
        shift = ((arr - canon) // g).astype(np.float64)
        cid = canon[:, 0]
        for a in range(1, dim):
            cid = cid * g + canon[:, a]
        k = self._counts[cid]                          # [R]
        cap = self._pos.shape[1]
        sel = np.arange(cap)[None, :] < k[:, None]     # [R, cap]
        pts = (self._pos[cid] + shift[:, None, :])[sel]
        gids = (self._offsets[cid][:, None] + np.arange(cap)[None, :])[sel]
        is_local = np.fromiter((c in local for c in cells), bool, len(arr))
        return pts, gids, np.repeat(is_local, k)

    def prefetch(self, cells: Sequence[Cell]) -> None:
        """No-op: the whole grid is resident from construction."""


def _certified_triangulation(
    bank, local_cells: set, dim: int, max_expand: int,
    region: Optional[set] = None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray,
           np.ndarray, np.ndarray, int]:
    """Run the halo protocol for one cell set until the triangulation is
    certified; returns (pts, gids, loc, simplices, box_lo, box_hi,
    expansions).  Circumsphere certificates are evaluated in one
    vectorized :func:`circumspheres` batch per iteration, never one
    simplex at a time.

    Test oracle: the production emitter (:class:`RdgStructure`) runs the
    same protocol level-synchronously on device, one batched kernel
    dispatch per halo round across all pending chunks.  ``region`` lets
    a caller resume from an already-expanded halo (a superset region can
    only certify earlier — the box check gets easier and every accepted
    simplex is still a global-DT simplex); default is the classic
    chunk + one ring start."""
    grid = bank.grid
    if region is None:
        region = set(local_cells)
        region |= _ring(region, dim)
    else:
        region = set(region)

    expansions = 0
    while True:
        pts_list, gid_list, is_local = [], [], []
        bank.prefetch(sorted(region))
        for cell in sorted(region):
            p, g = bank.get(cell)
            pts_list.append(p)
            gid_list.append(g)
            is_local.append(np.full(len(g), cell in local_cells))
        pts = np.concatenate(pts_list)
        gids = np.concatenate(gid_list)
        loc = np.concatenate(is_local)

        if len(pts) < dim + 2:
            raise ValueError("too few points for a Delaunay triangulation")

        tri = Delaunay(pts)  # repro: allow(no-per-chunk-host-loop) retained Qhull oracle

        # region bounding box (unwrapped cells are axis-aligned unit/g boxes)
        cells_arr = np.array(sorted(region))
        box_lo = cells_arr.min(axis=0) / grid.g
        box_hi = (cells_arr.max(axis=0) + 1) / grid.g

        ok = not loc[tri.convex_hull.ravel()].any()
        if ok:
            sel = tri.simplices[loc[tri.simplices].any(axis=1)]
            if len(sel):
                center, rad = circumspheres(pts[sel])  # repro: allow(no-per-chunk-host-loop) retained Qhull oracle
                ok = bool(((center - rad[:, None] >= box_lo).all()
                           & (center + rad[:, None] <= box_hi).all()))
        if ok:
            return pts, gids, loc, tri.simplices, box_lo, box_hi, expansions
        expansions += 1
        if expansions > max_expand:
            raise RuntimeError("halo did not converge")
        region |= _ring(region, dim)


def rdg_pe(
    seed: int, n: int, P: int, pe: int, dim: int = 2, max_expand: int = 8,
    chunk_P: int = 0,
) -> Tuple[np.ndarray, np.ndarray, int]:
    """Delaunay edges incident to PE `pe`'s vertices on the torus — the
    per-PE *host loop*, retired as the production edge phase (the engine
    executes :func:`rdg_pair_plan` instead) and kept as the independent
    test oracle for it.

    Returns (edges [k,2] gids u>v, local gids, #halo expansions used).
    ``chunk_P`` sizes the virtual chunk grid independently of P (the
    instance is a function of the grid; default:
    :func:`default_chunk_P`, matching the production emitter).
    """
    grid = rdg_grid(n, chunk_P or default_chunk_P(P, dim), dim)
    counter = CellCounter(seed, grid, n)
    bank = _PointBank(seed, grid, counter)
    local_cells = set(local_cells_for_pe(grid, P, pe))
    pts, gids, loc, simplices, _, _, expansions = _certified_triangulation(
        bank, local_cells, dim, max_expand)

    # edges: simplex edges with >= 1 local endpoint
    edges = set()
    for simplex in simplices:
        for i, j in itertools.combinations(simplex, 2):
            if loc[i] or loc[j]:
                u, v = int(gids[i]), int(gids[j])
                if u == v:
                    continue  # a point adjacent to its own periodic image
                edges.add((max(u, v), min(u, v)))

    local_gids = np.unique(gids[loc])  # repro: allow(no-numpy-unique) O(cell) plan-time gid metadata, not edge dedup
    e = np.array(sorted(edges), dtype=np.int64) if edges else np.zeros((0, 2), np.int64)
    return e, local_gids, expansions


def _designated_rows(simplices: np.ndarray, loc: np.ndarray, gids: np.ndarray,
                     n: int, dim: int, cap: int) -> Tuple[np.ndarray, np.ndarray]:
    """Vectorized edge-designation pass for one chunk's triangulation:
    (ascending simplex indices that emit, per-simplex edge bitmask).

    Batches what the per-simplex walk did scalar-wise: candidate edges
    as [S, combos] grids, ownership via sorted-gid membership, and
    first-designation dedup by stable-sorting edge codes — the same
    (simplex-major, combo-minor) first occurrence the ``seen`` set
    picked, so the masks are bit-identical."""
    from ..distrib.engine import pair_slot_index

    S = len(simplices)
    lg = np.sort(gids[loc])
    if S == 0 or len(lg) == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    combos = [(i, j) for i in range(dim + 1) for j in range(i + 1, dim + 1)]
    ci = np.array([i for i, _ in combos])
    cj = np.array([j for _, j in combos])
    bits = np.array([1 << pair_slot_index(i, j, cap) for i, j in combos],
                    np.int64)
    M = len(combos)
    ls = loc[simplices]                                   # [S, d+1]
    gs = gids[simplices]                                  # [S, d+1]
    a, b = gs[:, ci], gs[:, cj]                           # [S, M]
    hi, lo = np.maximum(a, b), np.minimum(a, b)
    keep = ls.any(axis=1)[:, None] & (ls[:, ci] | ls[:, cj]) & (a != b)
    pos = np.minimum(np.searchsorted(lg, hi), len(lg) - 1)
    keep &= lg[pos] == hi                                 # max-gid owner is ours
    idx = np.nonzero(keep.ravel())[0]   # ascending == the scalar walk order
    code = hi.ravel()[idx] * np.int64(n) + lo.ravel()[idx]
    order = np.argsort(code, kind="stable")
    sc = code[order]
    first = np.ones(len(sc), bool)
    first[1:] = sc[1:] != sc[:-1]
    chosen = idx[order[first]]          # first designation of each edge
    mask = np.zeros(S, np.int64)
    np.bitwise_or.at(mask, chosen // M, bits[chosen % M])
    rows = np.nonzero(mask)[0]
    return rows, mask[rows]


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


class RdgStructure:
    """Seed-independent RDG planning structure (PR-9 fast-path pattern).

    Caches everything the halo protocol needs that does not depend on
    the seed — the cell grid, the per-virtual-chunk cell sets, and their
    initial one-ring regions — so :meth:`emit` is the cheap
    ``reseed_fn`` the serve plan cache calls on seed rotation.

    :meth:`emit` runs the halo protocol *level-synchronously*: each
    round, every still-uncertified chunk's chunk+halo point row is
    padded into one ``[B, N, d]`` batch and triangulated in a single
    :func:`repro.kernels.delaunay.batched_delaunay` dispatch (no
    per-chunk host loop, no Qhull).  Certification is one vectorized
    :func:`circumspheres` call per round across all pending chunks.
    A chunk passes when

      (a) no alive simplex joins a chunk-local vertex to a super-simplex
          vertex (local id >= the row's point count) — the bounding
          super-simplex encloses everything, so hull vertices are
          exactly the points adjacent to super vertices — and
      (b) every super-free simplex touching a local point has its
          circumsphere inside the region box.

    Degenerate/cocircular configurations surface either as a cleared
    kernel ``ok`` flag or as an infinite certificate radius; both fail
    the round and expand the halo, like the Qhull oracle.  Certified
    simplices are genuine global-DT simplices, so the emitted edge set
    equals the oracle's even where the two paths pick different
    designated rows per edge.

    Tiny-grid exception: when a region *wraps* the torus on two axes
    (span > g cells, so the same canonical point enters under two
    lattice shifts per axis), the four images of one point form an
    exact rectangle — exactly cocircular in 2d, exactly coplanar in 3d,
    and any sphere through three corners passes exactly through the
    fourth.  These guaranteed ties would clear ``ok`` forever, so such
    chunks run the merged-facet Qhull oracle
    (:func:`_certified_triangulation`) instead; production-scale grids
    never wrap, so the device batch is the only path that runs there.
    """

    def __init__(self, n: int, P: int, dim: int = 2,
                 rng_impl: str = "threefry2x32", chunk_P: int = 0,
                 max_expand: int = 8):
        self.n, self.P, self.dim = int(n), int(P), int(dim)
        if self.n < self.dim + 2:
            raise ValueError("too few points for a Delaunay triangulation")
        self.rng_impl, self.max_expand = rng_impl, int(max_expand)
        self.grid = rdg_grid(n, chunk_P or default_chunk_P(P, dim), dim)
        self.K = self.grid.cpd ** self.dim
        self.chunk_cells: List[set] = [
            set(local_cells_for_pe(self.grid, self.K, v))
            for v in range(self.K)]
        self._tree = CellSplitTree(self.grid)   # seed-independent counts
        # start every chunk at chunk + TWO rings: a one-ring halo is a
        # single cell side ~ the (d+1)-NN distance, which the boundary
        # simplices' circumspheres essentially always overrun (measured:
        # 16/16 2d and 7/8 3d bench chunks fail ring 1), so starting at
        # ring 2 folds the guaranteed expansion into the first device
        # round.  A larger start is always sound: certification only
        # gets easier, and accepted simplices are global-DT either way.
        self._init_regions: List[set] = []
        for c in self.chunk_cells:
            r = set(c) | _ring(c, self.dim)
            self._init_regions.append(r | _ring(r, self.dim))
        self._col_cache: Dict[int, tuple] = {}

    def _wraps(self, region: set) -> bool:
        """True when the region's periodic images can be exactly
        degenerate: the cell box spans more than the torus on >= 2 axes
        (image rectangles) or more than two full turns on one (collinear
        image triples)."""
        arr = np.array(sorted(region))
        span = arr.max(axis=0) - arr.min(axis=0) + 1
        return bool(((span > self.grid.g).sum() >= 2)
                    or (span > 2 * self.grid.g).any())

    # -- halo protocol, one device batch per round ----------------------
    def _triangulate_chunks(self, seed: int) -> List[tuple]:
        """(pts, gids, loc, interior simplices, box_lo, box_hi) per
        virtual chunk."""
        from .. import obs
        from ..kernels.delaunay import batched_delaunay

        dim, grid = self.dim, self.grid
        bank = _GridBank(seed, grid, self.n, self._tree, self.rng_impl)
        regions = [set(r) for r in self._init_regions]
        pending = list(range(self.K))
        expansions = [0] * self.K
        done: Dict[int, tuple] = {}
        while pending:
            # torus-wrapping regions hold exact periodic degeneracies the
            # abort-on-tie kernel cannot resolve -> Qhull oracle, resumed
            # from the already-expanded region (tiny grids only; see the
            # class docstring)
            wrapped = [v for v in pending if self._wraps(regions[v])]
            for v in wrapped:
                pts, gids, loc, simplices, box_lo, box_hi, _ = \
                    _certified_triangulation(bank, self.chunk_cells[v], dim,
                                             self.max_expand,
                                             region=regions[v])
                done[v] = (pts, gids, loc, simplices, box_lo, box_hi)
            if wrapped:
                obs.event("plan/rdg/qhull_resume", chunks=len(wrapped))
                pending = [v for v in pending if v not in set(wrapped)]
                if not pending:
                    break
            rows, boxes = [], []
            for v in pending:
                cells = sorted(regions[v])
                rows.append(bank.region(cells, self.chunk_cells[v]))
                cells_arr = np.array(cells)
                boxes.append((cells_arr.min(axis=0) / grid.g,
                              (cells_arr.max(axis=0) + 1) / grid.g))
            if min(len(r[0]) for r in rows) < dim + 2:
                raise ValueError("too few points for a Delaunay triangulation")
            # pad to a (pow2 rows) x (128-multiple points) bucket so the
            # kernel recompiles at most a few times across halo rounds
            N = _round_up(max(len(r[0]) for r in rows), 128)
            B = 1 << max(0, len(pending) - 1).bit_length()
            ptsb = np.zeros((B, N, dim))
            cnt = np.zeros(B, np.int64)
            for i, (p, _, _) in enumerate(rows):
                ptsb[i, : len(p)] = p
                cnt[i] = len(p)
            simp, alive, ok = batched_delaunay(ptsb, cnt, dim=dim)
            simp, alive, ok = np.asarray(simp), np.asarray(alive), np.asarray(ok)

            # collect every pending chunk's local-touching interior
            # simplices, then certify them in ONE circumsphere batch
            per_chunk, seg_pts, offs = [], [], [0]
            for i, v in enumerate(pending):
                pts, gids, loc = rows[i]
                nb = int(cnt[i])
                live = simp[i][alive[i]]
                sup = (live >= nb).any(axis=1)
                lv = np.where(live < nb, loc[np.minimum(live, nb - 1)], False)
                hull_ok = bool(ok[i]) and not (lv.any(axis=1) & sup).any()
                interior = live[~sup]
                sel = interior[loc[interior].any(axis=1)] if len(interior) \
                    else interior
                per_chunk.append((v, hull_ok, interior, sel))
                seg_pts.append(pts[sel] if len(sel)
                               else np.zeros((0, dim + 1, dim)))
                offs.append(offs[-1] + len(sel))
            allsimp = np.concatenate(seg_pts)
            center, rad = (circumspheres(allsimp) if len(allsimp)  # repro: allow(no-per-chunk-host-loop) one batch per halo round, never per chunk
                           else (np.zeros((0, dim)), np.zeros(0)))
            inside = np.ones(len(allsimp), bool)
            for i, (v, _, _, _) in enumerate(per_chunk):
                lo, hi = boxes[i]
                s = slice(offs[i], offs[i + 1])
                inside[s] = ((center[s] - rad[s, None] >= lo).all(axis=1)
                             & (center[s] + rad[s, None] <= hi).all(axis=1))

            still = []
            for i, (v, hull_ok, interior, _) in enumerate(per_chunk):
                if hull_ok and inside[offs[i]:offs[i + 1]].all():
                    pts, gids, loc = rows[i]
                    done[v] = (pts, gids, loc, interior) + boxes[i]
                    continue
                expansions[v] += 1
                if expansions[v] > self.max_expand:
                    raise RuntimeError("halo did not converge")
                regions[v] |= _ring(regions[v], dim)
                still.append(v)
            pending = still
        return [done[v] for v in range(self.K)]

    # -- plan columns (seed-cached so segments share one device pass) ---
    def _columns(self, seed: int) -> tuple:
        if seed in self._col_cache:
            return self._col_cache[seed]
        n, dim, cap = self.n, self.dim, 4
        G = (dim + 1) * dim
        vg_l: List[np.ndarray] = []
        bits_l: List[np.ndarray] = []
        geom_l: List[np.ndarray] = []
        box_l: List[np.ndarray] = []
        for pts, gids, loc, simplices, box_lo, box_hi in \
                self._triangulate_chunks(seed):
            rows, mask = _designated_rows(simplices, loc, gids, n, dim, cap)
            if not len(rows):
                continue
            sel = simplices[rows]
            vg = np.zeros((len(rows), cap), np.int64)
            vg[:, : dim + 1] = gids[sel]
            vg_l.append(vg)
            bits_l.append(mask)
            geom_l.append(pts[sel].reshape(len(rows), G))
            box_l.append(np.broadcast_to(
                np.concatenate([box_lo, box_hi]), (len(rows), 2 * dim)))
        k = sum(len(v) for v in vg_l)
        gid_a = np.concatenate(vg_l) if k else np.zeros((0, cap), np.int64)
        gid_b = np.zeros((k, cap), np.int64)
        gid_b[:, 0] = np.concatenate(bits_l) if k else 0
        geom_a = np.concatenate(geom_l) if k else np.zeros((0, G))
        geom_b = np.ones((k, G))       # right-padded with the table fill
        geom_b[:, : 2 * dim] = np.concatenate(box_l) if k else 0
        cols = (k, gid_a, gid_b, geom_a, geom_b)
        if len(self._col_cache) >= 4:   # serve rotates seeds; keep it tiny
            self._col_cache.pop(next(iter(self._col_cache)))
        self._col_cache[seed] = cols
        return cols

    def _emit(self, seed: int, P_out: int, pe: np.ndarray, cols: tuple):
        from ..distrib.engine import GEOM_CERT, pair_plan_from_columns

        k = len(pe)
        _, gid_a, gid_b, geom_a, geom_b = cols
        dpl = np.full(k, self.dim + 1, np.int64)
        return pair_plan_from_columns(
            P_out, pe, np.full(k, GEOM_CERT, np.int32),
            np.zeros((k, 2), np.uint32), np.zeros((k, 2), np.uint32),
            dpl, dpl, gid_a, gid_b, geom_a, geom_b,
            np.zeros((k, 1)), np.ones(k, bool),
            capacity=4, rng_impl=self.rng_impl, dim=self.dim)

    def emit(self, seed: int):
        """Full PairPlan for this structure's (P, grid); also the plan's
        ``reseed_fn`` — reseeding re-runs only the device passes."""
        from .. import obs

        with obs.trace("plan/rdg", phase="plan", family="rdg",
                       reseed=False, P=self.P):
            cols = self._columns(seed)
            k = cols[0]
            out = self._emit(seed, self.P,
                             np.arange(k, dtype=np.int64) % self.P, cols)
        import dataclasses as _dc
        return _dc.replace(out, reseed_fn=self.emit)

    def segment(self, seed: int, lo: int, hi: int):
        """Native PlanEmitter segment: global PEs [lo, hi) re-indexed to
        [0, hi - lo); concatenating segments reproduces :meth:`emit`'s
        per-PE row order (the deal is stable in global row order)."""
        from .. import obs

        with obs.trace("plan/rdg", phase="plan", family="rdg",
                       reseed=False, P=self.P, lo=lo, hi=hi):
            cols = self._columns(seed)
            k, gid_a, gid_b, geom_a, geom_b = cols
            pe = np.arange(k, dtype=np.int64) % self.P
            sel = (pe >= lo) & (pe < hi)
            sub = (int(sel.sum()), gid_a[sel], gid_b[sel],
                   geom_a[sel], geom_b[sel])
            return self._emit(seed, hi - lo, pe[sel] - lo, sub)


@functools.lru_cache(maxsize=None)
def rdg_structure(n: int, P: int, dim: int = 2,
                  rng_impl: str = "threefry2x32", chunk_P: int = 0,
                  max_expand: int = 8) -> RdgStructure:
    return RdgStructure(n, P, dim, rng_impl, chunk_P, max_expand)


def rdg_pair_plan(seed: int, n: int, P: int, dim: int = 2,
                  rng_impl: str = "threefry2x32", chunk_P: int = 0,
                  max_expand: int = 8):
    """GEOM_CERT PairPlan: certified Delaunay simplices, dealt to PEs.

    The halo protocol runs once per *virtual chunk* of the grid
    (level-synchronously, one batched device triangulation per round —
    see :class:`RdgStructure`), so the plan is a pure function of the
    spec: identical rows for every P, with P only deciding which PE
    executes which rows.  Every shipped simplex carries its certificate
    inputs so the executor re-derives it on device with the same Cramer
    arithmetic the kernel used to build it.

    Each plan row is one simplex that is the *designated emitter* of at
    least one edge: the combinatorial pass dedups simplex edges (an
    interior edge lies in 2+ simplices), applies canonical ownership
    (the chunk owning the max-gid endpoint emits), and drops periodic
    self-images — the CERT analog of the chunk ``owned`` bit, encoded as
    a per-edge bitmask.  The device re-certifies the circumsphere and
    emits the masked edges, so concatenated per-PE outputs are the exact
    global Delaunay edge set with no sort/unique dedup.

    Designation is vectorized (:func:`_designated_rows`) and the rows —
    self-contained: every row carries its full certificate — are dealt
    round-robin by global row index, not by owning chunk, so per-PE row
    counts differ by at most one and the table's fill_fraction stays
    near 1 even when chunk sizes are skewed.  The chunk-dealt scalar
    Qhull walk is retained as :func:`rdg_pair_plan_specs`, the
    edge-content oracle (it may pick different designated rows per edge;
    the emitted edge sets are equal).
    """
    return rdg_structure(n, P, dim, rng_impl, chunk_P, max_expand).emit(seed)


def rdg_plan_segment(seed: int, n: int, P: int, lo: int, hi: int,
                     dim: int = 2, rng_impl: str = "threefry2x32",
                     chunk_P: int = 0, max_expand: int = 8):
    """Segment [lo, hi) of :func:`rdg_pair_plan` for the native
    :class:`repro.distrib.runtime.PlanEmitter` path; the device passes
    run once per seed (cached on the structure) and each segment just
    re-deals its slice."""
    return rdg_structure(n, P, dim, rng_impl, chunk_P,
                         max_expand).segment(seed, lo, hi)


def rdg_pair_plan_specs(seed: int, n: int, P: int, dim: int = 2,
                        rng_impl: str = "threefry2x32", chunk_P: int = 0,
                        max_expand: int = 8):
    """Retained oracle: the original scalar designation walk of
    :func:`rdg_pair_plan`, dealt by owning chunk (``v % P``).  Defines
    the row *content* and per-chunk row order the vectorized path must
    reproduce; the production path only re-deals the same rows for
    balance.  Not a production path."""
    from ..distrib.engine import GEOM_CERT, PairSpec, make_pair_plan, pair_slot_index

    grid = rdg_grid(n, chunk_P or default_chunk_P(P, dim), dim)
    counter = CellCounter(seed, grid, n)
    bank = _PointBank(seed, grid, counter, rng_impl)
    K = grid.cpd ** dim            # virtual chunks, one protocol run each
    cap = 4                        # d+1 <= 4 vertex slots per simplex row
    zero_key = np.zeros(2, np.uint32)

    per_pe: List[List[PairSpec]] = [[] for _ in range(P)]
    for v in range(K):
        local_cells = set(local_cells_for_pe(grid, K, v))
        pts, gids, loc, simplices, box_lo, box_hi, _ = _certified_triangulation(
            bank, local_cells, dim, max_expand)
        local_gids = set(np.unique(gids[loc]).tolist())  # repro: allow(no-numpy-unique) O(cell) plan-time gid metadata, not edge dedup
        box = tuple(box_lo) + tuple(box_hi)

        seen: set = set()
        emit_mask: Dict[int, int] = {}
        for s_idx, simplex in enumerate(simplices):
            ls = loc[simplex]
            if not ls.any():
                continue
            for i in range(dim + 1):
                for j in range(i + 1, dim + 1):
                    if not (ls[i] or ls[j]):
                        continue
                    a, b = int(gids[simplex[i]]), int(gids[simplex[j]])
                    if a == b:
                        continue  # periodic self-image
                    edge = (max(a, b), min(a, b))
                    if edge[0] not in local_gids or edge in seen:
                        continue  # not ours / already designated
                    seen.add(edge)
                    emit_mask[s_idx] = emit_mask.get(s_idx, 0) | (
                        1 << pair_slot_index(i, j, cap))

        for s_idx, bits in sorted(emit_mask.items()):
            simplex = simplices[s_idx]
            vg = np.zeros(cap, np.int64)
            vg[: dim + 1] = gids[simplex]
            per_pe[v % P].append(PairSpec(  # repro: allow(no-per-chunk-host-loop) retained oracle
                GEOM_CERT, zero_key, zero_key, dim + 1, dim + 1,
                vg, bits, tuple(pts[simplex].ravel()), box,
                self_pair=True))
    return make_pair_plan(per_pe, capacity=cap, rng_impl=rng_impl, dim=dim)


def rdg_union(seed: int, n: int, P: int, dim: int = 2) -> np.ndarray:
    es = []
    for pe in range(P):
        e, _, _ = rdg_pe(seed, n, P, pe, dim)
        es.append(e)
    e = np.concatenate(es, axis=0)
    return np.unique(e, axis=0) if e.size else e.reshape(0, 2)  # repro: allow(no-numpy-unique) test-oracle union (engine dedups by simplex ownership)


def rdg_brute_edges(points: np.ndarray, dim: int) -> np.ndarray:
    """Global periodic DT oracle: triangulate the 3^d tiling, keep edges
    with an endpoint in the canonical copy, fold gids mod n."""
    n = len(points)
    shifts = list(itertools.product((-1.0, 0.0, 1.0), repeat=dim))
    tiles = np.concatenate([points + np.array(s) for s in shifts])
    base = np.tile(np.arange(n), len(shifts))
    canonical = np.zeros(len(tiles), dtype=bool)
    center = shifts.index(tuple([0.0] * dim))
    canonical[center * n: (center + 1) * n] = True

    tri = Delaunay(tiles)
    edges = set()
    for simplex in tri.simplices:
        for i, j in itertools.combinations(simplex, 2):
            if canonical[i] or canonical[j]:
                u, v = int(base[i]), int(base[j])
                if u == v:
                    continue
                edges.add((max(u, v), min(u, v)))
    return np.array(sorted(edges), dtype=np.int64)
