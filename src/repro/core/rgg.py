"""Random geometric graphs in [0,1)^d, d in {2,3} (paper §5).

Communication-free parallelization: the unit cube is cut into a uniform
cell grid (cell side >= r when possible), cells are grouped into
2^(d*b) >= P Morton-ordered chunks, and per-cell vertex counts come from
a divide-and-conquer binomial recursion whose nodes are hashed — so any
PE can recompute any cell's vertices (its own *and* halo cells of
neighboring chunks) without communication.

Vertex ids are assigned in recursion order: the global id offset of a
cell is the sum of left-sibling counts along its root path, computable
in O(log #cells) by any PE — a consecutive, communication-free labeling.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import lru_cache, partial
from typing import Dict, Iterable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.pairdist.ops import pad_points
from ..kernels.pairdist.ref import pairdist_mask_ref
from .chunking import chunks_per_dim, cube_chunks_for_pe, morton_decode
from .prng import (PhiloxReplayer, counter_uniform, device_key, fold_in_many,
                   hash_paths, host_rng)
from .variates import binomial

_TAG_SPLIT, _TAG_PTS = 21, 22

Box = Tuple[Tuple[int, int], ...]  # ((lo, hi), ...) in cell coordinates
Cell = Tuple[int, ...]


@dataclass(frozen=True)
class CellGrid:
    """Uniform cell grid aligned with the Morton chunk decomposition."""
    dim: int
    g: int          # cells per dimension
    cpd: int        # chunks per dimension (power of two)
    rho: int        # neighbor search range in cells (ceil(r * g))

    @property
    def cells_per_chunk_dim(self) -> int:
        return self.g // self.cpd

    @property
    def num_cells(self) -> int:
        return self.g ** self.dim

    def cell_id(self, cell: Cell) -> int:
        cid = 0
        for c in cell:
            cid = cid * self.g + int(c)
        return cid

    def chunk_cells(self, chunk: Cell) -> List[Cell]:
        cc = self.cells_per_chunk_dim
        ranges = [range(c * cc, (c + 1) * cc) for c in chunk]
        out: List[Cell] = []

        def rec(prefix, rest):
            if not rest:
                out.append(tuple(prefix))
                return
            for v in rest[0]:
                rec(prefix + [v], rest[1:])

        rec([], ranges)
        return out


def make_grid(n: int, radius: float, P: int, dim: int) -> CellGrid:
    """Cell side = max(r, n^-1/d) rounded to tile the chunk grid (§5)."""
    cpd = chunks_per_dim(P, dim)
    target = max(radius, n ** (-1.0 / dim))
    per_chunk = max(1, int(1.0 / (target * cpd)))
    g = cpd * per_chunk
    rho = max(1, math.ceil(radius * g - 1e-9))
    return CellGrid(dim=dim, g=g, cpd=cpd, rho=rho)


class CellCounter:
    """Divide-and-conquer per-cell vertex counts (hashed binomial splits).

    `count(box)` and `cell_offset(cell)` are pure functions of
    (seed, grid, n): every PE computing them agrees — the core
    communication-free invariant.  Memoized per instance.
    """

    def __init__(self, seed: int, grid: CellGrid, n: int):
        self.seed, self.grid, self.n = seed, grid, n
        root = tuple((0, grid.g) for _ in range(grid.dim))
        self._memo: Dict[Box, int] = {root: n}
        self._root = root

    @staticmethod
    def _volume(box: Box) -> int:
        v = 1
        for lo, hi in box:
            v *= hi - lo
        return v

    @staticmethod
    def _split(box: Box) -> Tuple[int, int, Box, Box]:
        """Halve the largest dim (ties -> lowest index); chunk-aligned."""
        widths = [hi - lo for lo, hi in box]
        d = int(np.argmax(widths))
        lo, hi = box[d]
        mid = (lo + hi) // 2
        left = box[:d] + ((lo, mid),) + box[d + 1:]
        right = box[:d] + ((mid, hi),) + box[d + 1:]
        return d, mid, left, right

    def count(self, box: Box) -> int:
        if box in self._memo:
            return self._memo[box]
        parent, path = self._parent_of(box)
        _, _, left, right = self._split(parent)
        cp = self.count(parent)
        rng = host_rng(self.seed, _TAG_SPLIT, *[x for lohi in parent for x in lohi])
        cl = binomial(rng, cp, self._volume(left) / self._volume(parent))
        self._memo[left] = cl
        self._memo[right] = cp - cl
        return self._memo[box]

    def _parent_of(self, box: Box) -> Tuple[Box, None]:
        """Walk down from the root until `box` is a child of the cursor."""
        cur = self._root
        while True:
            if cur == box:
                raise AssertionError("box is root")
            _, _, left, right = self._split(cur)
            if self._contains(left, box):
                if left == box:
                    return cur, None
                # force materialization of left count, then descend
                self._ensure_children(cur)
                cur = left
            elif self._contains(right, box):
                if right == box:
                    return cur, None
                self._ensure_children(cur)
                cur = right
            else:
                raise AssertionError(f"{box} not inside {cur}")

    def _ensure_children(self, parent: Box) -> None:
        _, _, left, right = self._split(parent)
        if left in self._memo:
            return
        cp = self.count(parent)
        rng = host_rng(self.seed, _TAG_SPLIT, *[x for lohi in parent for x in lohi])
        cl = binomial(rng, cp, self._volume(left) / self._volume(parent))
        self._memo[left] = cl
        self._memo[right] = cp - cl

    @staticmethod
    def _contains(outer: Box, inner: Box) -> bool:
        return all(ol <= il and ih <= oh for (ol, oh), (il, ih) in zip(outer, inner))

    def cell_count(self, cell: Cell) -> int:
        box = tuple((c, c + 1) for c in cell)
        cur = self._root
        while cur != box:
            self._ensure_children(cur)
            _, _, left, right = self._split(cur)
            cur = left if self._contains(left, box) else right
        return self._memo[box]

    def cell_offset(self, cell: Cell) -> int:
        """Global vertex-id offset: sum of left-sibling counts on the path."""
        box = tuple((c, c + 1) for c in cell)
        cur, off = self._root, 0
        while cur != box:
            self._ensure_children(cur)
            _, _, left, right = self._split(cur)
            if self._contains(left, box):
                cur = left
            else:
                off += self._memo[left]
                cur = right
        return off


class CellSplitTree:
    """The :class:`CellCounter` recursion, flattened for replay.

    The split *tree* — which boxes exist, their hash paths, their volume
    ratios, which leaf is which cell — is a pure function of the grid
    (``_split`` halves the largest dim, ties lowest), never of the seed.
    Building it once and replaying the binomial draws in preorder gives
    every cell's count and vertex-id offset for any seed in one flat
    pass, with the *identical* per-node ``host_rng`` draws as the
    memoized descent — this is the seed-independent structure half of
    the RGG plan emitters, and what makes their reseed path cheap.
    """

    def __init__(self, grid: CellGrid):
        self.grid = grid
        boxes: List[Box] = []
        left: List[int] = []
        right: List[int] = []

        def build(box: Box) -> int:
            i = len(boxes)
            boxes.append(box)
            left.append(-1)
            right.append(-1)
            if CellCounter._volume(box) > 1:
                _, _, lo, hi = CellCounter._split(box)
                left[i] = build(lo)
                right[i] = build(hi)
            return i

        build(tuple((0, grid.g) for _ in range(grid.dim)))
        self._num_nodes = len(boxes)
        # internal nodes in preorder (index order): parent before children
        self._internal = [i for i in range(len(boxes)) if left[i] >= 0]
        self._left = left
        self._right = right
        # fixed-width hash paths (_TAG_SPLIT, *flattened box) per internal
        # node, ready for the vectorized splitmix64 chain
        self._path = np.array(
            [(_TAG_SPLIT,) + tuple(x for lohi in boxes[i] for x in lohi)
             for i in self._internal], np.int64).reshape(len(self._internal),
                                                         1 + 2 * grid.dim)
        self._ratio = [CellCounter._volume(boxes[self._left[i]])
                       / CellCounter._volume(boxes[i]) for i in self._internal]
        # leaf node of each cell, indexed by row-major cell id
        leaf = np.zeros(grid.num_cells, np.int64)
        for i, box in enumerate(boxes):
            if left[i] < 0:
                leaf[grid.cell_id(tuple(lo for lo, _ in box))] = i
        self._leaf = leaf

    def counts_offsets(self, seed: int, n: int) -> Tuple[np.ndarray, np.ndarray]:
        """(counts, vertex-id offsets) per cell (row-major cell id),
        bit-identical to :meth:`CellCounter.cell_count` /
        :meth:`CellCounter.cell_offset` for the same seed: the hash
        chains are batched (:func:`repro.core.prng.hash_paths`) and the
        Philox construction amortized (:class:`PhiloxReplayer`), but
        every node draws the identical variate the memoized descent
        would."""
        hashes = hash_paths(seed, self._path)
        replayer = PhiloxReplayer()
        cnt = np.zeros(self._num_nodes, np.int64)
        off = np.zeros(self._num_nodes, np.int64)
        cnt[0] = n
        left, right, ratio = self._left, self._right, self._ratio
        for k, i in enumerate(self._internal):
            c = int(cnt[i])
            if c:  # binomial(rng, 0, p) == 0 without consuming draws
                cl = binomial(replayer.at(hashes[k]), c, ratio[k])
            else:
                cl = 0
            l, r = left[i], right[i]
            cnt[l], cnt[r] = cl, c - cl
            off[l], off[r] = off[i], off[i] + cl
        return cnt[self._leaf], off[self._leaf]


# --------------------------------------------------------------------------
# device-side point generation
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cap", "dim", "g"))
def _points_for_cells(key, cell_ids, cell_coords, counts, cap: int, dim: int, g: int):
    """Uniform points inside each cell; (C, cap, dim) + mask (C, cap).

    Keyed by the *cell id* only, with capacity-independent per-slot
    draws — every PE regenerates identical points for the same cell no
    matter how its buffers are padded (the halo-recomputation
    invariant)."""
    def one(cid, coord, cnt):
        k = jax.random.fold_in(key, cid)
        u = counter_uniform(k, cap, dim)
        pos = (coord.astype(jnp.float64) + u) / g
        return pos, jnp.arange(cap) < cnt

    return jax.vmap(one)(cell_ids, cell_coords, counts)


def points_for_cells(
    seed: int, grid: CellGrid, counter: CellCounter, cells: Sequence[Cell],
    rng_impl: str | None = None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """(positions (C,cap,dim) f64, counts (C,), gid offsets (C,), cap).

    ``rng_impl`` selects the key implementation so point consumers can
    follow the same hashed stream a non-default-impl plan regenerates
    on device (None = the default threefry stream)."""
    counts = np.array([counter.cell_count(c) for c in cells], dtype=np.int64)
    offsets = np.array([counter.cell_offset(c) for c in cells], dtype=np.int64)
    cap = max(1, int(counts.max()) if len(counts) else 1)
    cap = (cap + 127) // 128 * 128  # kernel block multiple
    ids = jnp.array([grid.cell_id(c) for c in cells], dtype=jnp.int64)
    coords = jnp.array(cells, dtype=jnp.int64)
    pos, mask = _points_for_cells(
        device_key(seed, _TAG_PTS, impl=rng_impl), ids, coords, jnp.array(counts),
        cap, grid.dim, grid.g
    )
    return np.asarray(pos), counts, offsets, cap


# --------------------------------------------------------------------------
# per-PE generation
# --------------------------------------------------------------------------

def _neighbor_offsets(dim: int, rho: int) -> List[Cell]:
    rng = range(-rho, rho + 1)
    if dim == 2:
        return [(a, b) for a in rng for b in rng]
    return [(a, b, c) for a in rng for b in rng for c in rng]


def _is_forward(delta: Cell) -> bool:
    for x in delta:
        if x != 0:
            return x > 0
    return False  # zero offset


def local_cells_for_pe(grid: CellGrid, P: int, pe: int) -> List[Cell]:
    """Cells of PE `pe`: the grid's Morton chunks dealt round-robin.

    The chunk grid comes from ``grid.cpd`` (not from P), so a grid built
    for a fixed virtual chunk count yields the identical instance on any
    number of PEs."""
    cells: List[Cell] = []
    for ch in cube_chunks_for_pe(P, grid.dim, pe, cpd=grid.cpd):
        cells.extend(grid.chunk_cells(ch))
    return cells


def rgg_pe(
    seed: int, n: int, radius: float, P: int, pe: int, dim: int = 2,
    chunk_P: int = 0,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """All edges incident to PE `pe`'s vertices — the per-PE *host loop*.

    Retired as the production edge phase (the engine executes
    :func:`rgg_pair_plan` on device instead); kept as the independent
    test oracle the PairPlan path is checked against, and as the paper's
    literal §5.1 protocol: halo cells of neighboring chunks are
    recomputed locally, never communicated.

    Returns (edges [k,2] global ids, local vertex gids, local positions).
    ``chunk_P`` sizes the virtual chunk grid independently of P (the
    instance is a function of the grid; default: the legacy P-coupled
    grid).
    """
    grid = make_grid(n, radius, chunk_P or P, dim)
    counter = CellCounter(seed, grid, n)
    local = local_cells_for_pe(grid, P, pe)
    local_set = set(local)

    # halo = cells within rho of any local cell, not local themselves
    halo: set = set()
    for cell in local:
        for d in _neighbor_offsets(dim, grid.rho):
            nb = tuple(c + o for c, o in zip(cell, d))
            if all(0 <= x < grid.g for x in nb) and nb not in local_set:
                halo.add(nb)
    all_cells = list(local) + sorted(halo)
    index_of = {c: i for i, c in enumerate(all_cells)}

    pos, counts, offsets, cap = points_for_cells(seed, grid, counter, all_cells)
    # (C, cap, 8) f32 blocks; padding rows are +inf so they never pass r^2
    blocks = np.full((len(all_cells), cap, 8), np.inf, dtype=np.float32)
    valid = np.arange(cap)[None, :] < counts[:, None]
    blocks[:, :, :dim] = np.where(valid[:, :, None], pos, np.inf).astype(np.float32)
    padded = jnp.asarray(blocks)
    r2 = radius * radius

    pairs_a, pairs_b = [], []
    for cell in local:
        ia = index_of[cell]
        for delta in _neighbor_offsets(dim, grid.rho):
            nb = tuple(c + o for c, o in zip(cell, delta))
            if not all(0 <= x < grid.g for x in nb):
                continue
            if all(o == 0 for o in delta):
                pairs_a.append(ia), pairs_b.append(ia)
                continue
            nb_local = nb in local_set
            if nb_local and not _is_forward(delta):
                continue  # local-local pair handled once, from the forward side
            pairs_a.append(ia), pairs_b.append(index_of[nb])

    edges_u, edges_v = [], []
    if pairs_a:
        A = padded[jnp.array(pairs_a)]
        B = padded[jnp.array(pairs_b)]
        # the jitted jnp twin of the pairdist kernel (bit-identical to
        # the kernel, asserted in tests)
        fn = jax.jit(jax.vmap(lambda x, y: pairdist_mask_ref(x, y, r2, dim=dim)))
        masks = np.asarray(fn(A, B))
        for k, (ia, ib) in enumerate(zip(pairs_a, pairs_b)):
            mm = masks[k][: counts[ia], : counts[ib]]
            if ia == ib:
                mm = np.triu(mm, k=1)  # i < j within a cell
            ii, jj = np.nonzero(mm)
            if len(ii):
                edges_u.append(offsets[ia] + ii)
                edges_v.append(offsets[ib] + jj)

    if edges_u:
        edges = np.stack([np.concatenate(edges_u), np.concatenate(edges_v)], axis=1)
    else:
        edges = np.zeros((0, 2), dtype=np.int64)

    gids, positions = [], []
    for c in local:
        i = index_of[c]
        gids.append(np.arange(offsets[i], offsets[i] + counts[i]))
        positions.append(pos[i][: counts[i]])
    gids = np.concatenate(gids) if gids else np.zeros(0, np.int64)
    positions = np.concatenate(positions) if positions else np.zeros((0, dim))
    return edges, gids, positions


def grid_point_plan(seed: int, grid: CellGrid, counter: CellCounter, P: int,
                    rng_impl: str = "threefry2x32"):
    """PointPlan over a cube cell grid: every cell exactly once, dealt
    to PEs by Morton chunk (paper §5.1), keyed by cell id so the device
    stream is bit-identical to :func:`points_for_cells`.  Shared by RGG
    and RDG (which only differ in the grid's cell side); reseeding
    re-runs the counter recursion for the new seed against the same
    grid (RGG's :meth:`RggStructure.emit_points` is the fast path)."""
    import dataclasses as _dc

    from ..distrib.engine import POINTS_CUBE, make_point_plan

    base = device_key(seed, _TAG_PTS, impl=rng_impl)
    per_pe = []
    for pe in range(P):
        cells = local_cells_for_pe(grid, P, pe)
        ids = jnp.asarray([grid.cell_id(c) for c in cells], dtype=jnp.int64)
        kd = np.asarray(jax.vmap(jax.random.key_data)(fold_in_many(base, ids)))
        counts = np.array([counter.cell_count(c) for c in cells], np.int64)
        coords = np.asarray(cells, np.int64).reshape(len(cells), grid.dim)
        geom = np.ones((len(cells), 1), np.float64)
        per_pe.append((kd, counts, coords, geom))
    plan = make_point_plan(per_pe, POINTS_CUBE, scale=float(grid.g),
                           dim=grid.dim, rng_impl=rng_impl)
    n = counter.n

    def emit(s: int):
        return grid_point_plan(s, grid, CellCounter(s, grid, n), P, rng_impl)

    return _dc.replace(plan, reseed_fn=emit)


class RggStructure:
    """Seed-independent half of the RGG plan emitters.

    Everything except the binomial counts and the hashed cell keys — the
    split tree, the forward-canonical candidate-pair list, the Morton PE
    deal, the per-PE cell lists — is a pure function of
    (n, radius, chunk grid, P, dim).  :meth:`emit` / :meth:`emit_points`
    fill in the seed-dependent half fully vectorized: one split-tree
    replay plus one batched key dispatch plus numpy scatters, no
    per-pair host work.  The returned plans carry the emit methods as
    their ``reseed_fn``, so a plan-cache hit reseeds in a fraction of
    the cold emission cost (the serve plan cache's attack line (b)).
    """

    def __init__(self, n: int, radius: float, P: int, dim: int = 2,
                 rng_impl: str = "threefry2x32", chunk_P: int = 0):
        from ..distrib.engine import require_counter_rng

        require_counter_rng(rng_impl)
        self.n, self.radius, self.P, self.dim = int(n), float(radius), int(P), int(dim)
        self.rng_impl = rng_impl
        grid = make_grid(n, radius, chunk_P or P, dim)
        self.grid = grid
        self.tree = CellSplitTree(grid)
        g = grid.g
        # row-major cell coordinates (== np.ndindex order)
        coords = np.stack(np.meshgrid(*[np.arange(g, dtype=np.int64)] * dim,
                                      indexing="ij"), -1).reshape(g ** dim, dim)
        self._coords = coords
        self._coords_f = coords.astype(np.float64)
        cc = grid.cells_per_chunk_dim
        bits = grid.cpd.bit_length() - 1
        # batched morton_encode of each cell's chunk, bit-plane at a time
        chunk_of = coords // cc
        code = np.zeros(len(coords), np.int64)
        for b in range(bits):
            for d in range(dim):
                code |= ((chunk_of[:, d] >> b) & 1) << (b * dim + d)
        pe_of_cell = code % P
        # candidate pairs in the cold enumeration order: cells row-major,
        # self pair first, then forward deltas in _neighbor_offsets order
        forward = np.array(
            [d for d in _neighbor_offsets(dim, grid.rho) if _is_forward(d)],
            np.int64).reshape(-1, dim)
        deltas = np.concatenate([np.zeros((1, dim), np.int64), forward])
        nb = coords[:, None, :] + deltas[None, :, :]          # [N, D, dim]
        ok = ((nb >= 0) & (nb < g)).all(axis=-1)              # [N, D]
        strides = g ** np.arange(dim - 1, -1, -1, dtype=np.int64)
        nb_id = (nb * strides).sum(axis=-1)                   # row-major cell id
        N, D = ok.shape
        flat = ok.ravel()  # [N, D] row-major flatten = cell-major, delta-minor
        self._pa_i = np.repeat(np.arange(N, dtype=np.int64), D)[flat]
        self._pa_j = nb_id.ravel()[flat]
        self._pa_self = np.tile(np.arange(D) == 0, N)[flat]
        self._pa_pe = pe_of_cell[self._pa_i]
        self._fp = np.array([float(g), self.radius * self.radius], np.float64)
        # per-PE cell ids in local_cells_for_pe order (PointPlan layout):
        # chunks round-robin in Morton-code order, cells row-major within
        codes = np.arange(grid.cpd ** dim, dtype=np.int64)
        ch = np.zeros((len(codes), dim), np.int64)
        for b in range(bits):
            for d in range(dim):
                ch[:, d] |= ((codes >> (b * dim + d)) & 1) << b
        bc = np.stack(np.meshgrid(*[np.arange(cc, dtype=np.int64)] * dim,
                                  indexing="ij"), -1).reshape(cc ** dim, dim)
        strides = g ** np.arange(dim - 1, -1, -1, dtype=np.int64)
        cid = ((ch[:, None, :] * cc + bc[None, :, :]) * strides).sum(-1)
        self._local_ids = [cid[pe::P].reshape(-1) for pe in range(P)]

    def _keys(self, seed: int) -> np.ndarray:
        """Per-cell key data [num_cells, W], indexed by row-major cell id
        (== :meth:`CellGrid.cell_id`) — one batched fold_in dispatch."""
        base = device_key(seed, _TAG_PTS, impl=self.rng_impl)
        ids = jnp.arange(self.grid.num_cells, dtype=jnp.int64)
        return np.asarray(jax.vmap(jax.random.key_data)(fold_in_many(base, ids)))

    def emit(self, seed: int):
        """PairPlan for ``seed`` — bit-identical to the retired spec-list
        emission (same enumeration order, same table layout, same
        capacity rounding)."""
        import dataclasses as _dc

        from ..distrib.engine import GEOM_TORUS, PairPlan, make_pair_plan
        from .sampling import round_up_capacity

        counts, offsets = self.tree.counts_offsets(seed, self.n)
        ca = counts[self._pa_i]
        inc = (ca > 0) & np.where(self._pa_self, ca > 1,
                                  counts[self._pa_j] > 0)
        if not inc.any():
            plan = make_pair_plan([[] for _ in range(self.P)],
                                  rng_impl=self.rng_impl, dim=self.dim)
            return _dc.replace(plan, reseed_fn=self.emit)
        kd = self._keys(seed)
        ci, cj = self._pa_i[inc], self._pa_j[inc]
        selfp, pe = self._pa_self[inc], self._pa_pe[inc]
        k = ci.size
        # stable rank within each PE group = the per-PE append order
        order = np.argsort(pe, kind="stable")
        sorted_pe = pe[order]
        start = np.searchsorted(sorted_pe, np.arange(self.P))
        col = np.empty(k, np.int64)
        col[order] = np.arange(k, dtype=np.int64) - start[sorted_pe]
        P, dim = self.P, self.dim
        C = int(np.bincount(pe, minlength=P).max())
        W = kd.shape[-1]
        kind = np.zeros((P, C), np.int32)
        key_a = np.zeros((P, C, W), np.uint32)
        key_b = np.zeros((P, C, W), np.uint32)
        count_a = np.zeros((P, C), np.int64)
        count_b = np.zeros((P, C), np.int64)
        gid_a = np.zeros((P, C, 1), np.int64)
        gid_b = np.zeros((P, C, 1), np.int64)
        geom_a = np.ones((P, C, dim), np.float64)  # 1s: make_pair_plan padding
        geom_b = np.ones((P, C, dim), np.float64)
        fparams = np.zeros((P, C, 2), np.float64)
        self_pair = np.zeros((P, C), bool)
        active = np.zeros((P, C), bool)
        kind[pe, col] = GEOM_TORUS
        key_a[pe, col] = kd[ci]
        key_b[pe, col] = kd[cj]
        count_a[pe, col] = counts[ci]
        count_b[pe, col] = counts[cj]
        gid_a[pe, col, 0] = offsets[ci]
        gid_b[pe, col, 0] = offsets[cj]
        geom_a[pe, col] = self._coords_f[ci]
        geom_b[pe, col] = self._coords_f[cj]
        fparams[pe, col] = self._fp
        self_pair[pe, col] = selfp
        active[pe, col] = True
        cap = round_up_capacity(
            max(int(counts[ci].max()), int(counts[cj].max())), mult=8)
        return PairPlan(kind, key_a, key_b, count_a, count_b, gid_a, gid_b,
                        geom_a, geom_b, fparams, self_pair, active, cap,
                        dim, self.rng_impl, reseed_fn=self.emit)

    def emit_points(self, seed: int):
        """PointPlan for ``seed`` — bit-identical to
        :func:`grid_point_plan` over the same grid."""
        import dataclasses as _dc

        from ..distrib.engine import POINTS_CUBE, make_point_plan

        counts, _ = self.tree.counts_offsets(seed, self.n)
        kd = self._keys(seed)
        per_pe = [(kd[ids], counts[ids], self._coords[ids],
                   np.ones((len(ids), 1), np.float64))
                  for ids in self._local_ids]
        plan = make_point_plan(per_pe, POINTS_CUBE, scale=float(self.grid.g),
                               dim=self.dim, rng_impl=self.rng_impl)
        return _dc.replace(plan, reseed_fn=self.emit_points)


@lru_cache(maxsize=8)
def rgg_structure(n: int, radius: float, P: int, dim: int = 2,
                  rng_impl: str = "threefry2x32", chunk_P: int = 0) -> RggStructure:
    """Cached seed-independent :class:`RggStructure` — both cold and
    reseed emissions for a given shape share one instance, so the tree
    build is paid once per (n, radius, P, dim, impl, chunk grid)."""
    return RggStructure(n, radius, P, dim, rng_impl, chunk_P)


def rgg_point_plan(seed: int, n: int, radius: float, P: int, dim: int = 2,
                   rng_impl: str = "threefry2x32", chunk_P: int = 0):
    """PointPlan for the sharded engine over the RGG cell grid: the
    cached :class:`RggStructure` split-tree replay (bit-identical to the
    retained :func:`grid_point_plan` recursion over the same grid)."""
    from .. import obs

    with obs.trace("plan/rgg", phase="plan", family="rgg", reseed=False, P=P):
        return rgg_structure(n, radius, P, dim, rng_impl, chunk_P).emit_points(seed)


def rgg_pair_plan(seed: int, n: int, radius: float, P: int, dim: int = 2,
                  rng_impl: str = "threefry2x32", chunk_P: int = 0):
    """GEOM_TORUS PairPlan: every candidate cell pair exactly once.

    The forward-canonical enumeration of :func:`rgg_pe` made global:
    each cell pairs with itself and with its *forward* neighbors within
    ``rho`` rings, so every unordered cell pair within reach appears
    exactly once — the geometric analog of chunk ownership; per-PE
    outputs concatenate to the exact edge set with no dedup.  Rows are
    dealt to PEs by the Morton chunk that owns the pair's first cell
    (the same deal :func:`local_cells_for_pe` uses), so a PE streams the
    pairs of its own spatial region.

    The device regenerates both cells' points from hashed keys
    (bit-identical to the cube PointPlan / :func:`points_for_cells`
    stream) and runs the float32 r^2 test of the pairdist kernel, so
    the edge set matches the retired host loop exactly.  Empty cells
    emit no rows.  The pair list is a pure function of (seed, grid):
    identical for every P.

    Both cold emission and :meth:`~repro.distrib.engine.PairPlan.reseed`
    replay the cached :class:`RggStructure` — one split-tree pass, one
    batched key dispatch, numpy scatters.  The retired per-cell spec
    walk is retained as :func:`rgg_pair_plan_specs`, the table-layout
    oracle the vectorized path is tested against.
    """
    from .. import obs

    with obs.trace("plan/rgg", phase="plan", family="rgg", reseed=False, P=P):
        return rgg_structure(n, radius, P, dim, rng_impl, chunk_P).emit(seed)


def rgg_pair_plan_specs(seed: int, n: int, radius: float, P: int, dim: int = 2,
                        rng_impl: str = "threefry2x32", chunk_P: int = 0):
    """Retained oracle: the original per-cell spec-list emission of
    :func:`rgg_pair_plan`.  Defines the enumeration order and table
    layout the vectorized :meth:`RggStructure.emit` must reproduce
    bit-for-bit; not a production path."""
    import dataclasses as _dc

    from ..distrib.engine import GEOM_TORUS, PairSpec, make_pair_plan
    from .chunking import morton_encode

    grid = make_grid(n, radius, chunk_P or P, dim)
    counter = CellCounter(seed, grid, n)
    cells = [tuple(c) for c in np.ndindex(*([grid.g] * dim))]
    index_of = {c: i for i, c in enumerate(cells)}
    base = device_key(seed, _TAG_PTS, impl=rng_impl)
    ids = jnp.asarray([grid.cell_id(c) for c in cells], dtype=jnp.int64)
    kd = np.asarray(jax.vmap(jax.random.key_data)(fold_in_many(base, ids)))
    counts = np.array([counter.cell_count(c) for c in cells], np.int64)
    offsets = np.array([counter.cell_offset(c) for c in cells], np.int64)

    cc = grid.cells_per_chunk_dim
    bits = grid.cpd.bit_length() - 1
    fp = (float(grid.g), float(radius) * float(radius))
    forward = [d for d in _neighbor_offsets(dim, grid.rho) if _is_forward(d)]

    per_pe: List[List[PairSpec]] = [[] for _ in range(P)]
    for ci, cell in enumerate(cells):
        if counts[ci] == 0:
            continue
        pe = morton_encode(tuple(x // cc for x in cell), dim, bits) % P

        def pair(cj: int, self_pair: bool) -> PairSpec:
            return PairSpec(  # repro: allow(no-per-chunk-host-loop) retained oracle
                GEOM_TORUS, kd[ci], kd[cj], int(counts[ci]), int(counts[cj]),
                int(offsets[ci]), int(offsets[cj]),
                tuple(float(x) for x in cell),
                tuple(float(x) for x in cells[cj]),
                fparams=fp, self_pair=self_pair)

        if counts[ci] > 1:
            per_pe[pe].append(pair(ci, True))
        for delta in forward:
            nb = tuple(c + o for c, o in zip(cell, delta))
            if not all(0 <= x < grid.g for x in nb):
                continue
            cj = index_of[nb]
            if counts[cj]:
                per_pe[pe].append(pair(cj, False))
    plan = make_pair_plan(per_pe, rng_impl=rng_impl, dim=dim)
    structure = rgg_structure(n, radius, P, dim, rng_impl, chunk_P)
    return _dc.replace(plan, reseed_fn=structure.emit)


def rgg_union(seed: int, n: int, radius: float, P: int, dim: int = 2) -> np.ndarray:
    """Distinct undirected edge union over all PEs (canonical u>v)."""
    es = []
    for pe in range(P):
        e, _, _ = rgg_pe(seed, n, radius, P, pe, dim)
        es.append(e)
    e = np.concatenate(es, axis=0)
    if e.size == 0:
        return e.reshape(0, 2)
    u = np.maximum(e[:, 0], e[:, 1])
    v = np.minimum(e[:, 0], e[:, 1])
    return np.unique(np.stack([u, v], axis=1), axis=0)  # repro: allow(no-numpy-unique) test-oracle union (engine dedups by pair ownership)


def rgg_all_points(seed: int, n: int, radius: float, P: int, dim: int = 2):
    """Every vertex (gid-ordered) — oracle input for brute-force tests."""
    grid = make_grid(n, radius, P, dim)
    counter = CellCounter(seed, grid, n)
    cells = [tuple(c) for c in np.ndindex(*([grid.g] * dim))]
    pos, counts, offsets, cap = points_for_cells(seed, grid, counter, cells)
    out = np.zeros((n, dim))
    for i, c in enumerate(cells):
        out[offsets[i]: offsets[i] + counts[i]] = pos[i][: counts[i]]
    return out


def rgg_brute_edges(points: np.ndarray, radius: float) -> np.ndarray:
    d2 = ((points[:, None, :] - points[None, :, :]) ** 2).sum(-1)
    u, v = np.nonzero(np.tril(d2 <= radius * radius, k=-1))
    return np.stack([u, v], axis=1)
