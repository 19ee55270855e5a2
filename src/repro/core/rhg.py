"""Threshold random hyperbolic graphs (paper §7).

Partition (Fig. 3): a central *core* disk [0, R/2] (the paper's merged
clique annuli — any two points with r <= R/2 are adjacent), plus
equal-height concentric annuli over [R/2, R].  Each annulus is split
angularly into P chunks and further into equal-width cells holding an
expected constant number of vertices.

Communication-free plan: per-annulus counts are a multinomial drawn via
dependent binomials (§7.1); within an annulus, per-cell counts come from
a hashed 1-D binomial recursion (`RangeCounter`).  Any PE can regenerate
any cell bit-identically, so neighborhood queries recompute remote cells
instead of communicating (inward/outward queries).

Adjacency tests use the trig-free precompute (§7.2.1, Eq. 9) evaluated
by the `hypdist` Pallas kernel; candidate windows per (vertex, annulus)
use the Δθ bound (Eq. 8) whose overestimation is bounded by OE(·) ≤ √e
(Cor. 11) — so candidate work stays O(m).
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import numpy as np

from ..kernels.hypdist.ops import (
    FEAT,
    cosh_threshold,
    pad_features,
    precompute_features,
)
from ..kernels.hypdist.ref import hypdist_mask_ref

import jax as _jax
import jax.numpy as _jnp

_ref_jit = None


def _hyp_ref(q, c, cosh_r):
    global _ref_jit
    if _ref_jit is None:
        import jax
        _ref_jit = jax.jit(hypdist_mask_ref)
    return _ref_jit(_jnp.asarray(q), _jnp.asarray(c), cosh_r)
from .prng import (PhiloxReplayer, device_key, fold_in_many, hash_paths,
                   host_rng)
from .variates import binomial, multinomial_split

_TAG_ANN, _TAG_CELLS, _TAG_V = 31, 32, 33
_TAG_V_DEV = 34  # device-side vertex stream (sharded engine)
_TAG_V_ENG = 35  # device vertex stream, P-independent engine cell layout
_TAG_CELLS_ENG = 36  # RangeCounter streams for the engine cell layout
_CELL_OCC = 8  # expected vertices per cell (paper's tuning constant)


@dataclass(frozen=True)
class RHGParams:
    n: int
    avg_deg: float
    gamma: float
    seed: int

    @property
    def alpha(self) -> float:
        return (self.gamma - 1.0) / 2.0

    @property
    def C(self) -> float:
        xi = self.alpha / (self.alpha - 0.5)
        return -2.0 * math.log(self.avg_deg * math.pi / (2.0 * xi * xi))

    @property
    def R(self) -> float:
        return 2.0 * math.log(self.n) + self.C


def expected_tail_exponent(params: RHGParams) -> float:
    """Degree-distribution power-law exponent: 2*alpha + 1 == gamma.

    Gugelmann et al.: the threshold RHG degree sequence follows a power
    law with exponent 2*alpha + 1, which the alpha = (gamma-1)/2
    parametrization pins to the requested gamma — the closed-form law
    repro.stats validates fitted tail exponents against (paper §7).
    """
    return 2.0 * params.alpha + 1.0


def expected_avg_degree(params: RHGParams) -> float:
    """Expected average degree: the constant C (Eq. 4) is calibrated as
    C = -2 ln(avg_deg * pi / (2 xi^2)), the inverse of the asymptotic
    mean-degree formula — so the model's expectation *is* the requested
    ``avg_deg`` (up to o(1) finite-size terms)."""
    return float(params.avg_deg)


def _cdf(params: RHGParams, r: float) -> float:
    """mu(B_r(0)) = (cosh(alpha r) - 1)/(cosh(alpha R) - 1)  (Eq. A.2)."""
    a = params.alpha
    return (math.cosh(a * r) - 1.0) / (math.cosh(a * params.R) - 1.0)


def _inv_cdf_interval(params: RHGParams, lo: float, hi: float, u: np.ndarray) -> np.ndarray:
    """Inverse radial CDF restricted to [lo, hi)."""
    a = params.alpha
    clo, chi = np.cosh(a * lo), np.cosh(a * hi)
    return np.arccosh(clo + u * (chi - clo)) / a


def annuli_boundaries(params: RHGParams) -> np.ndarray:
    """[R/2 = l_0 < l_1 < ... < l_k = R], constant height ~ ln2/alpha."""
    half = params.R / 2.0
    k = max(1, int(params.alpha * half / math.log(2.0)))
    return half + np.arange(k + 1) * (half / k)


def region_counts(params: RHGParams) -> Tuple[int, np.ndarray, np.ndarray]:
    """(core count, per-annulus counts, boundaries) — identical on all PEs."""
    bounds = annuli_boundaries(params)
    probs = [_cdf(params, bounds[0])]
    for i in range(len(bounds) - 1):
        probs.append(_cdf(params, bounds[i + 1]) - _cdf(params, bounds[i]))
    probs = np.asarray(probs)
    counts = multinomial_split(host_rng(params.seed, _TAG_ANN), params.n, probs)
    return int(counts[0]), counts[1:], bounds


class RangeCounter:
    """1-D hashed binomial recursion over [0, units): per-cell counts and
    recursion-order (== angular-order) vertex-id offsets."""

    def __init__(self, seed: int, tag: int, annulus: int, units: int, total: int):
        self.seed, self.tag, self.annulus, self.units = seed, tag, annulus, units
        self._memo: Dict[Tuple[int, int], int] = {(0, units): total}

    def _children(self, lo: int, hi: int) -> Tuple[int, int]:
        mid = (lo + hi) // 2
        key_l = (lo, mid)
        if key_l not in self._memo:
            cp = self.count(lo, hi)
            rng = host_rng(self.seed, self.tag, self.annulus, lo, hi)
            cl = binomial(rng, cp, (mid - lo) / (hi - lo))
            self._memo[key_l] = cl
            self._memo[(mid, hi)] = cp - cl
        return self._memo[key_l], self._memo[(mid, hi)]

    def count(self, lo: int, hi: int) -> int:
        if (lo, hi) in self._memo:
            return self._memo[(lo, hi)]
        # descend from the smallest memoized ancestor
        clo, chi = 0, self.units
        while (clo, chi) != (lo, hi):
            mid = (clo + chi) // 2
            self._children(clo, chi)
            if hi <= mid:
                chi = mid
            elif lo >= mid:
                clo = mid
            else:
                raise AssertionError("query range must align with recursion")
        return self._memo[(lo, hi)]

    def cell_count(self, i: int) -> int:
        return self.count(i, i + 1)

    def cell_offset(self, i: int) -> int:
        clo, chi, off = 0, self.units, 0
        while chi - clo > 1:
            mid = (clo + chi) // 2
            left, _ = self._children(clo, chi)
            if i < mid:
                chi = mid
            else:
                off += left
                clo = mid
        return off


def _range_table(seed: int, tag: int, annulus: int, units: int,
                 total: int) -> Tuple[np.ndarray, np.ndarray]:
    """Level-synchronous replay of the :class:`RangeCounter` recursion:
    (per-cell counts, per-cell vertex-id offsets) over [0, units).

    Every interval's split draw comes from its own hashed generator
    (``host_rng(seed, tag, annulus, lo, hi)``), so the draws can be
    replayed level by level — one batched :func:`hash_paths` per level
    plus the identical scalar Binomials — and remain bit-identical to
    the memoized descent for every cell."""
    cnt_cells = np.zeros(units, np.int64)
    off_cells = np.zeros(units, np.int64)
    lo = np.array([0], np.int64)
    hi = np.array([units], np.int64)
    cnt = np.array([total], np.int64)
    off = np.array([0], np.int64)
    rep = PhiloxReplayer()
    while True:
        leaf = (hi - lo) == 1
        if leaf.any():
            cnt_cells[lo[leaf]] = cnt[leaf]
            off_cells[lo[leaf]] = off[leaf]
        keep = ~leaf
        if not keep.any():
            return cnt_cells, off_cells
        plo, phi = lo[keep], hi[keep]
        pc, po = cnt[keep], off[keep]
        mid = (plo + phi) // 2
        m = len(plo)
        paths = np.stack([np.full(m, tag, np.int64),
                          np.full(m, annulus, np.int64), plo, phi], axis=1)
        hashes = hash_paths(seed, paths)
        cl = np.empty(m, np.int64)
        for i in range(m):
            c = int(pc[i])
            if c:  # binomial(rng, 0, p) == 0 without consuming draws
                cl[i] = binomial(rep.at(hashes[i]), c,
                                 (int(mid[i]) - int(plo[i]))
                                 / (int(phi[i]) - int(plo[i])))
            else:
                cl[i] = 0
        lo = np.empty(2 * m, np.int64)
        hi = np.empty(2 * m, np.int64)
        cnt = np.empty(2 * m, np.int64)
        off = np.empty(2 * m, np.int64)
        lo[0::2], hi[0::2], cnt[0::2], off[0::2] = plo, mid, cl, po
        lo[1::2], hi[1::2], cnt[1::2], off[1::2] = mid, phi, pc - cl, po + cl


@dataclass
class _Annulus:
    idx: int
    lo: float
    hi: float
    count: int
    cells: int          # U_b, a multiple of P
    counter: RangeCounter
    gid0: int           # global id offset of this annulus

    @property
    def cell_width(self) -> float:
        return 2.0 * math.pi / self.cells


class RHGPlan:
    """Shared deterministic plan — every PE derives the identical one."""

    def __init__(self, params: RHGParams, P: int):
        self.params, self.P = params, P
        self.n_core, ann_counts, self.bounds = region_counts(params)
        self.annuli: List[_Annulus] = []
        gid = self.n_core
        for b, cnt in enumerate(ann_counts):
            cells = P * max(1, int(cnt) // (_CELL_OCC * P))
            ctr = RangeCounter(params.seed, _TAG_CELLS, b, cells, int(cnt))
            self.annuli.append(
                _Annulus(b, float(self.bounds[b]), float(self.bounds[b + 1]),
                         int(cnt), cells, ctr, gid)
            )
            gid += int(cnt)

    # ---------------- vertex generation (hash-keyed, recomputable) --------

    def core_vertices(self) -> Tuple[np.ndarray, np.ndarray]:
        rng = host_rng(self.params.seed, _TAG_V, -1, 0)
        u = rng.random(self.n_core)
        theta = rng.random(self.n_core) * 2.0 * math.pi
        r = _inv_cdf_interval(self.params, 0.0, self.params.R / 2.0, u)
        return r, theta

    def cell_vertices(self, b: int, cell: int) -> Tuple[np.ndarray, np.ndarray, int]:
        """(radii, angles, gid0) of one cell — identical from any PE."""
        ann = self.annuli[b]
        cnt = ann.counter.cell_count(cell)
        rng = host_rng(self.params.seed, _TAG_V, b, cell)
        u = rng.random(cnt)
        theta = (cell + rng.random(cnt)) * ann.cell_width
        r = _inv_cdf_interval(self.params, ann.lo, ann.hi, u)
        return r, theta, ann.gid0 + ann.counter.cell_offset(cell)


def delta_theta(r: np.ndarray, ell: float, R: float) -> np.ndarray:
    """Max angular deviation for a neighbor at radius >= ell (Eq. A.3)."""
    r = np.asarray(r, np.float64)
    with np.errstate(invalid="ignore", divide="ignore"):
        arg = (np.cosh(r) * math.cosh(ell) - math.cosh(R)) / (np.sinh(r) * math.sinh(ell))
    out = np.where(r + ell < R, math.pi, np.arccos(np.clip(arg, -1.0, 1.0)))
    return out


def _adjacency(q_feat: np.ndarray, c_feat: np.ndarray,
               cosh_r: float) -> np.ndarray:
    """Edge mask via the jitted jnp twin of the hypdist kernel (padded
    to 128 blocks; bit-identical to the kernel, asserted in tests)."""
    qp = pad_features(q_feat)
    cp = pad_features(c_feat)
    mask = np.asarray(_hyp_ref(qp, cp, cosh_r))
    return mask[: len(q_feat), : len(c_feat)].astype(bool)


def rhg_pe(
    params: RHGParams, P: int, pe: int,
    batch: int = 512,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """All edges incident to PE `pe`'s vertices, communication-free.

    Returns (edges [k,2], local gids, local radii, local angles).
    """
    plan = RHGPlan(params, P)
    R, coshR = params.R, cosh_threshold(params.R)
    chunk_lo, chunk_hi = pe * 2 * math.pi / P, (pe + 1) * 2 * math.pi / P

    # ---- core (recomputed redundantly on every PE, paper §7.1) ----------
    core_r, core_theta = plan.core_vertices()
    core_feat = precompute_features(core_r, core_theta)
    core_gids = np.arange(plan.n_core)
    core_local = (core_theta >= chunk_lo) & (core_theta < chunk_hi)

    # ---- local vertices per annulus -------------------------------------
    local: Dict[int, Tuple[np.ndarray, ...]] = {}
    for ann in plan.annuli:
        cpc = ann.cells // P
        rs, ts, gs = [], [], []
        for cell in range(pe * cpc, (pe + 1) * cpc):
            r, t, g0 = plan.cell_vertices(ann.idx, cell)
            rs.append(r), ts.append(t), gs.append(g0 + np.arange(len(r)))
        r = np.concatenate(rs) if rs else np.zeros(0)
        t = np.concatenate(ts) if ts else np.zeros(0)
        g = np.concatenate(gs) if gs else np.zeros(0, np.int64)
        local[ann.idx] = (r, t, g)

    edges_u: List[np.ndarray] = []
    edges_v: List[np.ndarray] = []

    def emit(mask: np.ndarray, qg: np.ndarray, cg: np.ndarray):
        ii, jj = np.nonzero(mask)
        if len(ii):
            u, v = qg[ii], cg[jj]
            keep = u != v
            edges_u.append(u[keep])
            edges_v.append(v[keep])

    # ---- core-core: a clique by the triangle inequality (r_u + r_v < R),
    # but checked through the same Eq. 9 path so borderline float rounding
    # can never disagree with the oracle/other PEs.
    if plan.n_core > 1 and core_local.any():
        m = _adjacency(core_feat[core_local], core_feat, coshR)
        emit(m, core_gids[core_local], core_gids)

    # ---- queries: local vertices (incl. owned core) vs every region ----
    query_sets = [(core_r[core_local], core_theta[core_local], core_gids[core_local])]
    query_sets += [local[a] for a in local]

    # cache of regenerated remote cells per annulus
    cell_cache: Dict[Tuple[int, int], Tuple[np.ndarray, np.ndarray, int]] = {}

    def get_cell(b: int, cell: int):
        key = (b, cell)
        if key not in cell_cache:
            cell_cache[key] = plan.cell_vertices(b, cell)
        return cell_cache[key]

    for (qr, qt, qg) in query_sets:
        if len(qr) == 0:
            continue
        q_feat_all = precompute_features(qr, qt)

        # vs core candidates (inward query; no window needed — core is tiny)
        if plan.n_core > 0:
            for s in range(0, len(qr), batch):
                sl = slice(s, s + batch)
                emit(_adjacency(q_feat_all[sl], core_feat, coshR), qg[sl], core_gids)

        # vs each annulus (inward + outward unified)
        for ann in plan.annuli:
            if ann.count == 0:
                continue
            dth = delta_theta(qr, ann.lo, R)
            w = ann.cell_width
            lo_cell = np.floor((qt - dth) / w).astype(np.int64)
            hi_cell = np.floor((qt + dth) / w).astype(np.int64)
            span = np.minimum(hi_cell - lo_cell + 1, ann.cells)
            L = int(span.max())
            for s in range(0, len(qr), batch):
                sl = slice(s, s + batch)
                q_feat = q_feat_all[sl]
                cand_feats, cand_gids = [], []
                # gather candidate cells for this batch (dedup per batch)
                needed = {}
                for qi in range(*sl.indices(len(qr))):
                    for j in range(int(span[qi])):
                        c = (lo_cell[qi] + j) % ann.cells
                        needed[c] = True
                for c in needed:
                    r, t, g0 = get_cell(ann.idx, int(c))
                    if len(r):
                        cand_feats.append(precompute_features(r, t))
                        cand_gids.append(g0 + np.arange(len(r)))
                if not cand_feats:
                    continue
                c_feat = np.concatenate(cand_feats)
                c_gid = np.concatenate(cand_gids)
                emit(_adjacency(q_feat, c_feat, coshR), qg[sl], c_gid)

    if edges_u:
        e = np.stack([np.concatenate(edges_u), np.concatenate(edges_v)], axis=1)
        u = np.maximum(e[:, 0], e[:, 1])
        v = np.minimum(e[:, 0], e[:, 1])
        e = np.unique(np.stack([u, v], axis=1), axis=0)  # repro: allow(no-numpy-unique) test-oracle union (engine dedups by pair ownership)
    else:
        e = np.zeros((0, 2), dtype=np.int64)

    lg = [core_gids[core_local]] + [local[a][2] for a in local]
    lr = [core_r[core_local]] + [local[a][0] for a in local]
    lt = [core_theta[core_local]] + [local[a][1] for a in local]
    return e, np.concatenate(lg), np.concatenate(lr), np.concatenate(lt)


def rhg_point_plan(params: RHGParams, P: int):
    """PointPlan for the sharded engine: every annulus cell exactly once.

    Cell geometry, per-cell counts and gid offsets are the host
    ``RHGPlan`` tables (so counts match the reference bit-for-bit); the
    (r, theta) draws come from the device-side fold_in stream keyed on
    (annulus, cell) — distribution-identical to the host Philox path and
    recomputable by any PE, which is the communication-free invariant.
    """
    from ..distrib.engine import POINTS_POLAR, make_point_plan

    plan = RHGPlan(params, P)
    a = params.alpha
    base = device_key(params.seed, _TAG_V_DEV)
    per_pe = []
    for pe in range(P):
        kds, counts, cells, geoms = [], [], [], []
        for ann in plan.annuli:
            cpc = ann.cells // P
            lo_cell, hi_cell = pe * cpc, (pe + 1) * cpc
            if hi_cell == lo_cell:
                continue
            ann_key = _jax.random.fold_in(base, ann.idx)
            ids = _jnp.arange(lo_cell, hi_cell, dtype=_jnp.int64)
            kds.append(np.asarray(_jax.vmap(_jax.random.key_data)(fold_in_many(ann_key, ids))))
            counts.extend(ann.counter.cell_count(c) for c in range(lo_cell, hi_cell))
            cells.extend((ann.idx, c) for c in range(lo_cell, hi_cell))
            geoms.extend(
                (math.cosh(a * ann.lo), math.cosh(a * ann.hi), ann.cell_width)
                for _ in range(lo_cell, hi_cell)
            )
        kd = np.concatenate(kds, axis=0) if kds else np.zeros((0, 2), np.uint32)
        per_pe.append((
            kd,
            np.asarray(counts, np.int64),
            np.asarray(cells, np.int64).reshape(len(counts), 2),
            np.asarray(geoms, np.float64).reshape(len(counts), 3),
        ))
    out = make_point_plan(per_pe, POINTS_POLAR, scale=a, dim=2)
    # RHG structure (annuli, cells-per-ring) is itself seed-dependent
    # (multinomial region counts size the cell grids): reseed re-emits
    return dataclasses.replace(
        out, reseed_fn=lambda s: rhg_point_plan(
            dataclasses.replace(params, seed=s), P))


# --------------------------------------------------------------------------
# engine cell layout + edge (candidate-pair) plan for distrib.engine
# --------------------------------------------------------------------------
#
# The per-PE reference generator above couples its cell grid to P
# (`cells = P * max(1, cnt // (_CELL_OCC * P))`), so its output is only
# comparable at a fixed P.  The engine layout below is *P-independent*:
# the same annuli, region counts and cells for every P, with P only
# deciding which PE executes which cell/pair — so `api.generate` yields
# the identical edge set on 1, 2 or 4096 PEs.  The core disk is one
# more "cell" (index 0, angular width 2*pi), putting the whole vertex
# set on the device-side hashed stream.

@dataclass(frozen=True)
class EngineCell:
    """One cell of the P-independent device layout."""
    ring: int       # 0 = core disk, 1 + b for annulus b
    cell: int       # angular index within the ring
    clo: float      # cosh(alpha * r_lo)
    chi: float      # cosh(alpha * r_hi)
    width: float    # angular cell width
    count: int
    gid0: int
    key_data: np.ndarray  # uint32 [W]


@dataclass(frozen=True)
class RhgEngineTable:
    """The P-independent cell layout as flat columns (one row per cell,
    ring-major, == the :func:`rhg_engine_cells` list order)."""
    ring: np.ndarray        # int64 [N]
    cell: np.ndarray        # int64 [N] angular index within the ring
    clo: np.ndarray         # f64 [N] cosh(alpha * r_lo)
    chi: np.ndarray         # f64 [N] cosh(alpha * r_hi)
    width: np.ndarray       # f64 [N] angular cell width
    count: np.ndarray       # int64 [N]
    gid0: np.ndarray        # int64 [N]
    key_data: np.ndarray    # uint32 [N, W]
    ring_lo: np.ndarray     # f64 [rings] inner radius (0.0 for the core)
    ring_start: np.ndarray  # int64 [rings] first row of each ring
    ring_k: np.ndarray      # int64 [rings] cells per ring
    ring_width: np.ndarray  # f64 [rings]


def rhg_engine_table(params: RHGParams,
                     rng_impl: str = "threefry2x32") -> RhgEngineTable:
    """Vectorized :func:`rhg_engine_cells`: one level-synchronous
    :func:`_range_table` replay per ring, one batched key dispatch over
    every cell, numpy column assembly — bit-identical rows in the same
    ring-major order."""
    n_core, ann_counts, bounds = region_counts(params)
    a = params.alpha
    B = len(ann_counts)
    ks = np.maximum(1, ann_counts.astype(np.int64) // _CELL_OCC)
    cnts, offs = [], []
    for b in range(B):
        c, o = _range_table(params.seed, _TAG_CELLS_ENG, b, int(ks[b]),
                            int(ann_counts[b]))
        cnts.append(c)
        offs.append(o)
    one = np.ones(1, np.int64)
    ring = np.concatenate([0 * one, np.repeat(np.arange(1, B + 1), ks)])
    cell = np.concatenate([0 * one] + [np.arange(k, dtype=np.int64) for k in ks])
    count = np.concatenate([n_core * one] + cnts)
    gid_ring = n_core + np.concatenate(
        [np.zeros(1, np.int64), np.cumsum(ann_counts.astype(np.int64))[:-1]])
    gid0 = np.concatenate([0 * one] +
                          [gid_ring[b] + offs[b] for b in range(B)])
    # math.cosh, not np.cosh: the SIMD variant can differ by 1 ulp from
    # the libm scalar the oracle rows were built with
    ring_clo = np.array([1.0] + [math.cosh(a * float(x))
                                 for x in bounds[:-1]])
    ring_chi = np.array([math.cosh(a * params.R / 2.0)]
                        + [math.cosh(a * float(x)) for x in bounds[1:]])
    ring_width = np.concatenate([[2.0 * math.pi], 2.0 * math.pi / ks])
    base = device_key(params.seed, _TAG_V_ENG, impl=rng_impl)
    keys = _jax.vmap(_jax.random.fold_in)(
        fold_in_many(base, _jnp.asarray(ring)), _jnp.asarray(cell))
    key_data = np.asarray(_jax.vmap(_jax.random.key_data)(keys))
    return RhgEngineTable(
        ring=ring, cell=cell,
        clo=ring_clo[ring], chi=ring_chi[ring], width=ring_width[ring],
        count=count, gid0=gid0, key_data=key_data,
        ring_lo=np.concatenate([[0.0], bounds[:-1]]),
        ring_start=np.concatenate([0 * one,
                                   1 + np.concatenate([np.zeros(1, np.int64),
                                                       np.cumsum(ks)[:-1]])]),
        ring_k=np.concatenate([one, ks]),
        ring_width=ring_width)


def rhg_engine_cells(params: RHGParams, rng_impl: str = "threefry2x32"):
    """(cells, ring_lo) — the P-independent cell table.

    ``ring_lo[r]`` is ring r's inner radius (0.0 for the core), the
    quantity the cell-level Delta-theta candidate bound needs.

    Retained oracle: defines the row order and values the vectorized
    :func:`rhg_engine_table` must reproduce bit-for-bit; the production
    emitters consume the table."""
    n_core, ann_counts, bounds = region_counts(params)
    a = params.alpha
    base = device_key(params.seed, _TAG_V_ENG, impl=rng_impl)

    def kd(ring, cell):
        k = _jax.random.fold_in(_jax.random.fold_in(base, ring), cell)
        return np.asarray(_jax.random.key_data(k)).ravel()

    cells = [EngineCell(0, 0, 1.0, math.cosh(a * params.R / 2.0),
                        2.0 * math.pi, n_core, 0, kd(0, 0))]
    ring_lo = [0.0]
    gid = n_core
    for b, cnt in enumerate(ann_counts):
        k = max(1, int(cnt) // _CELL_OCC)
        ctr = RangeCounter(params.seed, _TAG_CELLS_ENG, b, k, int(cnt))
        lo, hi = float(bounds[b]), float(bounds[b + 1])
        width = 2.0 * math.pi / k
        clo, chi = math.cosh(a * lo), math.cosh(a * hi)
        ring_keys = _jax.vmap(_jax.random.key_data)(
            fold_in_many(_jax.random.fold_in(base, b + 1),
                         _jnp.arange(k, dtype=_jnp.int64)))
        ring_keys = np.asarray(ring_keys)
        for c in range(k):
            cells.append(EngineCell(b + 1, c, clo, chi, width,
                                    ctr.cell_count(c),
                                    gid + ctr.cell_offset(c), ring_keys[c]))
        ring_lo.append(lo)
        gid += int(cnt)
    return cells, ring_lo


def rhg_engine_point_plan(params: RHGParams, P: int, rng_impl: str = "threefry2x32"):
    """PointPlan over the engine cell layout (core included), cells
    dealt round-robin by global index."""
    from .. import obs
    from ..distrib.engine import POINTS_POLAR, make_point_plan

    with obs.trace("plan/rhg", phase="plan", family="rhg", reseed=False, P=P):
        t = rhg_engine_table(params, rng_impl)
        per_pe = []
        for pe in range(P):
            sl = slice(pe, None, P)
            per_pe.append((
                t.key_data[sl],
                t.count[sl],
                np.stack([t.ring[sl], t.cell[sl]], axis=1),
                np.stack([t.clo[sl], t.chi[sl], t.width[sl]], axis=1),
            ))
        out = make_point_plan(per_pe, POINTS_POLAR, scale=params.alpha, dim=2,
                              rng_impl=rng_impl)
        return dataclasses.replace(
            out, reseed_fn=lambda s: rhg_engine_point_plan(
                dataclasses.replace(params, seed=s), P, rng_impl))


def rhg_engine_all_points(params: RHGParams, rng_impl: str = "threefry2x32") -> np.ndarray:
    """Every engine-layout vertex as (r, theta) in gid order."""
    from ..distrib.engine import run_points

    cells, _ = rhg_engine_cells(params, rng_impl)
    pts, mask, _ = run_points(rhg_engine_point_plan(params, 1, rng_impl), check=False)
    out = np.zeros((params.n, 2))
    for i, c in enumerate(cells):
        out[c.gid0: c.gid0 + c.count] = pts[0, i][: c.count]
    return out


def rhg_pair_plan(params: RHGParams, P: int, rng_impl: str = "threefry2x32"):
    """PairPlan: every candidate cell pair exactly once, dealt to PEs.

    Candidates come from the cell-level Delta-theta bound (Eq. 8
    evaluated at both cells' inner radii, the maximal angular reach
    over their contents), so every adjacent vertex pair is covered and
    candidate work stays near-linear (Cor. 11).  The enumeration is a
    pure function of the spec — every PE derives the identical global
    pair list and executes its slice, which makes the union exact for
    any P with zero communication.

    Emission is fully vectorized: ring-pair candidate windows become
    2-D index grids, deduped by sorting pair codes (the retired
    set-based walk is retained as :func:`rhg_pair_plan_specs`, the
    table-layout oracle).  The enumeration itself depends on the seed
    (region counts size the rings), so reseed re-emits — at the same
    vectorized cost."""
    from .. import obs
    from ..distrib.engine import GEOM_HYP, pair_plan_from_columns

    with obs.trace("plan/rhg", phase="plan", family="rhg", reseed=False, P=P):
        t = rhg_engine_table(params, rng_impl)
        code = _pair_codes(t, params.R)
        N = len(t.ring)
        ia, ib = code // N, code % N
        k = ia.size
        fp = np.broadcast_to(
            np.array([params.alpha, cosh_threshold(params.R)]), (k, 2))
        geom_a = np.stack([t.clo[ia], t.chi[ia],
                           t.cell[ia].astype(np.float64), t.width[ia]], axis=1)
        geom_b = np.stack([t.clo[ib], t.chi[ib],
                           t.cell[ib].astype(np.float64), t.width[ib]], axis=1)
        out = pair_plan_from_columns(
            P, ia % P, np.full(k, GEOM_HYP, np.int32),
            t.key_data[ia], t.key_data[ib], t.count[ia], t.count[ib],
            t.gid0[ia][:, None], t.gid0[ib][:, None], geom_a, geom_b,
            fp, ia == ib, rng_impl=rng_impl)
        return dataclasses.replace(
            out, reseed_fn=lambda s: rhg_pair_plan(
                dataclasses.replace(params, seed=s), P, rng_impl))


def _pair_codes(t: RhgEngineTable, R: float) -> np.ndarray:
    """Candidate cell-pair codes ``max(i1,i2) * N + min(i1,i2)``,
    deduped and ascending (== ``sorted(pairs)`` of the set-based walk).

    One 2-D index grid per ring pair: within a ring the window is a
    fixed span around each cell; across rings it is the Delta-theta
    window of each cell's angular extent, with full-ring fallback when
    the window wraps."""
    N = len(t.ring)
    rings = len(t.ring_k)
    codes: List[np.ndarray] = []
    for r1 in range(rings):
        k1, w1 = int(t.ring_k[r1]), float(t.ring_width[r1])
        s1, lo1 = int(t.ring_start[r1]), float(t.ring_lo[r1])
        c1 = np.arange(k1, dtype=np.int64)
        for r2 in range(r1 + 1):
            k2, w2 = int(t.ring_k[r2]), float(t.ring_width[r2])
            s2, lo2 = int(t.ring_start[r2]), float(t.ring_lo[r2])
            if lo1 + lo2 < R:
                dth = math.pi
            else:
                dth = float(delta_theta(np.array([lo1]), lo2, R)[0])
            if r1 == r2:
                span = min(int(dth / w1) + 1, k1)
                j = np.arange(span + 1, dtype=np.int64)
                i1 = (s1 + c1)[:, None]
                i2 = s1 + (c1[:, None] + j[None, :]) % k1
                codes.append((np.maximum(i1, i2) * N
                              + np.minimum(i1, i2)).ravel())
                continue
            lo_c = np.floor((c1 * w1 - dth) / w2).astype(np.int64)
            hi_c = np.floor(((c1 + 1) * w1 + dth) / w2).astype(np.int64)
            span = hi_c - lo_c + 1
            full = span >= k2
            # s1 > s2 + k2 here, so i1 > i2 always: i1 is the code's major
            if full.any():
                i1 = (s1 + c1[full])[:, None]
                i2 = (s2 + np.arange(k2, dtype=np.int64))[None, :]
                codes.append((i1 * N + i2).ravel())
            part = ~full
            if part.any():
                S = int(span[part].max())
                j = np.arange(S, dtype=np.int64)
                i2 = s2 + (lo_c[part][:, None] + j[None, :]) % k2
                i1 = np.broadcast_to((s1 + c1[part])[:, None], i2.shape)
                ok = j[None, :] < span[part][:, None]
                codes.append((i1 * N + i2)[ok].ravel())
    allc = np.sort(np.concatenate(codes))
    keep = np.ones(len(allc), bool)
    keep[1:] = allc[1:] != allc[:-1]
    return allc[keep]


def rhg_pair_plan_specs(params: RHGParams, P: int,
                        rng_impl: str = "threefry2x32"):
    """Retained oracle: the original set-based candidate walk of
    :func:`rhg_pair_plan`.  Defines the pair order and table layout the
    vectorized path must reproduce bit-for-bit; not a production path."""
    from ..distrib.engine import GEOM_HYP, PairSpec, make_pair_plan

    cells, ring_lo = rhg_engine_cells(params, rng_impl)
    R = params.R
    rings: List[List[EngineCell]] = [[] for _ in ring_lo]
    for c in cells:
        rings[c.ring].append(c)

    pairs = set()
    for r1 in range(len(rings)):
        k1 = len(rings[r1])
        w1 = rings[r1][0].width
        for r2 in range(r1 + 1):
            k2 = len(rings[r2])
            w2 = rings[r2][0].width
            lo1, lo2 = ring_lo[r1], ring_lo[r2]
            if lo1 + lo2 < R:
                dth = math.pi
            else:
                dth = float(delta_theta(np.array([lo1]), lo2, R)[0])
            for c1 in range(k1):
                if r1 == r2:
                    span = min(int(dth / w1) + 1, k1)
                    cands = range(c1, c1 + span + 1)
                else:
                    lo_c = math.floor((c1 * w1 - dth) / w2)
                    hi_c = math.floor(((c1 + 1) * w1 + dth) / w2)
                    if hi_c - lo_c + 1 >= k2:
                        cands = range(k2)
                    else:
                        cands = range(lo_c, hi_c + 1)
                i1 = _cell_index(rings, r1, c1)
                for c2 in cands:
                    i2 = _cell_index(rings, r2, c2 % k2)
                    pairs.add((max(i1, i2), min(i1, i2)))

    fp = (params.alpha, cosh_threshold(R))
    per_pe: List[List[PairSpec]] = [[] for _ in range(P)]
    for ia, ib in sorted(pairs):
        A, B = cells[ia], cells[ib]
        per_pe[ia % P].append(PairSpec(  # repro: allow(no-per-chunk-host-loop) retained oracle
            GEOM_HYP, A.key_data, B.key_data, A.count, B.count, A.gid0, B.gid0,
            (A.clo, A.chi, A.cell, A.width), (B.clo, B.chi, B.cell, B.width),
            fparams=fp, self_pair=ia == ib,
        ))
    return make_pair_plan(per_pe, rng_impl=rng_impl)


def _cell_index(rings: List[List[EngineCell]], ring: int, cell: int) -> int:
    """Global index of (ring, cell) in the flat cells list (ring-major)."""
    off = 0
    for r in range(ring):
        off += len(rings[r])
    return off + cell


def rhg_union(params: RHGParams, P: int) -> np.ndarray:
    es = [rhg_pe(params, P, pe)[0] for pe in range(P)]
    e = np.concatenate(es, axis=0)
    return np.unique(e, axis=0) if e.size else e.reshape(0, 2)  # repro: allow(no-numpy-unique) test-oracle union (engine dedups by pair ownership)


def rhg_all_vertices(params: RHGParams, P: int = 1):
    """Every vertex in gid order (oracle input)."""
    plan = RHGPlan(params, P)
    r_all = np.zeros(params.n)
    t_all = np.zeros(params.n)
    cr, ct = plan.core_vertices()
    r_all[: plan.n_core], t_all[: plan.n_core] = cr, ct
    for ann in plan.annuli:
        for cell in range(ann.cells):
            r, t, g0 = plan.cell_vertices(ann.idx, cell)
            r_all[g0: g0 + len(r)] = r
            t_all[g0: g0 + len(t)] = t
    return r_all, t_all


def rhg_brute_edges(r: np.ndarray, theta: np.ndarray, R: float) -> np.ndarray:
    """O(n^2) oracle using the identical Eq. 9 float64 expression."""
    f = precompute_features(r, theta)
    acc = f[:, 0][:, None] * f[:, 0][None, :]
    acc += f[:, 1][:, None] * f[:, 1][None, :]
    acc -= f[:, 2][:, None] * f[:, 2][None, :]
    acc += cosh_threshold(R) * (f[:, 3][:, None] * f[:, 3][None, :])
    mask = np.tril(acc > 0, k=-1)
    u, v = np.nonzero(mask)
    return np.stack([u, v], axis=1)
