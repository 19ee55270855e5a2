"""Plain reference of the threshold random hyperbolic graph as the
generator defines it (Funke et al., arXiv:1710.07565, section 7; the
model of Krioukov et al., Phys. Rev. E 82, 036106, 2010).

The instance of seed ``s`` with ``n`` vertices, average degree ``d`` and
exponent ``gamma``:

1. ``alpha = (gamma - 1) / 2``, ``xi = alpha / (alpha - 1/2)``,
   ``C = -2 ln(d pi / (2 xi^2))``, ``R = 2 ln n + C``.
2. Rings: a core disk ``[0, R/2]`` and ``k = max(1, floor(alpha R / 2 /
   ln 2))`` annuli of equal height over ``[R/2, R]``.  Their vertex
   counts are a multinomial of ``n`` over the radial law's masses
   ``mu(B_r) = (cosh(alpha r) - 1) / (cosh(alpha R) - 1)``, drawn as
   dependent binomials from the host generator of path ``(s, 31)``.
3. Annulus ``b`` has ``max(1, count_b // 8)`` cells of equal angle.
   Their counts, and so the vertex ids in angular order, come from a
   binary recursion over cell ranges: range ``[lo, hi)`` hands its left
   half ``Binomial(count, (mid - lo) / (hi - lo))`` drawn from the
   generator of path ``(s, 36, b, lo, hi)``.  Ids run over the core
   first, then the annuli outward.
4. Vertex ``i`` of a cell draws two 64-bit words from the Threefry key
   ``(0, s mod 2^31)`` folded with 35, the ring (0 for the core,
   ``b + 1`` for annulus ``b``) and the cell, then with ``i``; each
   word gives ``u = (word >> 11) / 2^53``.  ``r = arccosh(clo + u0 (chi -
   clo)) / alpha`` with ``clo, chi`` the ``cosh(alpha r)`` of the ring's
   bounds (the core's run from 1 to ``cosh(alpha R / 2)``), and
   ``theta = (cell + u1) * cell angle``.
5. ``u ~ v`` iff their hyperbolic distance is below ``R``, tested as
   the paper's Eq. 9: ``cos t_u cos t_v + sin t_u sin t_v - coth r_u
   coth r_v + cosh R / (sinh r_u sinh r_v) > 0``.  An edge is written
   ``(larger id, smaller id)``.

Points are computed with NumPy and the benchmark's own Threefry; Eq. 9
is evaluated by brute force against every vertex, in float64 or (the
control) float32.  Nothing here imports the program under test.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import threefry
from .gnm import path_rng

_TAG_RINGS = 31
_TAG_CELLS = 36
_TAG_POINTS = 35
_CELL_OCC = 8
_EXACT_LIMIT = 10**9 - 1


@dataclass(frozen=True)
class Model:
    n: int
    avg_deg: float
    gamma: float

    @property
    def alpha(self) -> float:
        return (self.gamma - 1.0) / 2.0

    @property
    def R(self) -> float:
        xi = self.alpha / (self.alpha - 0.5)
        C = -2.0 * math.log(self.avg_deg * math.pi / (2.0 * xi * xi))
        return 2.0 * math.log(self.n) + C


def _binomial(rng, n: int, p: float) -> int:
    if p <= 0.0 or n == 0:
        return 0
    if p >= 1.0:
        return n
    if n <= _EXACT_LIMIT:
        return int(rng.binomial(n, p))
    return int(np.clip(round(rng.normal(n * p, math.sqrt(n * p * (1 - p)))), 0, n))


def rings(seed: int, model: Model):
    """``(core count, annulus counts [k], bounds [k + 1])``."""
    a, R = model.alpha, model.R
    half = R / 2.0
    k = max(1, int(a * half / math.log(2.0)))
    bounds = half + np.arange(k + 1) * (half / k)

    def mass(r):
        return (math.cosh(a * r) - 1.0) / (math.cosh(a * R) - 1.0)

    probs = [mass(bounds[0])] + [mass(bounds[i + 1]) - mass(bounds[i])
                                 for i in range(k)]
    rng = path_rng(seed, _TAG_RINGS)
    counts = np.zeros(k + 1, np.int64)
    left, rest = int(model.n), 1.0
    for i, p in enumerate(probs[:-1]):
        if left == 0:
            break
        q = 0.0 if rest <= 0 else min(1.0, p / rest)
        counts[i] = _binomial(rng, left, q)
        left -= int(counts[i])
        rest -= p
    counts[k] += left
    return int(counts[0]), counts[1:], bounds


def cell_counts(seed: int, annulus: int, units: int, total: int):
    """``(counts [units], id offsets [units])`` of one annulus's cells."""
    counts = np.zeros(units, np.int64)
    offsets = np.zeros(units, np.int64)
    stack = [(0, units, int(total), 0)]
    while stack:
        lo, hi, c, off = stack.pop()
        if hi - lo == 1:
            counts[lo], offsets[lo] = c, off
            continue
        mid = (lo + hi) // 2
        left = (_binomial(path_rng(seed, _TAG_CELLS, annulus, lo, hi), c,
                          (mid - lo) / (hi - lo)) if c else 0)
        stack.append((lo, mid, left, off))
        stack.append((mid, hi, c - left, off + left))
    return counts, offsets


@partial(jax.jit, static_argnames=("width",))
def _vertex_words(k0, k1, slot, width: int = 2):
    """uint64 ``[V, width]``: the words vertex ``slot`` of the cell with
    key ``(k0, k1)`` draws."""
    ks = threefry.fold_in((k0, k1), slot)
    j = jnp.arange(width, dtype=jnp.uint32)[None, :]
    ks = (ks[0][:, None], ks[1][:, None])
    hi = threefry.bits32(ks, 2 * j).astype(jnp.uint64)
    lo = threefry.bits32(ks, 2 * j + 1).astype(jnp.uint64)
    return (hi << np.uint64(32)) | lo


def points(seed: int, model: Model):
    """``(r [n], theta [n])`` of every vertex, indexed by id."""
    a = model.alpha
    n_core, ann, bounds = rings(seed, model)
    ring, cell, cnt, gid0 = [[0]], [[0]], [[n_core]], [[0]]
    clo, chi, width = [[1.0]], [[math.cosh(a * model.R / 2.0)]], [[2.0 * math.pi]]
    start = n_core
    for b, c in enumerate(ann):
        k = max(1, int(c) // _CELL_OCC)
        cc, off = cell_counts(seed, b, k, int(c))
        ring.append(np.full(k, b + 1))
        cell.append(np.arange(k))
        cnt.append(cc)
        gid0.append(start + off)
        clo.append(np.full(k, math.cosh(a * float(bounds[b]))))
        chi.append(np.full(k, math.cosh(a * float(bounds[b + 1]))))
        width.append(np.full(k, 2.0 * math.pi / k))
        start += int(c)
    ring, cell, cnt, gid0, clo, chi, width = (
        np.concatenate(x) for x in (ring, cell, cnt, gid0, clo, chi, width))
    assert int(cnt.sum()) == model.n
    base = threefry.fold_in(threefry.key(int(seed) & 0x7FFFFFFF), _TAG_POINTS)
    kr = threefry.fold_in(base, jnp.asarray(ring, jnp.uint32))
    kc = threefry.fold_in(kr, jnp.asarray(cell, jnp.uint32))
    kc = np.asarray(kc[0]), np.asarray(kc[1])
    # ids run over the cells in table order, each cell's in slot order
    assert np.array_equal(gid0, np.cumsum(cnt) - cnt)
    owner = np.repeat(np.arange(len(cnt)), cnt)
    slot = np.arange(model.n) - gid0[owner]
    w = np.asarray(_vertex_words(jnp.asarray(kc[0][owner]),
                                 jnp.asarray(kc[1][owner]),
                                 jnp.asarray(slot, jnp.uint32)))
    u = (w >> np.uint64(11)).astype(np.float64) * (1.0 / (1 << 53))
    lo_, hi_ = clo[owner], chi[owner]
    r = np.arccosh(lo_ + u[:, 0] * (hi_ - lo_)) / a
    theta = (cell[owner].astype(np.float64) + u[:, 1]) * width[owner]
    return r, theta


def _features(r, theta, dtype):
    r = np.maximum(np.asarray(r, np.float64), 1e-12).astype(dtype)
    theta = np.asarray(theta, np.float64).astype(dtype)
    sh = np.sinh(r)
    return np.stack([np.cos(theta), np.sin(theta), np.cosh(r) / sh,
                     np.ones_like(sh) / sh], axis=-1)


def eq9(f, cosh_R, g):
    """Eq. 9's value of vertex ``g`` with every vertex (``f`` its
    features), with ``g`` itself set to -1."""
    q = f[g]
    acc = f[:, 0] * q[0]
    acc += f[:, 1] * q[1]
    acc -= f[:, 2] * q[2]
    acc += cosh_R * (f[:, 3] * q[3])
    acc[g] = -1
    return acc


def _hash(ids) -> np.uint32:
    """The wrapping uint32 sum of ``mix32`` of ``ids``."""
    from ..harness.digest import mix32

    h = mix32(np.asarray(ids, np.int64).astype(np.uint32))
    return np.uint32(np.sum(h, dtype=np.uint64) & 0xFFFFFFFF)


class StreamCheck:
    """The stream cell's consumer and comparison for the threshold RHG.

    Two samples, both drawn from the seed before the window:

    * vertices: every vertex of the core disk, ``inner_ranges`` ranges of
      ``inner_width`` ids in the inner half of the annuli (the hubs) and
      ``probe_ranges`` ranges of ``probe_width`` ids in the outer half.
      For every chunk the consumer gives each sampled vertex's count of
      delivered edges and an order-free digest of their other ends
      (:func:`bench.harness.digest.bench_chunk_vertices`).  For every
      pass streamed to its end, ``vertex_mismatch`` counts the sampled
      vertices whose count and digest summed over the pass are not
      those of their neighbour set in this reference (float64): a
      neighbour missing, extra, repeated or altered.  A pair within
      ``TOL`` of Eq. 9's threshold may fall either way (the program
      evaluates it in XLA's emulated float64): the comparison accepts
      either.  The largest count over the passes is compared.
    * chunk positions: the first chunk of every pass and every
      ``edge_stride``-th after an offset.  The consumer keeps their
      first ``edge_keep`` valid edges; ``edge_mismatch`` counts those
      that are not an edge of this reference (ids out of range or not
      as (larger, smaller), repeated in the chunk, or Eq. 9 below
      ``-TOL`` in float64).

    Every chunk's self-loops count too.  Traffic keys: ``probe_ranges``,
    ``probe_width``, ``inner_ranges``, ``inner_width``, ``edge_stride``,
    ``edge_keep`` and the limits ``vertex_mismatch_limit``,
    ``edge_mismatch_limit``."""

    TOL = 1e-12

    def __init__(self, args: dict, traffic: dict):
        from ..harness.digest import bench_chunk_edges, bench_chunk_vertices

        self.seed = int(args["seed"])
        self.model = Model(int(args["n"]), float(args["avg_deg"]),
                           float(args["gamma"]))
        n = self.model.n
        self.stride = int(traffic.get("edge_stride", 16))
        self.edge_keep = int(traffic.get("edge_keep", 8192))
        self.limits = {"vertex_mismatch": int(traffic["vertex_mismatch_limit"]),
                       "edge_mismatch": int(traffic["edge_mismatch_limit"])}
        n_core, ann, _ = rings(self.seed, self.model)
        half = n_core + int(ann[: len(ann) // 2].sum())
        rng = np.random.default_rng([self.seed, 0x2A6E])
        iw, ow = int(traffic.get("inner_width", 2)), int(traffic.get("probe_width", 8))
        inner = rng.integers(n_core, max(half - iw, n_core + 1), int(traffic.get("inner_ranges", 8)))
        outer = rng.integers(half, n - ow, int(traffic.get("probe_ranges", 8)))
        self.offset = int(rng.integers(self.stride))
        self.ids = np.unique(np.concatenate([
            np.arange(n_core), (inner[:, None] + np.arange(iw)).ravel(),
            (outer[:, None] + np.arange(ow)).ravel()]))
        # maximal runs of consecutive sampled ids, padded to a fixed count
        cut = np.flatnonzero(np.diff(self.ids) != 1) + 1
        starts = np.concatenate([[0], cut])
        width = np.diff(np.concatenate([starts, [len(self.ids)]]))
        k = 1 + len(inner) + len(outer)
        pad = k - len(starts)
        self._ranges = tuple(jnp.asarray(np.concatenate([x, np.zeros(pad, np.int64)]),
                                         jnp.int32)
                             for x in (self.ids[starts], width, starts))
        self._vertices, self._edges = bench_chunk_vertices, bench_chunk_edges
        self._points = None

    def _pts(self):
        if self._points is None:
            self._points = points(self.seed, self.model)
        return self._points

    def consume(self, buffer, mask, index):
        out = self._vertices(buffer, mask, *self._ranges, slots=len(self.ids))
        if index == 0 or index % self.stride == self.offset:
            out += self._edges(buffer, mask, keep=self.edge_keep)
        return out

    def expected(self, dtype=np.float64):
        """For each sampled vertex: its neighbours (Eq. 9 above ``TOL``)
        and the pairs within ``TOL`` of the threshold, in ``dtype``."""
        r, theta = self._pts()
        f = _features(r, theta, dtype)
        cosh_R = dtype(math.cosh(self.model.R))
        tol = self.TOL if dtype == np.float64 else 0.0
        out = []
        for g in self.ids:
            acc = eq9(f, cosh_R, int(g))
            out.append((np.flatnonzero(acc > tol), np.flatnonzero(np.abs(acc) <= tol)))
        return out

    def control_source(self):
        """The control in the program's place: this reference with Eq. 9
        in float32, answering with the sampled vertices' edges only (all
        that the comparison reads)."""
        from .gnm import _Chunk

        e = np.array(sorted({(max(int(g), int(x)), min(int(g), int(x)))
                             for g, (xs, _) in zip(self.ids, self.expected(np.float32))
                             for x in xs}), np.int64).reshape(-1, 2)
        keep = self.edge_keep
        for at in range(0, max(len(e), 1), keep):
            part = e[at: at + keep]
            buf = np.zeros((keep, 2), np.int64)
            buf[: len(part)] = part
            yield _Chunk(jnp.asarray(buf),
                         jnp.asarray(np.arange(keep) < len(part)), 0)

    @staticmethod
    def _matches(deg, dig, xs, amb) -> bool:
        """Whether ``deg`` edges with digest ``dig`` are the neighbours
        ``xs`` with some of the threshold pairs ``amb`` added."""
        from itertools import combinations

        extra = int(deg) - len(xs)
        if extra < 0 or extra > len(amb):
            return False
        rest = np.uint32((int(dig) - int(_hash(xs))) & 0xFFFFFFFF)
        return any(_hash(c) == rest for c in combinations(amb, extra))

    def _edge_mismatch(self, e, f) -> int:
        n = self.model.n
        ok = (e[:, 0] < n) & (e[:, 1] >= 0) & (e[:, 0] > e[:, 1])
        bad = int((~ok).sum())
        e = e[ok]
        bad += len(e) - len(np.unique(e, axis=0))
        a, b = f[e[:, 0]], f[e[:, 1]]
        acc = a[:, 0] * b[:, 0]
        acc += a[:, 1] * b[:, 1]
        acc -= a[:, 2] * b[:, 2]
        acc += math.cosh(self.model.R) * (a[:, 3] * b[:, 3])
        return bad + int((acc < -self.TOL).sum())

    def check(self, rows, complete) -> dict:
        import jax

        out = jax.device_get([o for _, _, o in rows])
        counts = np.stack([o[0] for o in out]).astype(np.int64)
        deg = np.stack([o[1] for o in out]).astype(np.int64)
        dig = np.stack([o[2] for o in out]).astype(np.uint64)
        passes = np.array([p for p, _, _ in rows])
        want = self.expected()
        per_pass = []
        for p in sorted(complete):
            at = passes == p
            d, h = deg[at].sum(0), dig[at].sum(0) & np.uint64(0xFFFFFFFF)
            per_pass.append(sum(not self._matches(d[i], h[i], xs, amb)
                                for i, (xs, amb) in enumerate(want)))
        r, theta = self._pts()
        f = _features(r, theta, np.float64)
        sampled = [o[3][: int(o[4])].astype(np.int64) for o in out if len(o) > 3]
        edge_bad = sum(self._edge_mismatch(e, f) for e in sampled)
        numbers = {"self_loops": (int(counts[:, 1].sum()), 0),
                   "vertex_mismatch": (max(per_pass, default=0),
                                       self.limits["vertex_mismatch"]),
                   "edge_mismatch": (edge_bad, self.limits["edge_mismatch"])}
        hubs = sum(len(xs) for xs, _ in want)
        return {
            "attempted": len(per_pass),
            "failed": sum(m > self.limits["vertex_mismatch"] for m in per_pass),
            "checks": numbers,
            "info": f"{len(rows)} chunks in {len(set(passes.tolist()))} passes, "
                    f"{len(per_pass)} streamed to their end; {len(self.ids)} "
                    f"sampled vertices ({hubs} reference neighbours, "
                    f"{sum(len(a) for _, a in want)} threshold pairs) and "
                    f"{sum(len(e) for e in sampled)} edges of {len(sampled)} "
                    f"chunks compared with the reference",
        }
