"""Plain reference of directed G(n, m) as the generator defines it
(Funke et al., arXiv:1710.07565, section 4).

The instance of seed ``s`` on a grid of ``k`` row chunks:

1. Chunk ``j`` holds the adjacency-matrix rows ``[n j / k, n (j+1) / k)``
   with the diagonal left out, so ``rows * (n - 1)`` possible edges.
2. Its edge count comes from a divide-and-conquer over chunk ranges:
   range ``[lo, hi)`` splits at ``mid = (lo + hi) / 2`` and hands its
   left half a hypergeometric share of its count, drawn from a NumPy
   Philox generator keyed by the splitmix64 hash of ``(s, 3, lo, hi)``.
3. Its edges are ``count`` distinct indices of its universe: slot ``i``
   draws 64 bits from Threefry key ``(s, 11, j)`` folded with round
   ``0`` and then ``i``, reduced mod the universe; the indices are
   sorted, and every index equal to its predecessor is redrawn from
   round ``t = 1, 2, ...`` at its sorted position, until none repeat.
4. Index ``x`` decodes to row ``lo + x / (n - 1)`` and column
   ``c = x mod (n - 1)``, moved up by one at or past the diagonal.

Nothing here imports the program under test.  Counts are host work
(NumPy); edges are computed with ``jax.numpy`` on whatever device the
caller runs on.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import threefry

_ROWS_TAG = 3
_CHUNK_TAG = 11
_MAX_ROUNDS = 64
_GOLDEN = 0x9E3779B97F4A7C15
_MASK64 = 0xFFFFFFFFFFFFFFFF
_EXACT_LIMIT = 10**9 - 1
_BINOM_LIMIT = 1 << 62


def _splitmix64(x: int) -> int:
    x = (x + _GOLDEN) & _MASK64
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _MASK64
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _MASK64
    return x ^ (x >> 31)


def path_rng(seed: int, *path: int) -> np.random.Generator:
    h = _splitmix64(int(seed) & _MASK64)
    for p in path:
        h = _splitmix64(h ^ ((int(p) & _MASK64) + _GOLDEN) & _MASK64)
    return np.random.Generator(np.random.Philox(key=h))


def _hypergeometric(rng, ngood: int, nbad: int, nsample: int) -> int:
    """Good draws in a uniform ``nsample``-subset: exact below NumPy's
    limit, binomial where the sample is far below the square root of
    the population, normal beyond."""
    total = ngood + nbad
    lo, hi = max(0, nsample - nbad), min(nsample, ngood)
    if lo == hi:
        return lo
    if max(ngood, nbad) <= _EXACT_LIMIT:
        return int(rng.hypergeometric(ngood, nbad, nsample))
    if nsample * nsample <= total // 100 and nsample <= _BINOM_LIMIT:
        return int(np.clip(rng.binomial(nsample, ngood / total), lo, hi))
    p = ngood / total
    mean = nsample * p
    var = nsample * p * (1.0 - p) * (total - nsample) / (total - 1.0)
    return int(np.clip(round(rng.normal(mean, np.sqrt(max(var, 0.0)))), lo, hi))


def row_bounds(n: int, k: int, j: int):
    return n * j // k, n * (j + 1) // k


def chunk_counts(seed: int, n: int, m: int, k: int) -> np.ndarray:
    """Edge count of each of the ``k`` row chunks."""
    out = np.zeros(k, np.int64)

    def universe(lo, hi):
        return (row_bounds(n, k, hi - 1)[1] - row_bounds(n, k, lo)[0]) * (n - 1)

    stack = [(0, k, int(m))]
    while stack:
        lo, hi, mm = stack.pop()
        if hi - lo == 1:
            out[lo] = mm
            continue
        mid = (lo + hi) // 2
        left = (_hypergeometric(path_rng(seed, _ROWS_TAG, lo, hi),
                                universe(lo, mid), universe(mid, hi), mm)
                if mm else 0)
        stack.append((lo, mid, left))
        stack.append((mid, hi, mm - left))
    return out


@partial(jax.jit, static_argnames=("capacity",))
def _draw(k0, k1, t, universe, count, capacity: int):
    kr = threefry.fold_in((k0, k1), t)
    w = threefry.slot_bits64(kr, capacity)[:, 0]
    idx = jnp.arange(capacity, dtype=jnp.int64)
    u = (w % universe.astype(jnp.uint64)).astype(jnp.int64)
    return jnp.where(idx < count, u, universe + idx)


@jax.jit
def _repeats(s):
    return jnp.concatenate([jnp.zeros((1,), bool), s[1:] == s[:-1]])


@jax.jit
def _decode(vals, count, n, row_lo):
    idx = jnp.arange(vals.shape[0], dtype=jnp.int64)
    row = row_lo + vals // (n - 1)
    c = vals % (n - 1)
    col = c + (c >= row)
    return jnp.stack([row, col], axis=-1), idx < count


def chunk_edges(seed: int, n: int, k: int, j: int, count: int,
                capacity: int = 0, distinct: bool = True):
    """``(edges [capacity, 2], valid [capacity])`` of chunk ``j``: the
    valid rows are its ``count`` edges in generation order.

    ``distinct=False`` leaves repeated indices in place: the control,
    which breaks the guarantee of distinct edges."""
    capacity = capacity or max(64, -(-int(count) // 4096) * 4096)
    lo, hi = row_bounds(n, k, j)
    universe = jnp.int64((hi - lo) * (n - 1))
    base = threefry.key(int(seed) & 0x7FFFFFFF)
    kc = threefry.fold_in(threefry.fold_in(base, _CHUNK_TAG), j)
    cnt = jnp.int64(count)
    s = jnp.sort(_draw(kc[0], kc[1], 0, universe, cnt, capacity))
    t = 1
    while distinct and t < _MAX_ROUNDS:
        rep = _repeats(s)
        if not bool(rep.any()):
            break
        s = jnp.sort(jnp.where(rep, _draw(kc[0], kc[1], t, universe, cnt,
                                          capacity), s))
        t += 1
    return _decode(s, cnt, jnp.int64(n), jnp.int64(lo))


class StreamCheck:
    """The stream cell's consumer and comparison for G(n, m): every
    chunk's edge count and self-loops, and a seeded sample of chunks'
    position-keyed digests, against this reference.

    Traffic keys: ``P``, ``sample`` (chunks whose digest is compared;
    the first and last chunk of the window always are)."""

    def __init__(self, args: dict, traffic: dict):
        from ..harness.digest import bench_chunk_summary

        self.seed, self.n, self.m = int(args["seed"]), int(args["n"]), int(args["m"])
        self.k = int(args.get("chunks") or max(int(traffic["P"]), 16))
        self.sample = int(traffic.get("sample", 64))
        self._summary = bench_chunk_summary
        self._counts = None

    def counts(self) -> np.ndarray:
        if self._counts is None:
            self._counts = chunk_counts(self.seed, self.n, self.m, self.k)
        return self._counts

    def consume(self, buffer, mask, index):
        return (self._summary(buffer, mask),)

    def control_source(self):
        """The control in the program's place: this reference with
        repeated indices left in, so edges are no longer distinct."""
        counts = self.counts()
        cap = _capacity(counts)
        for j in range(self.k):
            e, ok = chunk_edges(self.seed, self.n, self.k, j, int(counts[j]),
                                cap, distinct=False)
            yield _Chunk(e, ok, j)

    def check(self, rows, complete) -> dict:
        import jax

        summ = np.stack(jax.device_get([out[0] for _, _, out in rows]))
        pes = np.array([pe for _, pe, _ in rows])
        counts = self.counts()
        count_bad = int((summ[:, 0] != counts[pes]).sum())
        loops = int(summ[:, 1].sum())
        rng = np.random.default_rng([self.seed, 0x5EED])
        idx = rng.permutation(len(rows))[: self.sample]
        idx = np.unique(np.concatenate([[0, len(rows) - 1], idx]))
        cap = _capacity(counts)
        bad = (summ[:, 0] != counts[pes]) | (summ[:, 1] != 0)
        digest_bad = 0
        for i in idx:
            j = int(pes[i])
            e, ok = chunk_edges(self.seed, self.n, self.k, j, int(counts[j]), cap)
            want = np.asarray(self._summary(e, ok))
            if not np.array_equal(want, summ[i]):
                digest_bad += 1
                bad[i] = True
        passes = len({p for p, _, _ in rows})
        return {
            "attempted": len(rows),
            "failed": int(bad.sum()),
            "checks": {
                "chunk_count_mismatch": (count_bad, 0),
                "self_loops": (loops, 0),
                "sampled_digest_mismatch": (digest_bad, 0),
            },
            "info": f"{len(rows)} chunks in {passes} passes; counts and "
                    f"self-loops of all, digests of {len(idx)} against the "
                    f"reference",
        }


class _Chunk:
    def __init__(self, buffer, mask, pe):
        self.buffer, self.mask, self.pe = buffer, mask, pe


def _capacity(counts) -> int:
    """One static reference buffer for every chunk of the instance."""
    return int(-(-int(np.max(counts)) // 65536) * 65536)
