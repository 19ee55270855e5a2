"""Plain reference of Graph 500's Kronecker generator as the program
defines it: R-MAT with initiator (a, b, c, d), self-loops and repeated
edges kept, vertex labels permuted.

The instance of seed ``s`` with ``n = 2^L`` vertices and ``m`` edges on a
grid of ``k`` chunks:

1. The chunk key is ``K = fold_in(key(s mod 2^31), 51)`` (Threefry-2x32,
   ``threefry.py``).  Chunk ``j`` holds the edge ids ``[m j / k, m (j +
   1) / k)``, so its edge count is that range's length.
2. Edge ``i`` has the key ``Ki = fold_in(fold_in(K, i >> 31), i mod
   2^31)``.  Its level ``l`` (``0 .. L-1``, most significant bit first)
   draws the 64 bits ``w = y0 * 2^32 + y1`` of ``(y0, y1) =
   threefry(Ki, (0, l))``; the uniform is ``u = (w >> 12) / 2^52``, and
   the quadrant ``q = [u >= a] + [u >= a + b] + [u >= a + b + c]`` (sums
   in float64) sets the level's bit of the source to ``q >= 2`` and of
   the target to ``q mod 2``.  ``u >= t`` is tested exactly on the 52
   mantissa bits: ``w >> 12 >= ceil(t * 2^52)``.
3. Both ends pass through the permutation: with ``Kp = fold_in(K, 52)``
   and ``c_j`` the 64-bit value ``h * 2^32 + l`` of the key words ``(h,
   l) = fold_in(Kp, j)``, two rounds of ``x <- rev_L(((x + c_{2r}) *
   (c_{2r+1} | 1)) mod 2^L)``, ``rev_L`` reversing the ``L`` low bits
   (here one bit at a time).

Nothing here imports the program under test.  Edges are computed with
``jax.numpy`` on whatever device the caller runs on, in integers alone.
"""
from __future__ import annotations

from fractions import Fraction
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import threefry

_RMAT_TAG = 51
_PERM_TAG = 52


def chunk_key(seed: int):
    return threefry.fold_in(threefry.key(int(seed) & 0x7FFFFFFF), _RMAT_TAG)


def edge_bounds(m: int, k: int, j: int):
    return m * j // k, m * (j + 1) // k


def thresholds(probs) -> np.ndarray:
    """``ceil(t * 2^52)`` of ``t = a, a + b, a + b + c`` (float64 sums)."""
    a, b, c = (float(p) for p in probs[:3])
    ts = (a, a + b, a + b + c)
    return np.array([-(-Fraction(t) * 2**52 // 1) for t in ts], np.uint64)


def _perm_constants(k, log_n: int):
    kp = threefry.fold_in(k, _PERM_TAG)
    out = []
    for j in range(4):
        h, lo = threefry.fold_in(kp, j)
        c = (int(h) << 32) | int(lo)
        out.append(c % (1 << log_n))
    return out


def _reverse(x, log_n: int):
    out = jnp.zeros_like(x)
    for b in range(log_n):
        out = out | (((x >> b) & 1) << (log_n - 1 - b))
    return out


@partial(jax.jit, static_argnames=("capacity", "log_n", "permute"))
def _chunk(k0, k1, lo, count, thr, consts, *, capacity: int, log_n: int,
           permute: bool):
    i = lo + jnp.arange(capacity, dtype=jnp.int64)
    ka = threefry.fold_in((k0, k1), (i >> 31).astype(jnp.uint32))
    ke = threefry.fold_in(ka, (i & 0x7FFFFFFF).astype(jnp.uint32))
    lev = jnp.arange(log_n, dtype=jnp.uint32)[None, :]
    y0, y1 = threefry.threefry2x32(ke[0][:, None], ke[1][:, None],
                                   jnp.zeros_like(lev), lev)
    mant = ((y0.astype(jnp.uint64) << 32) | y1.astype(jnp.uint64)) >> 12
    q = sum((mant >= thr[t]).astype(jnp.int64) for t in range(3))
    bit = (log_n - 1 - jnp.arange(log_n, dtype=jnp.int64))[None, :]
    src = jnp.sum((q >= 2).astype(jnp.int64) << bit, axis=1)
    dst = jnp.sum((q % 2) << bit, axis=1)
    if permute:
        mask = (1 << log_n) - 1
        for r in range(2):
            src = _reverse(((src + consts[2 * r]) * (consts[2 * r + 1] | 1))
                           & mask, log_n)
            dst = _reverse(((dst + consts[2 * r]) * (consts[2 * r + 1] | 1))
                           & mask, log_n)
    valid = jnp.arange(capacity) < count
    e = jnp.stack([src, dst], axis=-1)
    return jnp.where(valid[:, None], e, 0), valid


def chunk_edges(seed: int, log_n: int, m: int, k: int, j: int, probs,
                capacity: int = 0, permute: bool = True):
    """``(edges [capacity, 2], valid [capacity])`` of chunk ``j``, its
    edges in id order.  ``permute=False`` leaves the labels unpermuted:
    the control."""
    lo, hi = edge_bounds(m, k, j)
    capacity = capacity or (hi - lo)
    k0, k1 = chunk_key(seed)
    consts = _perm_constants((k0, k1), log_n)
    # mod 2^L the constants fit int64 below L = 63; every cell is far below
    return _chunk(k0, k1, jnp.int64(lo), jnp.int64(hi - lo),
                  jnp.asarray(thresholds(probs)), jnp.asarray(consts, jnp.int64),
                  capacity=capacity, log_n=log_n, permute=permute)


class StreamCheck:
    """The stream cell's consumer and comparison for R-MAT: every chunk's
    edge count against the exact ``m / k`` split, and a seeded sample of
    chunks' summaries (count, self-loops, position-keyed digest) against
    this reference.  Self-loops are kept by the generator, so they are
    compared with the reference's, not with 0.

    Traffic keys: ``P`` (equal to the chunk grid: a chunk's PE is its
    chunk), ``sample`` (chunks whose summary is compared; the first and
    last chunk of the window always are)."""

    def __init__(self, args: dict, traffic: dict):
        from ..harness.digest import bench_chunk_summary

        self.seed, n, self.m = int(args["seed"]), int(args["n"]), int(args["m"])
        self.log_n = n.bit_length() - 1
        self.probs = tuple(args.get("probs", (0.57, 0.19, 0.19, 0.05)))
        self.k = int(args.get("chunks") or max(int(traffic["P"]), 16))
        if self.k != int(traffic["P"]):
            raise ValueError("the R-MAT reference reads a chunk's PE as its "
                             "chunk: the traffic needs chunks == P")
        self.sample = int(traffic.get("sample", 64))
        self._summary = bench_chunk_summary
        self.counts = np.diff(self.m * np.arange(self.k + 1) // self.k)
        self._cap = int(self.counts.max())

    def consume(self, buffer, mask, index):
        return (self._summary(buffer, mask),)

    def _edges(self, j: int, permute: bool = True):
        return chunk_edges(self.seed, self.log_n, self.m, self.k, j,
                           self.probs, self._cap, permute)

    def control_source(self):
        """The control in the program's place: this reference with the
        permutation left out, so every label is the descent's own."""
        for j in range(self.k):
            e, ok = self._edges(j, permute=False)
            yield _Chunk(e, ok, j)

    def check(self, rows, complete) -> dict:
        summ = np.stack(jax.device_get([out[0] for _, _, out in rows]))
        pes = np.array([pe for _, pe, _ in rows])
        bad = summ[:, 0] != self.counts[pes]
        count_bad = int(bad.sum())
        rng = np.random.default_rng([self.seed, 0x5EED])
        idx = rng.permutation(len(rows))[: self.sample]
        idx = np.unique(np.concatenate([[0, len(rows) - 1], idx]))
        digest_bad = loop_bad = 0
        for i in idx:
            want = np.asarray(self._summary(*self._edges(int(pes[i]))))
            loop_bad += int(want[1] != summ[i, 1])
            if not np.array_equal(want, summ[i]):
                digest_bad += 1
                bad[i] = True
        passes = len({p for p, _, _ in rows})
        return {
            "attempted": len(rows),
            "failed": int(bad.sum()),
            "checks": {
                "chunk_count_mismatch": (count_bad, 0),
                "sampled_digest_mismatch": (digest_bad, 0),
                "self_loop_mismatch": (loop_bad, 0),
            },
            "info": f"{len(rows)} chunks in {passes} passes; counts of all, "
                    f"summaries of {len(idx)} against the reference",
        }


class _Chunk:
    def __init__(self, buffer, mask, pe):
        self.buffer, self.mask, self.pe = buffer, mask, pe
