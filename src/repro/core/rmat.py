"""R-MAT generator (paper §3.5.2) — Graph 500's Kronecker instance.

Each of the m edges descends log2(n) levels of the recursive adjacency-
matrix partition with probabilities (a, b, c, d); one hashed key per
edge makes it communication-free and embarrassingly parallel (this is
what the paper benchmarks *against*: R-MAT needs O(log n) variates per
edge, KaGen's generators O(1) — Fig. 17/18).

Graph 500 semantics: self-loops and duplicate edges are kept, and vertex
labels are randomly permuted after generation.

**The instance.**  With ``L = log2(n)`` and ``K = device_key(seed, 51)``
(the chunk key every RMAT chunk carries), edge ``i`` of ``[0, m)``:

1. *descent* (``rmat/descent``): ``L`` 64-bit words ``w =
   jax.random.bits(fold_in64(K, i), (L,), uint64)``; level ``l`` (most
   significant bit first) takes quadrant ``q = [w >= T_0 << 12] + [w >=
   T_1 << 12] + [w >= T_2 << 12]``, with ``T_j = ceil(t_j 2^52)`` of ``t
   = a, a + b, a + b + c`` (float64 sums) computed on the host, setting
   bit ``L-1-l`` of the source to ``q >= 2`` and of the target to ``q mod
   2``.  This is float64's ``u >= t`` on ``jax.random.uniform``'s exact
   ``u = (w >> 12) / 2^52``, since ``w >> 12 >= ceil(t 2^52)`` iff ``u >= t``;
2. *permutation* (``rmat/permute``): both ends pass through ``pi``, a
   keyed bijection of ``[0, 2^L)``.  With ``Kp = fold_in(K, 52)`` and,
   for ``j = 0..3``, ``(h_j, l_j)`` the two key words of ``fold_in(Kp,
   j)`` and ``c_j = h_j * 2^32 + l_j``, ``pi`` is two rounds of

       x <- rev_L(((x + c_{2r}) * (c_{2r+1} | 1)) mod 2^L),  r = 0, 1,

   ``rev_L`` reversing the order of the ``L`` low bits.  Adding,
   multiplying by an odd number and reversing bits are each bijections
   of ``Z / 2^L``; the structure is that of the Graph 500 reference
   code's ``scramble`` (which adds once and takes its constants from its
   own PRNG).  Low bits of a sum or product depend only on low bits, so
   for ``L <= 32`` it runs in uint32 with ``c_j``'s low words alone;
   wider instances run it in uint64.

So edge ``i``'s labels are a function of ``(seed, i)`` alone, and the
**chunk grid** (``rmat_plan``: ``chunks`` chunks of consecutive edge
ids, the exact ``m / chunks`` split, dealt onto the PEs by
``engine.deal_plan``) decides only which PE emits which edge: every P
and every grid give the same edges.  Counts are seed-independent, so a
new seed compiles nothing new.
"""
from __future__ import annotations

import math
from fractions import Fraction
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .chunking import section_bounds
from .prng import device_key, fold_in64

_TAG_RMAT = 51
_TAG_PERM = 52


def perm_constants(key, log_n: int):
    """The permutation's four constants ``c_0..c_3`` (uint32 for
    ``log_n <= 32``, else uint64) from the RMAT chunk key."""
    kp = jax.random.fold_in(key, _TAG_PERM)
    words = jnp.stack([jax.random.key_data(jax.random.fold_in(kp, j))[:2]
                       for j in range(4)])
    if log_n <= 32:
        return words[:, 1]
    hi, lo = words[:, 0].astype(jnp.uint64), words[:, 1].astype(jnp.uint64)
    return (hi << 32) | lo


def _reverse_bits(x, width: int):
    """Reverse the ``width`` (32 or 64) bits of unsigned ``x``."""
    s, mask = width // 2, (1 << width) - 1
    while s:
        m = mask // ((1 << s) + 1)      # 0x5555.., 0x3333.., 0x0f0f.., ...
        c = jnp.asarray(m, x.dtype)
        x = ((x >> s) & c) | ((x & c) << s)
        s //= 2
    return x


def permute(x, consts, log_n: int):
    """``pi(x)`` for ids ``x`` in ``[0, 2^log_n)``: the two rounds of the
    module docstring.  Returns the ids in ``x``'s dtype."""
    width = 32 if log_n <= 32 else 64
    dt = jnp.uint32 if width == 32 else jnp.uint64
    y = x.astype(dt)
    for r in range(2):
        y = (y + consts[2 * r]) * (consts[2 * r + 1] | 1)
        y = _reverse_bits(y, width) >> (width - log_n)
    return y.astype(x.dtype)


def thresholds(probs) -> Tuple[int, int, int]:
    """``T_j = ceil(t_j 2^52)`` of ``t = a, a + b, a + b + c`` (IEEE
    float64 sums on the host, in exact rationals after that), clamped to
    ``[0, 2^52]``: the static constants of the descent."""
    a, b, c = (float(p) for p in probs[:3])
    return tuple(min(max(math.ceil(Fraction(t) * 2**52), 0), 1 << 52)
                 for t in (a, a + b, a + b + c))


def _at_least(hi, lo, t: int):
    """``(hi 2^32 + lo) >> 12 >= t`` for uint32 halves of a word and a
    static ``t`` in ``[0, 2^52]``, compared on the halves."""
    if t >= 1 << 52:
        return jnp.zeros(hi.shape, bool)
    th, tl = np.uint32(t >> 20), np.uint32((t & 0xFFFFF) << 12)
    return (hi > th) | ((hi == th) & (lo >= tl))


def descend(key, eid, thr: Tuple[int, int, int], log_n: int):
    """Unpermuted ``(src, dst)`` of edge ``eid`` (int64): the quadrant
    descent of step 1, one ``fold_in64`` and ``log_n`` 64-bit words
    compared with the thresholds ``thr`` (:func:`thresholds`)."""
    k = fold_in64(key, eid)  # 64-bit safe: edge ids exceed 2^32 at scale
    w = jax.random.bits(k, (log_n,), jnp.uint64)
    hi, lo = (w >> 32).astype(jnp.uint32), w.astype(jnp.uint32)
    quad = sum(_at_least(hi, lo, t).astype(jnp.int32) for t in thr)
    bits = jnp.arange(log_n - 1, -1, -1, dtype=jnp.int64)
    src = jnp.sum((quad >= 2).astype(jnp.int64) << bits)
    # q & 1, not q % 2: an emulated int64 remainder is most of the cost
    dst = jnp.sum((quad & 1).astype(jnp.int64) << bits)
    return src, dst


def edges(key, eids, thr: Tuple[int, int, int], log_n: int):
    """Permuted ``(src, dst)`` [k] int64 of the edges ``eids``: the
    instance of the module docstring, as the engine's RMAT chunks run it."""
    with jax.named_scope("rmat/descent"):
        src, dst = jax.vmap(lambda e: descend(key, e, thr, log_n))(eids)
    with jax.named_scope("rmat/permute"):
        consts = perm_constants(key, log_n)
        return permute(src, consts, log_n), permute(dst, consts, log_n)


_rmat_edges = jax.jit(edges, static_argnames=("thr", "log_n"))


def rmat_pe(
    seed: int,
    log_n: int,
    m: int,
    P: int,
    pe: int,
    probs=(0.57, 0.19, 0.19, 0.05),
) -> np.ndarray:
    """Edges ``[m pe / P, m (pe + 1) / P)`` of the instance; [k, 2] int64."""
    elo, ehi = section_bounds(m, P, pe)
    key = device_key(seed, _TAG_RMAT)
    ids = jnp.arange(elo, ehi, dtype=jnp.int64)
    src, dst = _rmat_edges(key, ids, thresholds(probs), log_n)
    return np.stack([np.asarray(src), np.asarray(dst)], axis=1)


def rmat_plan(seed: int, log_n: int, m: int, chunks: int,
              probs=(0.57, 0.19, 0.19, 0.05), rng_impl: str = "threefry2x32"):
    """ChunkPlan of ``chunks`` virtual chunks, one a row: chunk ``j``
    holds the edge ids ``[m j / chunks, m (j + 1) / chunks)``.  The
    descent and the permutation run on-device as in :func:`rmat_pe`, so
    the output is bit-identical; ``engine.deal_plan`` deals the rows
    onto the real PEs."""
    from .. import obs
    from ..distrib.engine import (KIND_RMAT, chunk_plan_from_columns,
                                  reseedable_chunk_plan)

    k = chunks

    def key_of(s: int) -> np.ndarray:
        one = np.asarray(jax.random.key_data(
            device_key(s, _TAG_RMAT, impl=rng_impl))).ravel()
        return np.broadcast_to(one, (k, one.size))

    with obs.trace("plan/rmat", phase="plan", family="rmat", reseed=False,
                   levels=log_n, chunks=k):
        a, b, c, _ = probs
        sec = m * np.arange(k + 1, dtype=np.int64) // k
        ids = np.arange(k, dtype=np.int64)
        z = np.zeros(k, np.int64)
        fparams = np.broadcast_to(
            np.array([float(a), float(b), float(c), 0.0]), (k, 4))
        plan = chunk_plan_from_columns(
            k, ids, np.full(k, KIND_RMAT, np.int32), key_of(seed), z,
            sec[1:] - sec[:-1],
            np.stack([np.full(k, log_n, np.int64), sec[:-1], z], axis=1),
            np.ones(k, bool), 1 << log_n, fparams=fparams, rng_impl=rng_impl)
        # edge-id sections are seed-independent: reseeding is a pure key swap
        return reseedable_chunk_plan(plan, key_fn=key_of)


def rmat_union(seed: int, log_n: int, m: int, P: int = 1, probs=(0.57, 0.19, 0.19, 0.05)):
    """Deprecated shim: delegates to :func:`repro.api.generate`."""
    from . import warn_deprecated_shim
    from ..api import RMAT, generate

    warn_deprecated_shim("rmat_union", "generate(RMAT(...))")
    return generate(RMAT(n=1 << log_n, m=m, probs=tuple(probs), seed=seed), P).edges
