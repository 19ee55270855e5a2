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

1. *descent* (``rmat/descent``): ``L`` float64 uniforms
   ``jax.random.uniform(fold_in64(K, i), (L,))``; level ``l`` (most
   significant bit first) takes quadrant ``q = [u >= a] + [u >= a + b]
   + [u >= a + b + c]``, setting bit ``L-1-l`` of the source to
   ``q >= 2`` and of the target to ``q mod 2``;
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

from functools import partial

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


def descend(key, eid, a, b, c, log_n: int):
    """Unpermuted ``(src, dst)`` of edge ``eid`` (int64): the quadrant
    descent of step 1, one ``fold_in64`` and ``log_n`` float64 uniforms."""
    k = fold_in64(key, eid)  # 64-bit safe: edge ids exceed 2^32 at scale
    uu = jax.random.uniform(k, (log_n,), dtype=jnp.float64)
    quad = (
        (uu >= a).astype(jnp.int64)
        + (uu >= a + b).astype(jnp.int64)
        + (uu >= a + b + c).astype(jnp.int64)
    )
    bits = jnp.arange(log_n - 1, -1, -1, dtype=jnp.int64)
    src = jnp.sum((quad >= 2).astype(jnp.int64) << bits)
    dst = jnp.sum((quad % 2) << bits)
    return src, dst


def edges(key, eids, a, b, c, log_n: int):
    """Permuted ``(src, dst)`` [k] int64 of the edges ``eids``: the
    instance of the module docstring, as the engine's RMAT chunks run it."""
    with jax.named_scope("rmat/descent"):
        src, dst = jax.vmap(lambda e: descend(key, e, a, b, c, log_n))(eids)
    with jax.named_scope("rmat/permute"):
        consts = perm_constants(key, log_n)
        return permute(src, consts, log_n), permute(dst, consts, log_n)


@partial(jax.jit, static_argnames=("log_n",))
def _rmat_edges(key, edge_ids, probs, log_n: int):
    a, b, c, _ = probs
    return edges(key, edge_ids, a, b, c, log_n)


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
    src, dst = _rmat_edges(key, ids, jnp.array(probs, jnp.float64), log_n)
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
