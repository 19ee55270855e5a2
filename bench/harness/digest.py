"""The benchmark's on-device consumers of a streamed chunk: the summary
(copied from ``chip_smoke.py``'s ``chunk_summary``, so that a change to
that script cannot move the benchmark), the sampled vertices' counts
and digests, and the sample of a chunk's edges.

Benchmark programs are named ``bench_*``: the trace reduction tells
them apart from the program under test by that prefix."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_M1 = np.uint64(0xBF58476D1CE4E5B9)
_M2 = np.uint64(0x94D049BB133111EB)


def _mix64(x):
    """splitmix64's finalizer: a bijection of uint64 with full avalanche."""
    x = (x ^ (x >> np.uint64(30))) * _M1
    x = (x ^ (x >> np.uint64(27))) * _M2
    return x ^ (x >> np.uint64(31))


@jax.jit
def bench_chunk_summary(buf, mask):
    """[valid edges, self-loops, position-keyed 64-bit digest] of one
    chunk buffer ``[..., 2]`` with its validity ``mask``, computed where
    the chunk lives."""
    return _summary(buf, mask)


def _summary(buf, mask):
    u = buf[..., 0].astype(jnp.uint64)
    v = buf[..., 1].astype(jnp.uint64)
    pos = jnp.arange(mask.size, dtype=jnp.uint64).reshape(mask.shape)
    h = _mix64(_mix64(u ^ (pos << np.uint64(32))) ^ v)
    digest = jnp.sum(jnp.where(mask, h, np.uint64(0)), dtype=jnp.uint64)
    return jnp.stack([jnp.sum(mask, dtype=jnp.int64),
                      jnp.sum(mask & (u == v), dtype=jnp.int64),
                      jax.lax.bitcast_convert_type(digest, jnp.int64)])


_H1 = np.uint32(0x85EBCA6B)
_H2 = np.uint32(0xC2B2AE35)


def mix32(x):
    """murmur3's finalizer: a bijection of uint32 (NumPy or JAX arrays)."""
    x = x ^ (x >> np.uint32(16))
    x = x * _H1
    x = x ^ (x >> np.uint32(13))
    x = x * _H2
    return x ^ (x >> np.uint32(16))


@partial(jax.jit, static_argnames=("slots", "block", "rows"))
def bench_chunk_vertices(buf, mask, lo, width, base, *, slots: int,
                         block: int = 128, rows: int = 256):
    """``[valid edges, self-loops]`` of one chunk, and for each of
    ``slots`` sampled vertices how many of the chunk's valid edges end
    at it and the wrapping uint32 sum of :func:`mix32` of their other
    ends: an order-free digest of the vertex's neighbours in the chunk.

    The sampled ids are the disjoint ranges ``[lo_k, lo_k + width_k)``
    (int32; a range of width 0 is padding), the ids of range ``k``
    numbered from slot ``base_k``.  Ids are below 2^31 in every cell.
    Edge ends are tested against the ranges in one fused pass; only the
    blocks of ``block`` ends that hold a sampled one are gathered, ``rows``
    blocks at a time, so a chunk with none costs the test alone."""
    ok = mask.reshape(-1)
    u = buf[..., 0].reshape(-1).astype(jnp.int32)
    v = buf[..., 1].reshape(-1).astype(jnp.int32)
    counts = jnp.stack([jnp.sum(ok, dtype=jnp.int32),
                        jnp.sum(ok & (u == v), dtype=jnp.int32)])
    end = jnp.concatenate([u, v])
    other = jnp.concatenate([v, u])
    d = end[:, None] - lo[None, :]
    inside = d.astype(jnp.uint32) < width.astype(jnp.uint32)[None, :]
    slot = slots + jnp.sum(jnp.where(inside, base[None, :] + d - slots, 0),
                           axis=1, dtype=jnp.int32)
    slot = jnp.where(jnp.concatenate([ok, ok]), slot, slots)
    nb = -(-end.shape[0] // block)
    pad = nb * block - end.shape[0]
    slot = jnp.pad(slot, (0, pad), constant_values=slots).reshape(nb, block)
    other = jnp.pad(other, (0, pad)).reshape(nb, block)
    cb = jnp.cumsum(jnp.any(slot < slots, axis=1), dtype=jnp.int32)
    ids = jnp.arange(slots, dtype=jnp.int32)

    def body(carry):
        j, deg, dig = carry
        k = j * rows + jnp.arange(1, rows + 1, dtype=jnp.int32)
        at = jnp.minimum(jnp.searchsorted(cb, k), nb - 1)
        s = jnp.where((k <= cb[-1])[:, None], slot[at], slots)
        one = s[..., None] == ids
        h = mix32(other[at].astype(jnp.uint32))[..., None]
        deg = deg + jnp.sum(one, axis=(0, 1), dtype=jnp.int32)
        dig = dig + jnp.sum(jnp.where(one, h, np.uint32(0)), axis=(0, 1),
                            dtype=jnp.uint32)
        return j + 1, deg, dig

    _, deg, dig = jax.lax.while_loop(
        lambda c: c[0] * rows < cb[-1], body,
        (jnp.int32(0), jnp.zeros(slots, jnp.int32),
         jnp.zeros(slots, jnp.uint32)))
    return counts, deg, dig


@partial(jax.jit, static_argnames=("keep",))
def bench_chunk_edges(buf, mask, *, keep: int):
    """The chunk's first ``keep`` valid edges in chunk order (``[keep,
    2]`` int32, ``-1`` past the last) and how many valid edges it holds."""
    ok = mask.reshape(-1)
    c = jnp.cumsum(ok, dtype=jnp.int32)
    k = jnp.arange(1, keep + 1, dtype=jnp.int32)
    at = jnp.minimum(jnp.searchsorted(c, k), c.shape[0] - 1)
    e = buf.reshape(-1, 2)[at].astype(jnp.int32)
    return jnp.where((k <= c[-1])[:, None], e, -1), c[-1]
