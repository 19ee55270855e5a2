"""Threefry-2x32 (Salmon et al., SC'11) and the key derivations built on
it, written out in ``jax.numpy`` uint32 arithmetic.

This is the benchmark's own implementation of the counter-based stream
the generator's instances are defined by: a key is two uint32 words,
``key(seed) = (seed >> 32, seed & 0xffffffff)``, ``fold_in(key, d)`` is
``threefry(key, (0, d))``, and 32 random bits at flat index ``j`` of a
draw are the XOR of the two words of ``threefry(key, (0, j))``.  It
calls nothing of ``jax.random`` and nothing of the program under test.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))
_PARITY = np.uint32(0x1BD11BDA)


def _rotl(x, r: int):
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def threefry2x32(k0, k1, x0, x1):
    """20-round Threefry-2x32 of the counter words ``(x0, x1)`` under the
    key ``(k0, k1)``; all uint32, broadcast together."""
    k0, k1, x0, x1 = (jnp.asarray(a, jnp.uint32) for a in (k0, k1, x0, x1))
    ks = (k0, k1, k0 ^ k1 ^ _PARITY)
    x0 = x0 + ks[0]
    x1 = x1 + ks[1]
    for i in range(5):
        for r in _ROTATIONS[i % 2]:
            x0 = x0 + x1
            x1 = _rotl(x1, r)
            x1 = x1 ^ x0
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + np.uint32(i + 1)
    return x0, x1


def key(seed: int):
    """The two key words of an integer seed."""
    seed = int(seed) & 0xFFFFFFFFFFFFFFFF
    return np.uint32(seed >> 32), np.uint32(seed & 0xFFFFFFFF)


def fold_in(k, data):
    """``(k0, k1)`` folded with the uint32 ``data`` (array or scalar)."""
    return threefry2x32(k[0], k[1], jnp.zeros_like(jnp.asarray(data, jnp.uint32)),
                        data)


def bits32(k, index):
    """32 random bits at flat position ``index`` of a draw under ``k``."""
    y0, y1 = threefry2x32(k[0], k[1], jnp.zeros_like(jnp.asarray(index, jnp.uint32)),
                          index)
    return y0 ^ y1


def slot_bits64(k, capacity: int, width: int = 1):
    """uint64 ``[capacity, width]``: word ``(i, j)`` takes the key folded
    with slot ``i`` and joins its 32-bit draws ``2j`` (high) and
    ``2j + 1`` (low)."""
    i = jnp.arange(capacity, dtype=jnp.uint32)
    ks = fold_in(k, i)
    ks = (ks[0][:, None], ks[1][:, None])
    j = jnp.arange(width, dtype=jnp.uint32)[None, :]
    hi = bits32(ks, 2 * j).astype(jnp.uint64)
    lo = bits32(ks, 2 * j + 1).astype(jnp.uint64)
    return (hi << np.uint64(32)) | lo
