"""Jit'd public wrapper around the hist kernel (pads, dispatches).

Two entry points, both device-side:

* :func:`degree_histogram` — histogram of *values* (e.g. per-vertex
  degrees), linear or log2-binned, any input length (padded with -1 to
  the value-block multiple).
* :func:`bincount_ids` — scatter-add of occurrence counts over ids
  (degree accumulation from edge endpoints).  The one-hot segment-sum
  kernel is O(N * num_bins) work, the right trade on TPU up to a few
  thousand bins; above ``SCATTER_BINS_LIMIT`` it falls back to XLA's
  native scatter-add (still on device — never a host bincount loop).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .hist import LOG2_BINS, hist_counts

_BLOCK_V = 1024
SCATTER_BINS_LIMIT = 4096
_ONEHOT_WORK_LIMIT = 1 << 27  # max N * bins the one-hot formulation pays


def pad_values(v, block: int = _BLOCK_V) -> jax.Array:
    """int array [N] -> int32 [ceil(N/block)*block, 1], -1 padding.

    -1 rows fall in no bin, so padded entries never count — masks stay
    implicit, like the pairdist kernel's +inf rows."""
    v = jnp.asarray(v, jnp.int32).reshape(-1)
    npad = max(block, (v.shape[0] + block - 1) // block * block)
    out = jnp.full((npad, 1), -1, jnp.int32)
    return out.at[: v.shape[0], 0].set(v)


def degree_histogram(values, num_bins: int, *,
                     log2: bool = False) -> jax.Array:
    """int64 counts[num_bins] of ``values`` via the Pallas kernel."""
    counts = hist_counts(pad_values(values), num_bins=num_bins, log2=log2)
    return counts[:num_bins].astype(jnp.int64)


def log2_histogram(values) -> jax.Array:
    """int64 counts[LOG2_BINS]: bin 0 = zeros, bin 1+k = [2^k, 2^(k+1))."""
    return degree_histogram(values, LOG2_BINS, log2=True)


@partial(jax.jit, static_argnames=("length",))
def _scatter_add(ids, length: int):
    return jnp.zeros(length, jnp.int64).at[ids].add(1, mode="drop")


def bincount_ids(ids, length: int) -> jax.Array:
    """int64 counts[length]: occurrences of each id in [0, length).

    Device scatter-add: the Pallas one-hot kernel when its O(N*length)
    work is worth it (length <= SCATTER_BINS_LIMIT and N*length within
    the work budget), XLA scatter otherwise.  Out-of-range ids are
    *dropped* on both paths (the kernel's overflow clamp is masked off
    here — identical semantics whichever path dispatches, so
    sentinel-padded batches count correctly at any length)."""
    ids = jnp.asarray(ids, jnp.int64)
    if (length <= SCATTER_BINS_LIMIT
            and ids.size * max(length, 1) <= _ONEHOT_WORK_LIMIT):
        ids = jnp.where(ids >= length, -1, ids)  # drop, don't clamp
        return degree_histogram(ids, length)
    return _scatter_add(ids, length)


def log2_bin_edges(num_bins: int = LOG2_BINS) -> np.ndarray:
    """Lower edge of each log2 bin: [0, 1, 2, 4, 8, ...]."""
    return np.concatenate([[0], 2 ** np.arange(num_bins - 1, dtype=np.int64)])
