"""Pallas TPU kernel: scatter-add histogram (binning + segment-sum).

Degree accumulation is the stats subsystem's hot loop, and TPUs have no
fast per-element scatter: the idiomatic formulation is *one-hot
segment-sum* — each grid step loads a (bv,) tile of values, computes
their bin ids on the VPU (log2 binning is 31 integer compares, exact,
no float log), expands to a (bv, bb) one-hot tile against the step's
bin window, and column-sums into the (1, bb) output block.  The grid is
(bin blocks, value blocks) with the *value* dim innermost, so each
output block's revisits are consecutive (the standard Pallas accumulate
pattern: zero on the first value step, ``+=`` after) and the counts
tile stays resident in VMEM for its whole reduction.

Negative values are padding and fall in no bin; values past the last
bin are clamped into it (an explicit overflow bin keeps totals exact).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .. import interpret_mode

# log2 binning: bin 0 holds value 0, bin 1 + k holds [2^k, 2^(k+1)).
# 32 bins cover every non-negative int32 (max value 2^31 - 1 -> bin 31).
LOG2_BINS = 32

# block index 0 for the index maps, int32 even under x64
_ZERO = np.int32(0)


def _hist_kernel(v_ref, out_ref, *, num_bins: int, block_b: int, log2: bool):
    j, i = pl.program_id(0), pl.program_id(1)  # bin block outer, value block inner

    @pl.when(i == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    # every constant is an explicit int32: under x64 a Python int would
    # trace as int64, which Mosaic cannot lower
    i32 = jnp.int32
    v = v_ref[:, 0]  # (bv,) int32; negatives = padding
    if log2:
        b = jnp.zeros_like(v)
        for k in range(31):  # static: bin id = 1 + floor(log2 v), exact in int
            b += (v >= i32(1 << k)).astype(i32)
    else:
        b = v
    b = jnp.where(v < i32(0), i32(-1), jnp.minimum(b, i32(num_bins - 1)))
    local = b - j * i32(block_b)  # this step's bin window
    onehot = local[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (v.shape[0], block_b), 1)
    out_ref[0, :] += jnp.sum(onehot, axis=0, dtype=jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("num_bins", "log2", "block_v", "block_b", "interpret")
)
def hist_counts(
    values: jax.Array,
    *,
    num_bins: int,
    log2: bool = False,
    block_v: int = 1024,
    block_b: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """int32 counts[ceil(num_bins/block_b) * block_b] of ``values``.

    values: (N, 1) int32, N a multiple of ``block_v``; negative entries
    are padding and counted nowhere.  With ``log2=False`` bin = value;
    with ``log2=True`` bin = 0 for value 0, else 1 + floor(log2 value).
    Values >= num_bins land in the last (overflow) bin either way, so
    the counts always sum to the number of non-negative values.  Only
    the first ``num_bins`` output entries are meaningful.  ``interpret``
    defaults to the platform's choice (:func:`repro.kernels.interpret_mode`).
    """
    n, one = values.shape
    assert one == 1 and n % block_v == 0, (values.shape, block_v)
    bpad = (num_bins + block_b - 1) // block_b * block_b
    grid = (bpad // block_b, n // block_v)  # value dim innermost: consecutive
    return pl.pallas_call(                  # revisits of each counts block
        functools.partial(_hist_kernel, num_bins=num_bins, block_b=block_b,
                          log2=log2),
        grid=grid,
        in_specs=[pl.BlockSpec((block_v, 1), lambda j, i: (i, _ZERO))],
        out_specs=pl.BlockSpec((1, block_b), lambda j, i: (_ZERO, j)),
        out_shape=jax.ShapeDtypeStruct((1, bpad), jnp.int32),
        interpret=interpret_mode() if interpret is None else interpret,
    )(values)[0]
