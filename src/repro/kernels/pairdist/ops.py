"""Point-block padding for the pairdist kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

_DPAD = 8  # sublane-friendly coordinate padding


def pad_points(pts: jax.Array) -> jax.Array:
    """(N, d) f32 -> (ceil128(N), _DPAD) with +inf padding rows.

    +inf rows give +inf distances, so padded entries can never pass the
    r^2 threshold — masks stay implicit.
    """
    n, d = pts.shape
    npad = (n + 127) // 128 * 128
    out = jnp.full((npad, _DPAD), jnp.inf, jnp.float32)
    return out.at[:n, :d].set(pts.astype(jnp.float32))
