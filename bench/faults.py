"""Faults planted in the timed path, to show that a cell's comparison
catches them.  Each breaks the answers where they are produced: the
chunks that ``repro.api.iter_edge_chunks`` yields.

* ``altered``: in every chunk, one valid edge's second end moved to the
  next id;
* ``half_left_out``: every second valid edge of every chunk left out;
* ``stale``: every second chunk is the one before it, handed out again.

No cell has state that a step updates, and none exchanges anything
between chips, so these are the faults a stream cell can have.  Each
runs on the device, so that a broken stream keeps its pace.  The
benchmark's own runs never plant one: ``bench/control.py --fault`` and
``bench/tests`` do."""
from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.jit
def _alter(buf, mask):
    flat, ok = buf.reshape(-1, 2), mask.reshape(-1)
    i = jnp.argmax(ok)
    u, v = flat[i, 0], flat[i, 1]
    w = jnp.where(ok[i], jnp.where(v + 1 != u, v + 1, v + 2), v)
    return flat.at[i, 1].set(w).reshape(buf.shape), mask


@jax.jit
def _half(buf, mask):
    ok = mask.reshape(-1)
    keep = ok & (jnp.cumsum(ok, dtype=jnp.int32) % 2 == 0)
    return buf, keep.reshape(mask.shape)


class _Stale:
    def __init__(self):
        self.last, self.calls = None, 0

    def __call__(self, buf, mask):
        self.calls += 1
        if self.calls % 2 == 0 and self.last[0].shape == buf.shape:
            return self.last
        self.last = (buf, mask)
        return buf, mask


FAULTS = {"altered": lambda: _alter, "half_left_out": lambda: _half,
          "stale": _Stale}


class _Chunk:
    def __init__(self, buffer, mask, pe):
        self.buffer, self.mask, self.pe = buffer, mask, pe


def broken_stream(name: str):
    """``repro.api.iter_edge_chunks`` with fault ``name`` planted: put it
    in the program's place before the cell's driver is made."""
    from repro import api

    real, fault = api.iter_edge_chunks, FAULTS[name]()

    def broken(*a, **k):
        for c in real(*a, **k):
            yield _Chunk(*fault(c.buffer, c.mask), c.pe)

    return broken
