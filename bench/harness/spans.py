"""A host span of the benchmark's own, recorded both in the JAX
profiler's trace (as ``jax.profiler.TraceAnnotation``) and in
``repro.obs`` (where tracing is on; a no-op otherwise)."""
from __future__ import annotations

import contextlib

from jax.profiler import TraceAnnotation


@contextlib.contextmanager
def span(name: str):
    from repro import obs

    with TraceAnnotation(name), obs.trace(name):
        yield
