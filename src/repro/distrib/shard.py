"""Deprecated legacy facade over :mod:`repro.distrib.runtime`.

The original ``shard_map`` distribution of the generators lived here;
it is now three deprecated shims.  The per-family entry points predate
both the unified engine plans (PR 1/2) and the runtime executor (this
PR): new code should emit a plan (``repro.api`` spec ``.plan()`` or the
``core.*`` plan emitters) and hand it to
:func:`repro.distrib.runtime.run` / :func:`~repro.distrib.runtime.stream_waves`,
which own jit + ``shard_map``, compile caching and the zero-collective
assertion for every plan type.

The engine re-exports below are kept warning-free — they are the
stable names (``launch.dryrun``, benchmarks and tests import them
here) — only the three legacy per-family entry points warn.
"""
from __future__ import annotations

import dataclasses
import warnings

import numpy as np
from jax.sharding import Mesh

from ..core.er import gnm_directed_plan
from ..core.rgg import rgg_point_plan
from .engine import (  # noqa: F401  (re-exported public API)
    ChunkPlan,
    ChunkSpec,
    GEOM_CERT,
    GEOM_HYP,
    GEOM_TORUS,
    KIND_BA,
    KIND_DIRECTED,
    KIND_RMAT,
    PairPlan,
    PairSpec,
    PointPlan,
    assert_communication_free,
    collective_ops_in,
    COLLECTIVE_RE,
    deal_plan,
    edge_executor,
    make_chunk_plan,
    make_pair_plan,
    pair_executor,
    point_executor,
    run_edges,
    run_pairs,
    run_points,
    stream_chunk_edges,
    stream_pair_edges,
    stream_points,
)


def _mesh_size(mesh: Mesh) -> int:
    from . import runtime

    return runtime.mesh_size(mesh)


def _deprecated(name: str, instead: str) -> None:
    warnings.warn(
        f"repro.distrib.shard.{name} is a deprecated shim; {instead}",
        DeprecationWarning, stacklevel=3)


# --------------------------------------------------------------------------
# deprecated per-family entry points (runtime facades)
# --------------------------------------------------------------------------

def gnm_directed_sharded(
    seed: int, n: int, m: int, mesh: Mesh, axis: str = "pe",
    capacity: int | None = None, rng_impl: str = "threefry2x32",
):
    """Deprecated: build (jitted_fn, inputs) for the sharded G(n,m) step.

    Use ``er.gnm_directed_plan(...)`` + :func:`repro.distrib.runtime.executor`
    (or ``repro.api.generate(GNM(...), mesh=...)``).  Output is
    unchanged: the shim emits the same plan and hands it to the same
    runtime executor."""
    from . import runtime

    _deprecated("gnm_directed_sharded",
                "emit er.gnm_directed_plan and use repro.distrib.runtime.executor")
    P = _mesh_size(mesh)
    plan = gnm_directed_plan(seed, n, m, P, rng_impl)
    if capacity is not None:
        plan = dataclasses.replace(plan, capacity=capacity)
    return runtime.executor(plan, mesh)


def run_gnm_directed_sharded(seed: int, n: int, m: int, mesh: Mesh):
    """Deprecated: execute + gather; returns (edges [m,2], lowered_text).

    Use ``repro.api.generate(GNM(n, m, directed=True, chunks=P), mesh=...)``
    or :func:`repro.distrib.runtime.run` on an ``er.gnm_directed_plan``."""
    from . import runtime

    _deprecated("run_gnm_directed_sharded",
                "use repro.api.generate or repro.distrib.runtime.run")
    plan = gnm_directed_plan(seed, n, m, _mesh_size(mesh))
    edges, keep, hlo = runtime.run(plan, mesh, check=True, want_hlo=True)
    return np.asarray(edges)[np.asarray(keep)], hlo


def rgg_points_sharded(seed: int, n: int, radius: float, mesh: Mesh, dim: int = 2):
    """Deprecated: sharded RGG vertex generation (fn, inputs).

    Use ``rgg.rgg_point_plan(...)`` + :func:`repro.distrib.runtime.executor`,
    or stream positions with ``repro.api.iter_points(RGG(...))``."""
    from . import runtime

    _deprecated("rgg_points_sharded",
                "emit rgg.rgg_point_plan and use repro.distrib.runtime.executor "
                "(or stream via repro.api.iter_points)")
    plan = rgg_point_plan(seed, n, radius, _mesh_size(mesh), dim)
    return runtime.executor(plan, mesh)
