"""One ``GraphSpec -> plan -> run`` front door for all seven families.

The paper's pitch is a *single* communication-free paradigm behind many
network models; this module is that paradigm as one library interface
(the KaGen shape):

1. **Spec**: a frozen dataclass (:class:`GNM`, :class:`GNP`,
   :class:`RGG`, :class:`RHG`, :class:`RDG`, :class:`BA`, :class:`RMAT`,
   :class:`SBM`) carrying seed + model parameters.
2. **Plan**: ``spec.plan(P, rng_impl=...)`` runs the host-side O(P)-ish
   divide-and-conquer recursion and emits the per-PE table
   (``ChunkPlan`` for sampled families, a geometry-kind-tagged
   ``PairPlan`` for RGG/RHG/RDG edges) that :mod:`repro.distrib.engine`
   executes as one zero-collective SPMD program.  ``PointPlan`` vertex
   tables remain available from the geometric emitters for callers that
   want positions only.
3. **Run / stream**: :func:`generate` executes the plan and returns a
   :class:`Graph`; :func:`iter_edge_chunks` yields fixed-capacity edge
   buffers chunk-by-chunk and :func:`iter_points` streams the
   geometric families' vertex positions — per-chunk counts are host
   data, so a 2^30-edge instance is consumed in O(capacity) memory
   instead of one [P, C, cap, 2] materialization.  Both execution
   paths live in :mod:`repro.distrib.runtime`: the streams ride its
   mesh-wide *wave* dispatch (``mesh=``, ``batch=``, ``prefetch=``),
   so streaming throughput scales with device count too.

Every spec produces the identical edge set for any P: the instance is
a function of the *virtual chunk grid* (the spec's ``chunks`` field,
default ``max(P, 16)`` — KaGen's chunks >= PEs decoupling), and P only
decides which PE executes which chunk/cell/pair.  (This is also why
:class:`RHG` runs on the P-independent engine cell layout rather than
the per-PE reference generator, whose cell grid is coupled to P.)

    >>> from repro.api import GNM, generate
    >>> g = generate(GNM(n=1000, m=8000, seed=1), P=4)
    >>> g.m, g.n
    (8000, 1000)
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterator, Optional, Protocol, Tuple, Union, runtime_checkable

import numpy as np

from .core import ba as _ba
from .core import er as _er
from .core import graph as _graph
from .core import rdg as _rdg
from .core import rgg as _rgg
from .core import rhg as _rhg
from .core import rmat as _rmat
from .core import sbm as _sbm
from .distrib import engine, runtime
from . import obs

DEFAULT_RNG = "threefry2x32"

# default virtual chunk-grid size: any P <= 16 generates the identical
# instance; larger machines grow the grid (chunks >= PEs) unless the
# spec pins `chunks` explicitly.
DEFAULT_CHUNKS = 16

Plan = Union["engine.ChunkPlan", "engine.PointPlan", "engine.PairPlan"]


def _virtual_chunks(chunks: Optional[int], P: int) -> int:
    return chunks if chunks else max(P, DEFAULT_CHUNKS)


# --------------------------------------------------------------------------
# results
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class Graph:
    """Generated edge list plus the metadata needed to interpret it."""
    edges: np.ndarray               # int64 [m, 2]
    n: int                          # number of vertices
    directed: bool = False
    points: Optional[np.ndarray] = None  # geometric families, [n, dim]

    @property
    def m(self) -> int:
        return len(self.edges)

    def degrees(self) -> np.ndarray:
        return _graph.degrees(self.edges, self.n, self.directed)


@dataclass(frozen=True)
class EdgeChunk:
    """One streamed chunk: a fixed-capacity device buffer + validity.

    ``mask`` is the authoritative validity (:meth:`edges` uses it when
    present); ``count`` is the host-known number of valid edges in the
    chunk — for an *unbatched* ChunkPlan buffer that is also a
    contiguous prefix length (``buffer[:count]`` is valid), but for
    batched buffers (``[b, cap, 2]``) validity is per row, so slice by
    ``mask``, never by ``count``.  Candidate-pair buffers have
    scattered validity and carry ``mask`` only.  The buffer never
    exceeds the plan's static capacity (times the stream ``batch``),
    which is how the streaming path keeps peak memory independent of
    total edge count.

    ``pe`` is the virtual PE that owns (emitted) this chunk — the
    plan's ownership stream index surfaced in-band as stream metadata
    (placement debugging, per-PE load accounting; a chunk never mixes
    PEs).  Note that :mod:`repro.stats` routes by *vertex* ownership,
    not chunk ownership: the stream being an exact once-per-chunk
    union is what its accumulators rely on, and that holds regardless
    of ``pe``.
    """
    buffer: object                  # [cap, 2] / [b, cap, 2] (device or host)
    count: Optional[int] = None     # valid edges in this chunk (ChunkPlan)
    mask: Optional[object] = None   # bool validity, same leading shape
    pe: Optional[int] = None        # owning virtual PE

    def edges(self) -> np.ndarray:
        """Materialize this chunk's valid edges on the host."""
        if self.mask is not None:
            return np.asarray(self.buffer)[np.asarray(self.mask)]
        return np.asarray(self.buffer)[: self.count]


@dataclass(frozen=True)
class PointChunk:
    """One streamed vertex-cell buffer: positions + validity + owner.

    The point analog of :class:`EdgeChunk` — :func:`iter_points` yields
    these so vertex positions of huge geometric instances stream in
    O(capacity) buffers instead of the [P, C, cap, dim]
    materialization of ``engine.run_points``."""
    buffer: object                  # [cap, dim] or [b, cap, dim] positions
    mask: object                    # bool [cap] / [b, cap] validity
    pe: Optional[int] = None        # owning virtual PE

    def points(self) -> np.ndarray:
        """Materialize this chunk's valid positions on the host."""
        return np.asarray(self.buffer)[np.asarray(self.mask)]


# --------------------------------------------------------------------------
# specs
# --------------------------------------------------------------------------

@runtime_checkable
class GraphSpec(Protocol):
    """What every family spec provides: parameters + a plan emitter."""
    seed: int

    @property
    def num_vertices(self) -> int: ...

    @property
    def directed(self) -> bool: ...

    def plan(self, P: int, *, rng_impl: str = DEFAULT_RNG) -> Plan: ...


@dataclass(frozen=True)
class GNM:
    """Erdős-Rényi G(n, m): exactly m distinct edges (paper §4).

    ``chunks`` sizes the virtual chunk grid (the instance); the legacy
    per-PE generators correspond to ``chunks == P``."""
    n: int
    m: int
    directed: bool = False
    seed: int = 0
    chunks: Optional[int] = None

    @property
    def num_vertices(self) -> int:
        return self.n

    def plan(self, P: int, *, rng_impl: str = DEFAULT_RNG):
        k = _virtual_chunks(self.chunks, P)
        f = _er.gnm_directed_plan if self.directed else _er.gnm_undirected_plan
        return engine.deal_plan(f(self.seed, self.n, self.m, k, rng_impl), P)


@dataclass(frozen=True)
class GNP:
    """Erdős-Rényi G(n, p): Bernoulli(p) per vertex pair (paper §4.3)."""
    n: int
    p: float
    directed: bool = False
    seed: int = 0
    chunks: Optional[int] = None

    @property
    def num_vertices(self) -> int:
        return self.n

    def plan(self, P: int, *, rng_impl: str = DEFAULT_RNG):
        k = _virtual_chunks(self.chunks, P)
        f = _er.gnp_directed_plan if self.directed else _er.gnp_undirected_plan
        return engine.deal_plan(f(self.seed, self.n, self.p, k, rng_impl), P)


@dataclass(frozen=True)
class RGG:
    """Random geometric graph in [0,1)^dim: edge iff dist <= radius (§5)."""
    n: int
    radius: float
    dim: int = 2
    seed: int = 0
    chunks: Optional[int] = None
    directed: bool = False

    @property
    def num_vertices(self) -> int:
        return self.n

    def plan(self, P: int, *, rng_impl: str = DEFAULT_RNG):
        return _rgg.rgg_pair_plan(self.seed, self.n, self.radius, P, self.dim,
                                  rng_impl, chunk_P=_virtual_chunks(self.chunks, P))

    def point_plan(self, P: int, *, rng_impl: str = DEFAULT_RNG):
        """PointPlan over the same virtual cell grid the edge plan
        regenerates, so streamed positions match ``Graph.points``."""
        return _rgg.rgg_point_plan(self.seed, self.n, self.radius, P, self.dim,
                                   rng_impl, chunk_P=_virtual_chunks(self.chunks, P))


@dataclass(frozen=True)
class RHG:
    """Threshold random hyperbolic graph (paper §7), power-law exponent
    ``gamma``, target average degree ``avg_deg``."""
    n: int
    avg_deg: float
    gamma: float
    seed: int = 0
    directed: bool = False

    @property
    def num_vertices(self) -> int:
        return self.n

    @property
    def params(self) -> _rhg.RHGParams:
        return _rhg.RHGParams(n=self.n, avg_deg=self.avg_deg,
                              gamma=self.gamma, seed=self.seed)

    def plan(self, P: int, *, rng_impl: str = DEFAULT_RNG):
        return _rhg.rhg_pair_plan(self.params, P, rng_impl)

    def point_plan(self, P: int, *, rng_impl: str = DEFAULT_RNG):
        """Polar PointPlan over the engine cell layout — the same
        hashed streams the pair plan recomputes for its edge tests."""
        return _rhg.rhg_engine_point_plan(self.params, P, rng_impl)


@dataclass(frozen=True)
class RDG:
    """Random Delaunay graph on the unit torus [0,1)^dim (paper §6)."""
    n: int
    dim: int = 2
    seed: int = 0
    chunks: Optional[int] = None
    directed: bool = False

    @property
    def num_vertices(self) -> int:
        return self.n

    def plan(self, P: int, *, rng_impl: str = DEFAULT_RNG):
        return _rdg.rdg_pair_plan(self.seed, self.n, P, self.dim, rng_impl,
                                  chunk_P=self.chunks or 0)

    def plan_segment(self, P: int, lo: int, hi: int, *,
                     rng_impl: str = DEFAULT_RNG):
        """Lazily emit the plan rows of PEs [lo, hi) only.  The device
        triangulation passes run once per seed (cached on the RDG
        planning structure); each segment just deals its PE slice."""
        return _rdg.rdg_plan_segment(self.seed, self.n, P, lo, hi, self.dim,
                                     rng_impl, chunk_P=self.chunks or 0)

    def point_plan(self, P: int, *, rng_impl: str = DEFAULT_RNG):
        """PointPlan over the RDG cell grid (same virtual chunk grid as
        the simplex-certificate edge plan)."""
        return _rdg.rdg_point_plan(self.seed, self.n, P, self.dim, rng_impl,
                                   chunk_P=self.chunks or 0)


@dataclass(frozen=True)
class BA:
    """Barabási-Albert preferential attachment, d edges per vertex
    (Sanders-Schulz chain resolution, paper §3.5.1)."""
    n: int
    d: int
    seed: int = 0
    directed: bool = True

    @property
    def num_vertices(self) -> int:
        return self.n

    def plan(self, P: int, *, rng_impl: str = DEFAULT_RNG):
        return _ba.ba_plan(self.seed, self.n, self.d, P, rng_impl)


@dataclass(frozen=True)
class RMAT:
    """Graph 500's Kronecker generator: R-MAT with ``n`` vertices (a
    power of two) and m edge tuples, self-loops and duplicates kept,
    labels randomly permuted (paper §3.5.2; :mod:`repro.core.rmat`).

    ``chunks`` sizes the virtual chunk grid of consecutive edge ids;
    every grid and every P give the same edges."""
    n: int
    m: int
    probs: Tuple[float, float, float, float] = (0.57, 0.19, 0.19, 0.05)
    seed: int = 0
    directed: bool = True
    chunks: Optional[int] = None

    def __post_init__(self):
        if self.n < 2 or self.n & (self.n - 1):
            raise ValueError(f"RMAT needs n a power of two >= 2, got {self.n}")

    @property
    def num_vertices(self) -> int:
        return self.n

    @property
    def log_n(self) -> int:
        return self.n.bit_length() - 1

    def plan(self, P: int, *, rng_impl: str = DEFAULT_RNG):
        k = _virtual_chunks(self.chunks, P)
        return engine.deal_plan(_rmat.rmat_plan(
            self.seed, self.log_n, self.m, k, self.probs, rng_impl), P)


@dataclass(frozen=True)
class SBM:
    """Stochastic block model: ``blocks`` equal groups, within-block
    probability p_in, cross-block p_out (paper §Future-Work)."""
    n: int
    blocks: int
    p_in: float
    p_out: float
    seed: int = 0
    directed: bool = False

    @property
    def num_vertices(self) -> int:
        return self.n

    def plan(self, P: int, *, rng_impl: str = DEFAULT_RNG):
        return _sbm.sbm_plan(self.seed, self.n, self.blocks,
                             self.p_in, self.p_out, P, rng_impl)

    def plan_segment(self, P: int, lo: int, hi: int, *,
                     rng_impl: str = DEFAULT_RNG):
        """Lazily emit the plan rows of PEs [lo, hi) only — the
        PE-range build :func:`plan_emitter` hands to the runtime's
        plan/execute overlap (cost scales with ``(hi - lo) / P``)."""
        return _sbm.sbm_plan_segment(self.seed, self.n, self.blocks,
                                     self.p_in, self.p_out, P, lo, hi,
                                     rng_impl)


# --------------------------------------------------------------------------
# execution
# --------------------------------------------------------------------------
#
# All execution — jit + shard_map, compile caching keyed on the plan's
# static signature, the once-per-program zero-collective assertion, and
# both the materializing and the wave-streaming paths — lives in
# repro.distrib.runtime.  Every plan type implements the runtime's
# PlanProgram protocol, so this module only extracts edges from the
# (payload, valid) outputs.


def _run_plan_edges(plan, mesh, check) -> np.ndarray:
    edges, keep, _ = runtime.run(plan, mesh, check=check)
    with obs.trace("extract", phase="sink"):
        return np.asarray(edges)[np.asarray(keep)]


def _geometric_points(spec, P: int, rng_impl: str) -> np.ndarray:
    """All vertex positions of a geometric spec in gid order (the
    ``return_points`` payload; oracle input for brute-force parity)."""
    if isinstance(spec, RHG):
        return _rhg.rhg_engine_all_points(spec.params, rng_impl)
    if isinstance(spec, RGG):
        grid = _rgg.make_grid(spec.n, spec.radius,
                              _virtual_chunks(spec.chunks, P), spec.dim)
    else:
        grid = _rdg.rdg_grid(
            spec.n, spec.chunks or _rdg.default_chunk_P(P, spec.dim), spec.dim)
    return _rgg_grid_points(spec.seed, grid, spec.n, rng_impl)


# --------------------------------------------------------------------------
# the public entry points
# --------------------------------------------------------------------------

def generate(
    spec: GraphSpec,
    P: int = 1,
    *,
    mesh=None,
    rng_impl: str = DEFAULT_RNG,
    check: bool = True,
    return_points: bool = False,
) -> Graph:
    """Generate ``spec`` across P virtual PEs; returns a :class:`Graph`.

    The edge set is identical for every P.  ``check=True`` asserts the
    zero-collective invariant on the lowered engine HLO (once per
    distinct program).  ``return_points`` additionally fills
    ``Graph.points`` for the geometric families (RGG/RDG/RHG).
    """
    plan = spec.plan(P, rng_impl=rng_impl)
    points = None
    if isinstance(plan, (engine.ChunkPlan, engine.PairPlan)):
        edges = _run_plan_edges(plan, mesh, check)
        if return_points and isinstance(plan, engine.PairPlan):
            points = _geometric_points(spec, P, rng_impl)
    else:
        raise TypeError(f"unknown plan type {type(plan).__name__}")
    return Graph(edges=edges, n=spec.num_vertices,
                 directed=spec.directed, points=points)


def collect(spec: GraphSpec, P: int = 1, **kwargs):
    """Streaming analytics over ``spec``: :func:`repro.stats.collect`.

    Convenience re-export so the generate/measure pair lives behind one
    front door; see :mod:`repro.stats` for the metric definitions."""
    from . import stats as _stats

    return _stats.collect(spec, P, **kwargs)


def validate(spec: GraphSpec, P: int = 1, **kwargs):
    """Goodness-of-fit of ``spec``'s output against its closed-form
    model law: :func:`repro.stats.validate` (re-export)."""
    from . import stats as _stats

    return _stats.validate(spec, P, **kwargs)


def verify_contracts(spec: GraphSpec, P: int = 1, *, mesh=None,
                     batch: int = 4, raise_on_violation: bool = True):
    """Statically verify ``spec``'s communication-free contracts.

    Lowers every program the spec emits (its edge plan and, for
    geometric families, its point plan — through both the runtime's
    materializing run step and the shard_map'd wave step) and walks the
    modules with :mod:`repro.analyze` Pass 1: zero collectives, no host
    callbacks, deterministic counter PRNG on recompute paths, static
    shapes.  Nothing executes — this is the paper's §2 invariant
    checked on the lowered IR, the same scanner ``generate(...,
    check=True)`` asserts with at runtime.  Returns the per-program
    reports; raises ``AssertionError`` on any violation unless
    ``raise_on_violation=False``.
    """
    from .analyze import programs as _programs

    reports = _programs.scan_spec(spec, P, mesh=mesh, batch=batch,
                                  name=type(spec).__name__.lower())
    bad = [r for r in reports if not r.ok]
    if bad and raise_on_violation:
        lines = [f"{r.name}: " + (r.error or "; ".join(
            f.detail for f in r.scan.findings)) for r in bad]
        raise AssertionError(
            "static contract violations:\n  " + "\n  ".join(lines))
    return reports


def _rgg_grid_points(seed: int, grid, n: int,
                     rng_impl: str = DEFAULT_RNG) -> np.ndarray:
    """All points of a cube cell grid in gid order (RGG/RDG helper);
    follows the same hashed stream the pair plans regenerate on device."""
    counter = _rgg.CellCounter(seed, grid, n)
    cells = [tuple(c) for c in np.ndindex(*([grid.g] * grid.dim))]
    pos, counts, offsets, _ = _rgg.points_for_cells(seed, grid, counter, cells,
                                                    rng_impl)
    out = np.zeros((n, grid.dim))
    for i in range(len(cells)):
        out[offsets[i]: offsets[i] + counts[i]] = pos[i][: counts[i]]
    return out


def plan_emitter(
    spec: GraphSpec,
    P: int = 1,
    *,
    segments: int = 0,
    rng_impl: str = DEFAULT_RNG,
) -> "runtime.PlanEmitter":
    """A lazily segmented plan for ``spec``: the input of the runtime's
    plan/execute overlap path (:class:`repro.distrib.runtime.PlanEmitter`).

    Families that implement ``plan_segment(P, lo, hi)`` (e.g.
    :class:`SBM`) emit each PE-range natively at ``(hi - lo) / P`` of
    the full plan cost, so the first segment's waves execute while the
    background planner emits the rest, and the first chunk waits for
    one segment's plan, not the whole table's.  Other families fall back to one
    full emission *on the planner thread* (first ``build`` call) plus
    ``slice_plan`` segmentation — same ordering/bit-identity contract,
    planning merely moved off the consumer thread.  ``segments=0``
    picks the runtime default.
    """
    seg_fn = getattr(spec, "plan_segment", None)
    if seg_fn is not None:
        build = lambda lo, hi: seg_fn(P, lo, hi, rng_impl=rng_impl)
    else:
        state = {}

        def build(lo: int, hi: int):
            if "plan" not in state:
                state["plan"] = spec.plan(P, rng_impl=rng_impl)
            return engine.slice_plan(state["plan"], lo, hi)

    return runtime.PlanEmitter(P, build, segments)


def iter_edge_chunks(
    spec: GraphSpec,
    P: int = 1,
    *,
    mesh=None,
    rng_impl: str = DEFAULT_RNG,
    check: bool = False,
    batch: int = 1,
    prefetch: int = 2,
    overlap: int = 0,
) -> Iterator[EdgeChunk]:
    """Stream ``spec``'s edges as :class:`EdgeChunk` wave rows.

    Every family streams through the runtime's **wave** path: each
    dispatch executes the next ``batch`` chunks / candidate pairs of
    *every* mesh row simultaneously under ``shard_map`` (streaming
    scales with device count, not just :func:`generate`), with
    ``prefetch`` waves kept in flight so wave k+1 is dispatched before
    chunk k is consumed.  Peak memory is O(devices · batch · capacity),
    never O(total edges), and per-chunk capacities are host-known plan
    data: the consumer can size downstream buffers before any device
    work happens.

    Each chunk carries the id of its owning PE (``chunk.pe``, from the
    plan's ownership stream index; a chunk never mixes PEs).  Per-PE
    order is exact: grouping chunks by ``pe`` and concatenating
    ``chunk.edges()`` reproduces ``generate(spec, P).edges`` — and on a
    single-device mesh the stream order itself is generate order, so
    plain concatenation reproduces it too.  ``batch > 1`` yields
    batched buffers ([b, cap, 2] with a [b, cap] mask); ``mesh``
    accepts any mesh whose size divides P, including a multi-process
    ``jax.make_mesh``; ``check`` asserts the zero-collective invariant
    on the lowered wave step itself (once per program signature).

    Each chunk's buffer and mask are the wave program's own output
    buffers on that mesh row's device: no eager op runs between the
    wave step and the consumer.  An unbatched stream (``batch <= 1``)
    drops the unit batch axis inside the program.  On a mesh of more
    than one device a chunk therefore lives on its own mesh row's device
    alone, not replicated: bring chunks of different rows to one device
    (or the host, ``jax.device_get``) before combining them.

    ``overlap > 0`` streams through a lazily segmented plan
    (:func:`plan_emitter` with that many segments): plan emission runs
    on a background thread while earlier segments' waves execute, so
    the first chunk of a cold stream waits for one segment's plan, not
    the whole table's.  Chunk edges, PE ids and per-PE
    order are identical to the non-overlapped stream; ``count``
    metadata is omitted (``mask`` stays authoritative).
    """
    if overlap:
        source = plan_emitter(spec, P, segments=int(overlap),
                              rng_impl=rng_impl)
        chunk_counts = None
    else:
        source = spec.plan(P, rng_impl=rng_impl)
        if not isinstance(source, (engine.ChunkPlan, engine.PairPlan)):
            raise TypeError(f"unknown plan type {type(source).__name__}")
        chunk_counts = (source.count if isinstance(source, engine.ChunkPlan)
                        else None)
    for pe, slots, payload, valid in runtime.stream_slots(
            source, mesh=mesh, batch=batch, prefetch=prefetch, check=check):
        with obs.trace("stream/chunk", phase="sink"):
            count = (int(chunk_counts[pe, slots].sum())
                     if chunk_counts is not None else None)
            chunk = EdgeChunk(buffer=payload, mask=valid, count=count,
                              pe=int(pe))
        yield chunk


def iter_points(
    spec: GraphSpec,
    P: int = 1,
    *,
    mesh=None,
    rng_impl: str = DEFAULT_RNG,
    check: bool = False,
    batch: int = 1,
    prefetch: int = 2,
) -> Iterator[PointChunk]:
    """Stream a geometric spec's vertex positions as :class:`PointChunk`.

    The streaming route to ``Graph.points``: ``generate(...,
    return_points=True)`` materializes all n positions, this yields
    O(batch · capacity) cell buffers through the same runtime wave path
    as :func:`iter_edge_chunks` (whole-mesh dispatch, prefetch
    double-buffering, zero-collective-checked wave step).  Positions
    follow the exact hashed per-cell streams the family's edge plan
    recomputes, in gid order within each PE: grouping by ``pe`` and
    concatenating ``chunk.points()`` reproduces the masked
    ``engine.run_points`` output of ``spec.point_plan(P)``.  Chunks are
    the wave program's own buffers, placed as in
    :func:`iter_edge_chunks`.
    """
    point_plan = getattr(spec, "point_plan", None)
    if point_plan is None:
        raise TypeError(
            f"{type(spec).__name__} has no vertex positions to stream "
            f"(only the geometric families RGG/RDG/RHG carry points)")
    plan = point_plan(P, rng_impl=rng_impl)
    for pe, slots, payload, valid in runtime.stream_slots(
            plan, mesh=mesh, batch=batch, prefetch=prefetch, check=check):
        yield PointChunk(buffer=payload, mask=valid, pe=int(pe))


def serve(specs, P: int = 1, **kwargs):
    """Serve many concurrent specs off one mesh: :func:`repro.serve.serve`.

    Bit-identical to ``[generate(s, P) for s in specs]``, but requests
    resolve plans through a re-seedable cache and their ready slots
    pack into shared mixed-request slabs (see :mod:`repro.serve`).
    Keyword arguments forward to :class:`repro.serve.Service`; use the
    ``Service`` class directly for streaming consumption, continuous
    admission and per-request latency metrics."""
    from .serve import serve as _serve

    return _serve(specs, P, **kwargs)


def make_service(P: int = 1, **kwargs):
    """Construct a :class:`repro.serve.Service` (lazy front door)."""
    from .serve import Service

    return Service(P, **kwargs)
