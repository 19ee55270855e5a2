"""Generator-agnostic zero-collective sharded execution engine.

The paper's headline property — embarrassingly parallel, communication-
free generation — is realized here as a *table-driven* SPMD program:

1. The HLO zero-collective assertion as a reusable invariant
   (``assert_communication_free``).

2. ``ChunkPlan`` / ``PointPlan``: per-PE tables — chunk keys, universes,
   counts, fixed capacities and decode parameters — emitted by the host
   divide-and-conquer recursions (the only O(P)-ish sequential work).

3. One jitted SPMD program for *every* plan type, owned by
   :mod:`repro.distrib.runtime`: each plan implements the
   ``PlanProgram`` protocol (``input_arrays`` / ``slot_fn`` /
   ``stream_index`` / ``signature``) and the runtime supplies
   jit + ``shard_map``, compile caching, the zero-collective
   assertion, materializing runs and mesh-wide wave streaming.  The
   ``edge_executor``/``run_edges``/``stream_chunk_edges``,
   ``point_executor``/``run_points`` and
   ``pair_executor``/``run_pairs``/``stream_pair_edges`` entry points
   below are thin facades over it, kept for their call sites.

Exact union without sorting: each chunk row carries an ``owned`` bit.
Undirected chunk (I, J) is generated bit-identically on PE I and PE J
(the paper's <= 2m recomputation bound) but *kept* only by its
designated owner (the row PE), so the concatenated output is exactly
the global edge set — no O(m log m) ``np.unique`` dedup.

Plan emitters live next to their generators: ``core.er`` (directed and
undirected G(n,m), G(n,p)), ``core.rgg`` (cube vertex plans + GEOM_TORUS
pair plans), ``core.rdg`` (GEOM_CERT simplex-certificate pair plans) and
``core.rhg`` (polar vertex plans + GEOM_HYP pair plans).  The geometric
edge phase is one kind-tagged ``PairPlan`` executor shared by all three
families.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ..core.prng import counter_uniform, fold_in64
from ..core.rmat import edges as rmat_edges, thresholds as rmat_thresholds
from ..core.sampling import (
    decode_directed,
    decode_rect,
    decode_tri,
    round_up_capacity,
    sample_wo_replacement,
)

# --------------------------------------------------------------------------
# the zero-collective invariant
# --------------------------------------------------------------------------
#
# One scanner, shared verbatim with the static CI gate: the historical
# names below re-export repro.analyze.hloscan (Pass 1 of the contract
# verifier), so the runtime's check=True path and `python -m
# repro.analyze --all-programs` walk lowered modules with the same
# code.  The scanner matches both the StableHLO spelling
# (`stablehlo.all_reduce`) of Lowered.as_text() and the hyphenated HLO
# spelling of Compiled.as_text() — the original engine regex knew only
# the latter, so a planted psum in the StableHLO lowering passed the
# "assertion" unseen (tests/test_analyze.py now plants one to keep the
# scanner honest).

from ..analyze.hloscan import (  # noqa: F401  (re-exported invariant)
    COLLECTIVE_RE,
    assert_communication_free,
    collective_ops_in,
)


def default_mesh(P: int, axis: str = "pe") -> Mesh:
    """1-D mesh over the most local devices that divide P evenly."""
    ndev = len(jax.devices())
    use = max(d for d in range(1, min(ndev, P) + 1) if P % d == 0)
    return Mesh(np.array(jax.devices()[:use]), (axis,))


# --------------------------------------------------------------------------
# edge plans: the unified ER-family table
# --------------------------------------------------------------------------

# chunk kinds understood by the SPMD edge step
KIND_EMPTY, KIND_DIRECTED, KIND_TRI, KIND_RECT, KIND_RMAT, KIND_BA = 0, 1, 2, 3, 4, 5

# kinds whose edges come from the without-replacement index sampler
SAMPLED_KINDS = frozenset({KIND_DIRECTED, KIND_TRI, KIND_RECT})


@dataclass(frozen=True)
class ChunkSpec:
    """One chunk as the host D&C recursion emits it.

    ``params`` is kind-specific: DIRECTED -> (row_lo, n, 0) (the global
    vertex count rides in the table so the decode is data, not a
    compile-time constant — plans for different n share one program);
    TRI -> (lo, 0, 0); RECT -> (width, rlo, clo); RMAT -> (log_n,
    edge_lo, 0); BA -> (d, edge_lo, 0).  ``fparams`` holds kind-specific
    reals (RMAT: the (a, b, c) quadrant probabilities).

    ``key`` is the PRNG key of the chunk's hash path — either a typed
    JAX key or its raw uint32 key data (emitters batch-compute the
    latter to avoid per-chunk dispatches).
    """
    kind: int
    key: object             # jax key or uint32 key-data array
    universe: int
    count: int
    params: Tuple[int, int, int]
    owned: bool = True
    fparams: Tuple[float, ...] = ()


@dataclass(frozen=True)
class ChunkPlan:
    """Host-emitted table driving the unified SPMD edge engine.

    All arrays have leading dims [P, C] (PE x chunk slot, padded with
    KIND_EMPTY rows); the device program is pure table execution.
    """
    kind: np.ndarray        # int32  [P, C]
    key_data: np.ndarray    # uint32 [P, C, W]  (W = key words of rng_impl)
    universe: np.ndarray    # int64  [P, C]
    count: np.ndarray       # int64  [P, C]
    params: np.ndarray      # int64  [P, C, 3]
    fparams: np.ndarray     # float64 [P, C, 4]
    owned: np.ndarray       # bool   [P, C]
    n: int                  # global vertex count (metadata; decode reads params)
    capacity: int           # fixed per-chunk buffer (static shape)
    rng_impl: str = "threefry2x32"
    # seed -> equivalent plan for that seed, closing over the
    # seed-independent structure (see reseed()); excluded from the
    # signature so reseeded plans share compiled programs.
    reseed_fn: Optional[Callable[[int], "ChunkPlan"]] = field(
        default=None, compare=False, repr=False)

    @property
    def num_pes(self) -> int:
        return self.kind.shape[0]

    @property
    def chunks_per_pe(self) -> int:
        return self.kind.shape[1]

    @property
    def total_edges(self) -> int:
        return int(self.count[self.owned].sum())

    @property
    def kinds_present(self) -> Tuple[int, ...]:
        """Distinct non-empty chunk kinds — static per plan, so the
        device program only lowers the decode paths it actually needs."""
        return tuple(sorted(int(k) for k in np.unique(self.kind) if k != KIND_EMPTY))  # repro: allow(no-numpy-unique) O(P*C) static plan metadata, not edge dedup

    @property
    def rmat_log_n(self) -> int:
        """Static descent depth shared by every RMAT chunk in the plan."""
        sel = self.kind == KIND_RMAT
        return int(self.params[sel, 0].max()) if sel.any() else 0

    @property
    def rmat_thresholds(self) -> Tuple[int, ...]:
        """Static descent thresholds (:func:`repro.core.rmat.thresholds`)
        of the one probability triple every RMAT chunk in the plan shares;
        ``()`` without RMAT chunks."""
        probs = self.fparams[self.kind == KIND_RMAT, :3]
        if not len(probs):
            return ()
        if (probs != probs[0]).any():
            raise ValueError("RMAT chunks of one plan must share their "
                             "probabilities (a, b, c)")
        return rmat_thresholds(probs[0])

    # ---- PlanProgram protocol (repro.distrib.runtime) ----

    def input_arrays(self) -> Tuple[np.ndarray, ...]:
        return _plan_arrays(self)

    def slot_fn(self):
        return _edge_chunk_fn(self.capacity, self.rng_impl,
                              self.kinds_present, self.rmat_log_n,
                              self.rmat_thresholds)

    def stream_index(self) -> np.ndarray:
        return owned_chunk_index(self)

    def signature(self) -> tuple:
        # n is deliberately absent: the directed decode reads it from
        # params, so plans differing only in n share one compiled program.
        return ("chunk", self.kind.shape, self.key_data.shape[-1],
                self.capacity, self.rng_impl, self.kinds_present,
                self.rmat_log_n, self.rmat_thresholds)

    def reseed(self, seed: int) -> "ChunkPlan":
        """The plan this emitter would have produced for ``seed``.

        Costs only the seed-*dependent* work (counts + key columns);
        the structure tables are reused.  The serving plan cache's hit
        path is exactly this call."""
        if self.reseed_fn is None:
            raise ValueError(
                "plan carries no reseed emitter; re-emit from the GraphSpec")
        from .. import obs
        with obs.trace("plan/reseed", phase="plan", reseed=True,
                       plan=type(self).__name__):
            return self.reseed_fn(int(seed))


def _key_data_of(key) -> np.ndarray:
    """Accepts a typed JAX key or precomputed uint32 key data."""
    if isinstance(key, np.ndarray):
        return key.ravel()
    return np.asarray(jax.random.key_data(key)).ravel()


def make_chunk_plan(
    per_pe: Sequence[Sequence[ChunkSpec]],
    n: int,
    capacity: Optional[int] = None,
    rng_impl: str = "threefry2x32",
) -> ChunkPlan:
    """Pad per-PE chunk lists into the rectangular plan tables."""
    P = len(per_pe)
    C = max(1, max((len(row) for row in per_pe), default=1))
    first = next((row[0] for row in per_pe if row), None)
    width = len(_key_data_of(first.key)) if first is not None else 2
    kind = np.zeros((P, C), np.int32)
    key_data = np.zeros((P, C, width), np.uint32)
    universe = np.zeros((P, C), np.int64)
    count = np.zeros((P, C), np.int64)
    params = np.zeros((P, C, 3), np.int64)
    fparams = np.zeros((P, C, 4), np.float64)
    owned = np.zeros((P, C), bool)
    for pe, row in enumerate(per_pe):
        for j, spec in enumerate(row):
            kind[pe, j] = spec.kind
            key_data[pe, j] = _key_data_of(spec.key)
            universe[pe, j] = spec.universe
            count[pe, j] = spec.count
            params[pe, j] = spec.params
            if spec.fparams:
                fparams[pe, j, : len(spec.fparams)] = spec.fparams
            owned[pe, j] = spec.owned
    cap = capacity if capacity is not None else round_up_capacity(int(count.max()) if count.size else 0)
    return ChunkPlan(kind, key_data, universe, count, params, fparams, owned, n, cap, rng_impl)


def chunk_plan_from_columns(
    P: int,
    pe: np.ndarray,
    kind: np.ndarray,
    key_data: np.ndarray,
    universe: np.ndarray,
    count: np.ndarray,
    params: np.ndarray,
    owned: np.ndarray,
    n: int,
    fparams: Optional[np.ndarray] = None,
    capacity: Optional[int] = None,
    rng_impl: str = "threefry2x32",
) -> ChunkPlan:
    """Vectorized :func:`make_chunk_plan`: flat per-chunk columns in.

    ``pe`` [k] assigns each flat row to its PE; within-PE slot order is
    the rows' order of appearance (a stable sort groups them), exactly
    the order a per-PE ``ChunkSpec`` list would have had.  All other
    columns are [k] / [k, W] / [k, 3] / [k, F<=4] arrays.  Capacity
    defaults follow :func:`make_chunk_plan`, so a column-built plan is
    bit-identical to the padded-list path given the same rows."""
    pe = np.asarray(pe, np.int64)
    k = len(pe)
    per = np.bincount(pe, minlength=P) if k else np.zeros(P, np.int64)
    C = max(1, int(per.max()) if per.size else 0)
    W = key_data.shape[-1] if k else 2
    order = np.argsort(pe, kind="stable")
    spe = pe[order]
    starts = np.concatenate(([0], np.cumsum(per)))
    col = np.arange(k, dtype=np.int64) - starts[spe]
    t_kind = np.zeros((P, C), np.int32)
    t_key = np.zeros((P, C, W), np.uint32)
    t_uni = np.zeros((P, C), np.int64)
    t_cnt = np.zeros((P, C), np.int64)
    t_par = np.zeros((P, C, 3), np.int64)
    t_fpar = np.zeros((P, C, 4), np.float64)
    t_own = np.zeros((P, C), bool)
    if k:
        t_kind[spe, col] = np.asarray(kind, np.int32)[order]
        t_key[spe, col] = np.asarray(key_data, np.uint32)[order]
        t_uni[spe, col] = np.asarray(universe, np.int64)[order]
        t_cnt[spe, col] = np.asarray(count, np.int64)[order]
        t_par[spe, col] = np.asarray(params, np.int64)[order]
        if fparams is not None:
            fp = np.asarray(fparams, np.float64)
            t_fpar[spe, col, : fp.shape[-1]] = fp[order]
        t_own[spe, col] = np.asarray(owned, bool)[order]
    cap = capacity if capacity is not None else round_up_capacity(
        int(count.max()) if k else 0)
    return ChunkPlan(t_kind, t_key, t_uni, t_cnt, t_par, t_fpar, t_own,
                     n, cap, rng_impl)


def deal_plan(plan: ChunkPlan, P: int) -> ChunkPlan:
    """Re-deal a plan built for k *virtual* chunks onto P real PEs.

    The generated instance is a function of the virtual chunk grid, not
    of the machine size (KaGen's chunks >= PEs decoupling): the owned
    rows of the k-PE plan are dealt round-robin onto P PEs, so any P
    executes the identical edge set.  Mirror (recomputed, un-owned)
    rows are dropped — ownership already makes the union exact.
    """
    from .. import obs
    with obs.trace("plan/deal", phase="plan", P=P, virtual=plan.num_pes):
        return _deal_plan(plan, P)


def _deal_plan(plan: ChunkPlan, P: int) -> ChunkPlan:
    # np.argwhere walks v-major, c-minor — the exact order the old
    # per-row append loop visited, so dealing by stable sort on v % P
    # reproduces its slot layout without any per-chunk Python work.
    idx = np.argwhere(plan.owned & (plan.kind != KIND_EMPTY))
    src = (idx[:, 0], idx[:, 1])
    dealt = chunk_plan_from_columns(
        P, idx[:, 0] % P, plan.kind[src], plan.key_data[src],
        plan.universe[src], plan.count[src], plan.params[src],
        np.ones(len(idx), bool), plan.n, fparams=plan.fparams[src],
        capacity=plan.capacity, rng_impl=plan.rng_impl)
    reseed = None
    if plan.reseed_fn is not None:
        reseed = lambda s, _p=plan, _P=P: deal_plan(_p.reseed(s), _P)
    return dataclasses.replace(dealt, reseed_fn=reseed)


def reseedable_chunk_plan(plan: ChunkPlan, key_fn: Callable[[int], np.ndarray],
                          count_fn: Optional[Callable[[int], np.ndarray]] = None,
                          ) -> ChunkPlan:
    """Attach a structure/seed-split reseed emitter to a ChunkPlan.

    The kind/universe/params/fparams/owned tables of the ER-family and
    preferential-attachment plans depend only on the *shape* of the spec
    (n, m/p, chunk grid) — never on the seed.  Reseeding therefore
    reduces to recomputing the two seed-dependent columns against the
    cached structure:

    * ``key_fn(seed) -> uint32 [k, W]`` — key data for the k non-empty
      chunks in table (pe-major) order, and
    * ``count_fn(seed) -> int64 [k]`` — their edge counts (omit for
      families like BA/RMAT whose counts are seed-independent, where
      reseeding is a pure key swap).

    The derived capacity follows :func:`make_chunk_plan`'s default rule
    so a reseeded plan is bit-identical to a cold emission."""
    pos = np.argwhere(plan.kind != KIND_EMPTY)
    idx = (pos[:, 0], pos[:, 1])

    def emit(seed: int) -> ChunkPlan:
        if count_fn is None:
            count, cap = plan.count, plan.capacity
        else:
            flat = np.asarray(count_fn(seed), np.int64)
            count = np.zeros_like(plan.count)
            count[idx] = flat
            cap = round_up_capacity(int(flat.max()) if flat.size else 0)
        key_data = np.zeros_like(plan.key_data)
        key_data[idx] = np.asarray(key_fn(seed), np.uint32)
        return dataclasses.replace(plan, key_data=key_data, count=count,
                                   capacity=cap, reseed_fn=emit)

    return dataclasses.replace(plan, reseed_fn=emit)


def _edge_chunk_fn(capacity: int, rng_impl: str,
                   kinds: Sequence[int] = SAMPLED_KINDS, log_n: int = 0,
                   rmat_thr: Tuple[int, ...] = ()):
    """Per-chunk device program, specialized to the kinds in the plan.

    Sampled kinds (DIRECTED/TRI/RECT) share one without-replacement
    index draw + per-kind decode; RMAT runs the per-edge hashed quadrant
    descent (one fold_in per edge id, ``log_n`` 64-bit words compared
    with the static integer thresholds ``rmat_thr``) and Graph 500's
    vertex permutation (:func:`repro.core.rmat.edges`); BA resolves
    the Batagelj-Brandes position chain with a hashed ``while_loop``
    (Sanders-Schulz).  Only the branches for kinds actually present are
    lowered, so an RMAT plan never pays for the sampler's sort and vice
    versa.  All draws are capacity-independent per slot, preserving the
    cross-PE recomputation invariant.
    """
    kinds = frozenset(int(k) for k in kinds) - {KIND_EMPTY}
    sampled = kinds & SAMPLED_KINDS

    def one_chunk(kind, kd, universe, count, params, fparams, owned):
        key = jax.random.wrap_key_data(kd, impl=rng_impl)
        p0, p1 = params[0], params[1]
        idx = jnp.arange(capacity, dtype=jnp.int64)
        u = v = jnp.zeros(capacity, jnp.int64)

        if sampled:
            vals, _ = sample_wo_replacement(key, universe, count, capacity)
            if KIND_DIRECTED in sampled:
                du, dv = decode_directed(vals, p1, p0)  # p1 = global n (traced)
                u = jnp.where(kind == KIND_DIRECTED, du, u)
                v = jnp.where(kind == KIND_DIRECTED, dv, v)
            if KIND_TRI in sampled:
                tu, tv = decode_tri(vals, p0)
                u = jnp.where(kind == KIND_TRI, tu, u)
                v = jnp.where(kind == KIND_TRI, tv, v)
            if KIND_RECT in sampled:
                width = jnp.maximum(jnp.where(kind == KIND_RECT, p0, 1), 1)
                ru, rv = decode_rect(vals, width, params[1], params[2])
                u = jnp.where(kind == KIND_RECT, ru, u)
                v = jnp.where(kind == KIND_RECT, rv, v)

        if KIND_RMAT in kinds:
            ru, rv = rmat_edges(key, p1 + idx, rmat_thr, log_n)
            u = jnp.where(kind == KIND_RMAT, ru, u)
            v = jnp.where(kind == KIND_RMAT, rv, v)

        if KIND_BA in kinds:
            d = jnp.maximum(p0, 1)
            is_ba = kind == KIND_BA

            def resolve(eid):
                # non-BA chunks start at an even position: zero iterations
                pos = jnp.where(is_ba, 2 * eid + 1, jnp.int64(0))

                def cond(p):
                    return (p % 2) == 1

                def body(p):
                    kk = fold_in64(key, p)
                    return jax.random.randint(kk, (), 0, p, dtype=jnp.int64)

                pos = jax.lax.while_loop(cond, body, pos)
                return (pos // 2) // d

            eids = p1 + idx
            u = jnp.where(is_ba, eids // d, u)
            v = jnp.where(is_ba, jax.vmap(resolve)(eids), v)

        keep = (idx < count) & owned & (kind != KIND_EMPTY)
        return jnp.stack([u, v], axis=-1), keep

    return one_chunk


_EDGE_INPUTS = ("kind", "key_data", "universe", "count", "params", "fparams", "owned")


def _plan_arrays(plan: ChunkPlan):
    return tuple(getattr(plan, name) for name in _EDGE_INPUTS)


def edge_executor(plan: ChunkPlan, mesh: Mesh):
    """(jitted fn, sharded inputs) for the plan's SPMD edge step.

    fn(*inputs) -> (edges [P, C, cap, 2], keep [P, C, cap]); ``keep``
    already folds in validity masks and canonical chunk ownership.
    Facade over :func:`repro.distrib.runtime.executor`.
    """
    from . import runtime

    return runtime.executor(plan, mesh)


def run_edges(plan: ChunkPlan, mesh: Optional[Mesh] = None, check: bool = True):
    """Execute a ChunkPlan; returns (edges [k, 2] int64, hlo_text).

    The output is the exact global edge set: every chunk is emitted by
    its designated owner only, so no sort/unique dedup is needed.
    Facade over :func:`repro.distrib.runtime.run`.
    """
    from . import runtime

    edges, keep, hlo = runtime.run(plan, mesh, check=check, want_hlo=True)
    return np.asarray(edges)[np.asarray(keep)], hlo


def owned_chunk_index(plan: ChunkPlan) -> np.ndarray:
    """int64 [K, 2] of (pe, slot) for every owned non-empty chunk, in
    stream order (pe-major — exactly :func:`stream_chunk_edges` order).

    This is the plan's *ownership mask* as an index: each global chunk
    appears exactly once (mirrored recomputed chunks are excluded), so
    any per-chunk consumer that walks it — edge writers, the
    :mod:`repro.stats` accumulators — sees the exact global edge
    multiset with no sort/unique dedup; the (pe, slot) rows additionally
    say which PE emitted what (surfaced as ``EdgeChunk.pe``).
    """
    sel = plan.owned & (plan.kind != KIND_EMPTY)
    return np.argwhere(sel).astype(np.int64)


def stream_chunk_edges(plan: ChunkPlan, check: bool = False, with_pe: bool = False,
                       mesh: Optional[Mesh] = None, prefetch: int = 2):
    """Yield (buffer [cap, 2], count) per *owned* chunk.

    The streaming consumer path: per-chunk counts are host data, so a
    2^30-edge plan is emitted chunk-by-chunk into O(capacity) buffers
    instead of a [P, C, cap, 2] materialization.  Valid edges are the
    first ``count`` rows (owned chunks always have a contiguous
    validity prefix).  Facade over
    :func:`repro.distrib.runtime.stream_slots` at batch=1: chunks
    arrive in wave order — on a single-device mesh that is exactly
    :func:`owned_chunk_index` (= :func:`run_edges`) order; on wider
    meshes per-PE order is preserved and grouping by ``pe`` reproduces
    the run output.  ``check`` asserts zero collectives on the lowered
    wave step itself (the shard_map'd dispatch, once per program
    signature).  ``with_pe`` prepends the owning PE to each tuple.
    """
    from . import runtime

    for pe, slots, payload, _ in runtime.stream_slots(
            plan, mesh=mesh, batch=1, prefetch=prefetch, check=check):
        out = (payload, int(plan.count[pe, slots[0]]))
        yield (int(pe), *out) if with_pe else out


# --------------------------------------------------------------------------
# point plans: spatial (RGG cube cells) and radial (RHG annulus cells)
# --------------------------------------------------------------------------

POINTS_CUBE, POINTS_POLAR = "cube", "polar"


@dataclass(frozen=True)
class PointPlan:
    """Per-PE cell table for sharded vertex generation.

    kind == 'cube':  point = (cell + u) / scale           (scale = grid g)
    kind == 'polar': r = arccosh(g0 + u0*(g1 - g0)) / scale  (scale = alpha)
                     theta = (cell[1] + u1) * g2
    """
    kind: str               # POINTS_CUBE | POINTS_POLAR (static)
    key_data: np.ndarray    # uint32  [P, C, W] per-cell key
    count: np.ndarray       # int64   [P, C]
    cell: np.ndarray        # int64   [P, C, K] integer cell coordinates
    geom: np.ndarray        # float64 [P, C, G] kind-specific reals
    scale: float
    dim: int                # output dims per point
    capacity: int
    rng_impl: str = "threefry2x32"
    reseed_fn: Optional[Callable[[int], "PointPlan"]] = field(
        default=None, compare=False, repr=False)

    @property
    def num_pes(self) -> int:
        return self.count.shape[0]

    @property
    def total_points(self) -> int:
        return int(self.count.sum())

    # ---- PlanProgram protocol (repro.distrib.runtime) ----

    def input_arrays(self) -> Tuple[np.ndarray, ...]:
        return (self.key_data, self.count, self.cell, self.geom)

    def slot_fn(self):
        return _point_cell_fn(self.kind, self.capacity, self.dim,
                              self.scale, self.rng_impl)

    def stream_index(self) -> np.ndarray:
        """Non-empty cells in pe-major order (cells are globally unique
        by construction, so every populated cell is 'owned')."""
        return np.argwhere(self.count > 0).astype(np.int64)

    def signature(self) -> tuple:
        return ("point", self.kind, self.count.shape,
                self.key_data.shape[-1], self.cell.shape[-1],
                self.geom.shape[-1], self.scale, self.dim, self.capacity,
                self.rng_impl)

    def reseed(self, seed: int) -> "PointPlan":
        """Equivalent plan for ``seed`` from the cached cell structure
        (see :meth:`ChunkPlan.reseed`)."""
        if self.reseed_fn is None:
            raise ValueError(
                "plan carries no reseed emitter; re-emit from the GraphSpec")
        from .. import obs
        with obs.trace("plan/reseed", phase="plan", reseed=True,
                       plan=type(self).__name__):
            return self.reseed_fn(int(seed))


def make_point_plan(
    per_pe: Sequence[Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]],
    kind: str,
    scale: float,
    dim: int,
    capacity: Optional[int] = None,
    rng_impl: str = "threefry2x32",
) -> PointPlan:
    """per_pe: one (key_data [Ci,W], counts [Ci], cells [Ci,K], geom [Ci,G])
    tuple per PE; rows are padded to the widest PE with count-0 cells."""
    P = len(per_pe)
    C = max(1, max(int(len(c)) for _, c, _, _ in per_pe))
    first = next((row for row in per_pe if row[0].size), None)
    W = first[0].shape[-1] if first is not None else 2
    K = first[2].shape[-1] if first is not None else 1
    G = first[3].shape[-1] if first is not None else 1
    key_data = np.zeros((P, C, W), np.uint32)
    count = np.zeros((P, C), np.int64)
    cell = np.zeros((P, C, K), np.int64)
    geom = np.ones((P, C, G), np.float64)  # 1s: harmless in both transforms
    for pe, (kd, cnt, cl, gm) in enumerate(per_pe):
        k = len(cnt)
        if k:
            key_data[pe, :k] = kd
            count[pe, :k] = cnt
            cell[pe, :k] = cl
            geom[pe, :k] = gm
    cap = capacity if capacity is not None else max(8, int(count.max()) + 8)
    return PointPlan(kind, key_data, count, cell, geom, scale, dim, cap, rng_impl)


def _point_cell_fn(plan_kind: str, capacity: int, dim: int, scale: float, rng_impl: str):
    def one_cell(kd, cnt, cell, geom):
        key = jax.random.wrap_key_data(kd, impl=rng_impl)
        if plan_kind == POINTS_CUBE:
            u = counter_uniform(key, capacity, dim)
            pts = (cell.astype(jnp.float64) + u) / scale
        else:  # POINTS_POLAR
            u = counter_uniform(key, capacity, 2)
            clo, chi, width = geom[0], geom[1], geom[2]
            r = jnp.arccosh(clo + u[:, 0] * (chi - clo)) / scale
            theta = (cell[1].astype(jnp.float64) + u[:, 1]) * width
            pts = jnp.stack([r, theta], axis=-1)
        return pts, jnp.arange(capacity) < cnt

    return one_cell


def point_executor(plan: PointPlan, mesh: Mesh):
    """(jitted fn, sharded inputs); fn -> (points [P,C,cap,dim], mask).
    Facade over :func:`repro.distrib.runtime.executor`."""
    from . import runtime

    return runtime.executor(plan, mesh)


def run_points(plan: PointPlan, mesh: Optional[Mesh] = None, check: bool = True):
    """Execute a PointPlan; returns (points [P,C,cap,dim], mask, hlo_text).
    Facade over :func:`repro.distrib.runtime.run`."""
    from . import runtime

    pts, mask, hlo = runtime.run(plan, mesh, check=check, want_hlo=True)
    return np.asarray(pts), np.asarray(mask), hlo


def stream_points(plan: PointPlan, check: bool = False, batch: int = 1,
                  with_pe: bool = False, mesh: Optional[Mesh] = None,
                  prefetch: int = 2):
    """Yield point buffers per populated cell, in wave order — the
    PointPlan streaming path (:func:`run_points` materializes
    [P, C, cap, dim]; this emits O(batch · capacity) buffers, so vertex
    positions of huge geometric instances stream like edges do).

    ``batch = 1`` yields (points [cap, dim], mask [cap]) per cell;
    ``batch > 1`` yields up to ``batch`` same-PE cells per dispatch as
    (points [b, cap, dim], mask [b, cap]).  Cell order within each PE
    matches :func:`run_points` exactly, so grouping by PE and
    concatenating the masked rows reproduces its output.  ``with_pe``
    prepends the owning PE; ``check`` asserts zero collectives on the
    lowered wave step (once per program signature).
    """
    from . import runtime

    for pe, slots, payload, mask in runtime.stream_slots(
            plan, mesh=mesh, batch=batch, prefetch=prefetch, check=check):
        yield (int(pe), payload, mask) if with_pe else (payload, mask)


# --------------------------------------------------------------------------
# pair plans: the unified geometric edge table (RHG / RGG / RDG)
# --------------------------------------------------------------------------

# geometry kinds understood by the SPMD pair step
GEOM_EMPTY, GEOM_HYP, GEOM_TORUS, GEOM_CERT = 0, 1, 2, 3

# key impls whose draws are a pure function of (key, slot) — invariant
# under vmap batching.  'rbg' (RngBitGenerator) draws *different* values
# for the same key in different vmap rows, so a cell recomputed in two
# candidate-pair rows would disagree with itself: the recomputation
# invariant every pair plan rests on only holds for counter-based impls.
COUNTER_RNGS = frozenset({"threefry2x32"})


def require_counter_rng(rng_impl: str) -> None:
    """Reject non-counter key impls for pair plans (see COUNTER_RNGS)."""
    if rng_impl not in COUNTER_RNGS:
        raise ValueError(
            f"pair plans require a counter-based per-element PRNG, got "
            f"{rng_impl!r}: geometric edge plans recompute cell points from "
            f"hashed keys across candidate-pair rows, and non-counter impls "
            f"('rbg') draw different values for the same key in different "
            f"vmap rows, breaking the recomputation invariant; use rng_impl "
            f"of {sorted(COUNTER_RNGS)} for RGG/RHG/RDG")


def pair_slot_index(i: int, j: int, cap: int):
    """Lexicographic index of slot pair (i, j), i < j, among the
    C(cap, 2) ordered pairs of a row — the bit position GEOM_CERT rows
    use for their per-edge emit masks.  Works on ints and jnp arrays."""
    return i * (cap - 1) - i * (i - 1) // 2 + (j - i - 1)


@dataclass(frozen=True)
class PairSpec:
    """One candidate-pair row as a host geometric emitter produces it.

    ``kind`` selects the device-side geometry test; the two *sides* are
    kind-specific (widths are emitter-derived, see :func:`make_pair_plan`):

    GEOM_HYP (RHG annulus-cell pair) — side = (key_data, count, gid0,
      geom=(cosh(a*lo), cosh(a*hi), cell_index, angular_width));
      fparams = (alpha, cosh R).  The device regenerates each cell's
      points from the hashed key exactly as the polar PointPlan does and
      evaluates the trig-free Eq. 9 threshold on the cross product.

    GEOM_TORUS (RGG cube-cell pair) — side = (key_data, count, gid0,
      geom = integer cell coordinates as floats); fparams =
      (grid_side g, r^2).  Points decode as (cell + u) / g
      (bit-identical to the cube PointPlan) and the squared Euclidean
      threshold runs in float32, matching the pairdist kernel exactly.
      The decode imposes no [0, 1) bound, so an emitter *could* ship
      shifted (unwrapped) coordinates for periodic pairs; the RGG
      emitter is non-periodic ([0,1)^d with boundary, paper §5) and
      never does.

    GEOM_CERT (RDG certified simplex) — ``gid_a`` = the simplex's d+1
      vertex gids (padded to capacity), ``gid_b`` = the per-edge emit
      bitmask (bit :func:`pair_slot_index`(i, j, capacity) set iff this
      simplex is the designated emitter of edge (i, j) — the host's
      combinatorial dedup/ownership pass, the CERT analog of the chunk
      ``owned`` bit), ``geom_a`` = the (d+1) x d vertex coordinates
      flattened, ``geom_b`` = the region box (lo_0..d, hi_0..d).  The
      device recomputes the circumsphere (Cramer, same formula as
      :func:`repro.core.rdg.circumspheres`) and emits the masked simplex
      edges only when the certificate (circumsphere inside the box)
      holds.

    ``self_pair`` restricts a row to slot pairs i < j (cell-vs-itself,
    and all CERT rows).
    """
    kind: int
    key_a: object
    key_b: object
    count_a: int
    count_b: int
    gid_a: object           # int (gid offset) or int sequence (CERT)
    gid_b: object
    geom_a: Sequence[float]
    geom_b: Sequence[float]
    fparams: Tuple[float, ...] = ()
    self_pair: bool = False


@dataclass(frozen=True)
class PairPlan:
    """Host-emitted candidate-pair table for geometric edge generation.

    Every candidate pair appears exactly once globally (canonical
    enumeration), so the concatenated per-PE outputs are the exact edge
    set — the geometric analog of chunk ownership.  All arrays have
    leading dims [P, C] (PE x pair slot, padded with GEOM_EMPTY rows);
    like :class:`ChunkPlan`, rows are kind-tagged and the device program
    only lowers the geometry branches in :attr:`kinds_present`.

    Trailing widths are emitter-derived: W key words, K gid words, G
    geometry features, F float params per row — a TORUS plan carries
    ``dim`` geometry floats, not a hardcoded 4.
    """
    kind: np.ndarray        # int32  [P, C]  (GEOM_*)
    key_a: np.ndarray       # uint32 [P, C, W]
    key_b: np.ndarray       # uint32 [P, C, W]
    count_a: np.ndarray     # int64  [P, C]
    count_b: np.ndarray     # int64  [P, C]
    gid_a: np.ndarray       # int64  [P, C, K]
    gid_b: np.ndarray       # int64  [P, C, K]
    geom_a: np.ndarray      # float64 [P, C, G]
    geom_b: np.ndarray      # float64 [P, C, G]
    fparams: np.ndarray     # float64 [P, C, F]  (kind-specific reals)
    self_pair: np.ndarray   # bool   [P, C]
    active: np.ndarray      # bool   [P, C]
    capacity: int           # per-cell point capacity (static)
    dim: int = 2            # spatial dimension (static; TORUS/CERT decode)
    rng_impl: str = "threefry2x32"
    reseed_fn: Optional[Callable[[int], "PairPlan"]] = field(
        default=None, compare=False, repr=False)

    @property
    def num_pes(self) -> int:
        return self.active.shape[0]

    @property
    def pairs_per_pe(self) -> int:
        return self.active.shape[1]

    @property
    def total_pairs(self) -> int:
        return int(self.active.sum())

    @property
    def kinds_present(self) -> Tuple[int, ...]:
        """Distinct non-empty geometry kinds — static per plan, so the
        device program only lowers the geometry tests it needs."""
        return tuple(sorted(int(k) for k in np.unique(self.kind) if k != GEOM_EMPTY))  # repro: allow(no-numpy-unique) O(P*C) static plan metadata, not edge dedup

    @property
    def fill_fraction(self) -> float:
        """Active rows / table slots.  C = max per-PE row count, so one
        overloaded PE inflates every PE's table with padding; benchmarks
        report this to surface the waste."""
        return float(self.active.sum()) / max(1, self.active.size)

    # ---- PlanProgram protocol (repro.distrib.runtime) ----

    def input_arrays(self) -> Tuple[np.ndarray, ...]:
        return tuple(getattr(self, name) for name in _PAIR_INPUTS)

    def slot_fn(self):
        return _pair_fn(self.capacity, self.rng_impl, self.kinds_present,
                        self.dim)

    def stream_index(self) -> np.ndarray:
        return active_pair_index(self)

    def signature(self) -> tuple:
        return ("pair", self.active.shape, self.key_a.shape[-1],
                self.gid_a.shape[-1], self.geom_a.shape[-1],
                self.fparams.shape[-1], self.capacity, self.kinds_present,
                self.dim, self.rng_impl)

    def reseed(self, seed: int) -> "PairPlan":
        """Equivalent plan for ``seed`` from the cached pair structure
        (see :meth:`ChunkPlan.reseed`)."""
        if self.reseed_fn is None:
            raise ValueError(
                "plan carries no reseed emitter; re-emit from the GraphSpec")
        from .. import obs
        with obs.trace("plan/reseed", phase="plan", reseed=True,
                       plan=type(self).__name__):
            return self.reseed_fn(int(seed))


_PAIR_INPUTS = ("kind", "key_a", "key_b", "count_a", "count_b", "gid_a",
                "gid_b", "geom_a", "geom_b", "fparams", "self_pair", "active")


def make_pair_plan(
    per_pe: Sequence[Sequence[PairSpec]],
    capacity: Optional[int] = None,
    rng_impl: str = "threefry2x32",
    dim: int = 2,
) -> PairPlan:
    """Pad per-PE pair lists into the rectangular plan tables.

    Trailing table widths (key words W, gid words K, geometry features
    G, float params F) are derived from the widest spec the emitters
    hand in — no kind pays for another kind's layout."""
    require_counter_rng(rng_impl)
    P = len(per_pe)
    C = max(1, max((len(row) for row in per_pe), default=1))
    specs = [sp for row in per_pe for sp in row]
    W = len(_key_data_of(specs[0].key_a)) if specs else 2
    K = max([1] + [len(np.atleast_1d(np.asarray(s))) for sp in specs
                   for s in (sp.gid_a, sp.gid_b)])
    G = max([1] + [len(np.atleast_1d(np.asarray(g, np.float64))) for sp in specs
                   for g in (sp.geom_a, sp.geom_b)])
    F = max([1] + [len(sp.fparams) for sp in specs])
    kind = np.zeros((P, C), np.int32)
    key_a = np.zeros((P, C, W), np.uint32)
    key_b = np.zeros((P, C, W), np.uint32)
    count_a = np.zeros((P, C), np.int64)
    count_b = np.zeros((P, C), np.int64)
    gid_a = np.zeros((P, C, K), np.int64)
    gid_b = np.zeros((P, C, K), np.int64)
    geom_a = np.ones((P, C, G), np.float64)  # 1s: harmless in every decode
    geom_b = np.ones((P, C, G), np.float64)
    fparams = np.zeros((P, C, F), np.float64)
    self_pair = np.zeros((P, C), bool)
    active = np.zeros((P, C), bool)
    for pe, row in enumerate(per_pe):
        for j, sp in enumerate(row):
            kind[pe, j] = sp.kind
            key_a[pe, j] = _key_data_of(sp.key_a)
            key_b[pe, j] = _key_data_of(sp.key_b)
            count_a[pe, j] = sp.count_a
            count_b[pe, j] = sp.count_b
            ga = np.atleast_1d(np.asarray(sp.gid_a, np.int64))
            gb = np.atleast_1d(np.asarray(sp.gid_b, np.int64))
            gid_a[pe, j, : len(ga)] = ga
            gid_b[pe, j, : len(gb)] = gb
            va = np.atleast_1d(np.asarray(sp.geom_a, np.float64))
            vb = np.atleast_1d(np.asarray(sp.geom_b, np.float64))
            geom_a[pe, j, : len(va)] = va
            geom_b[pe, j, : len(vb)] = vb
            if sp.fparams:
                fparams[pe, j, : len(sp.fparams)] = sp.fparams
            self_pair[pe, j] = sp.self_pair
            active[pe, j] = True
    cap = capacity
    if cap is None:
        cmax = max(int(count_a.max()) if count_a.size else 0,
                   int(count_b.max()) if count_b.size else 0)
        cap = round_up_capacity(cmax, mult=8)
    return PairPlan(kind, key_a, key_b, count_a, count_b, gid_a, gid_b,
                    geom_a, geom_b, fparams, self_pair, active, cap, dim, rng_impl)


def pair_plan_from_columns(
    P: int,
    pe: np.ndarray,
    kind: np.ndarray,
    key_a: np.ndarray,
    key_b: np.ndarray,
    count_a: np.ndarray,
    count_b: np.ndarray,
    gid_a: np.ndarray,
    gid_b: np.ndarray,
    geom_a: np.ndarray,
    geom_b: np.ndarray,
    fparams: np.ndarray,
    self_pair: np.ndarray,
    capacity: Optional[int] = None,
    rng_impl: str = "threefry2x32",
    dim: int = 2,
) -> PairPlan:
    """Vectorized :func:`make_pair_plan`: flat per-pair columns in.

    ``pe`` [k] assigns each flat candidate-pair row to its PE; within-PE
    slot order is the rows' order of appearance (stable sort), matching
    the per-PE ``PairSpec`` list the loop-based emitters would build.
    ``gid_a``/``gid_b`` are [k, K], ``geom_a``/``geom_b`` [k, G] (G may
    differ from the table width only by right-padding with the same 1.0
    fill), ``fparams`` [k, F].  Capacity defaults follow
    :func:`make_pair_plan` (round up the max side count, mult=8)."""
    require_counter_rng(rng_impl)
    pe = np.asarray(pe, np.int64)
    k = len(pe)
    per = np.bincount(pe, minlength=P) if k else np.zeros(P, np.int64)
    C = max(1, int(per.max()) if per.size else 0)
    W = key_a.shape[-1] if k else 2
    K = gid_a.shape[-1] if k else 1
    G = geom_a.shape[-1] if k else 1
    F = fparams.shape[-1] if k else 1
    order = np.argsort(pe, kind="stable")
    spe = pe[order]
    starts = np.concatenate(([0], np.cumsum(per)))
    col = np.arange(k, dtype=np.int64) - starts[spe]
    t_kind = np.zeros((P, C), np.int32)
    t_ka = np.zeros((P, C, W), np.uint32)
    t_kb = np.zeros((P, C, W), np.uint32)
    t_ca = np.zeros((P, C), np.int64)
    t_cb = np.zeros((P, C), np.int64)
    t_ga = np.zeros((P, C, K), np.int64)
    t_gb = np.zeros((P, C, K), np.int64)
    t_va = np.ones((P, C, G), np.float64)
    t_vb = np.ones((P, C, G), np.float64)
    t_fp = np.zeros((P, C, F), np.float64)
    t_sp = np.zeros((P, C), bool)
    t_act = np.zeros((P, C), bool)
    if k:
        t_kind[spe, col] = np.asarray(kind, np.int32)[order]
        t_ka[spe, col] = np.asarray(key_a, np.uint32)[order]
        t_kb[spe, col] = np.asarray(key_b, np.uint32)[order]
        t_ca[spe, col] = np.asarray(count_a, np.int64)[order]
        t_cb[spe, col] = np.asarray(count_b, np.int64)[order]
        t_ga[spe, col] = np.asarray(gid_a, np.int64)[order]
        t_gb[spe, col] = np.asarray(gid_b, np.int64)[order]
        t_va[spe, col] = np.asarray(geom_a, np.float64)[order]
        t_vb[spe, col] = np.asarray(geom_b, np.float64)[order]
        t_fp[spe, col] = np.asarray(fparams, np.float64)[order]
        t_sp[spe, col] = np.asarray(self_pair, bool)[order]
        t_act[spe, col] = True
    cap = capacity
    if cap is None:
        cmax = max(int(count_a.max()) if k else 0,
                   int(count_b.max()) if k else 0)
        cap = round_up_capacity(cmax, mult=8)
    return PairPlan(t_kind, t_ka, t_kb, t_ca, t_cb, t_ga, t_gb,
                    t_va, t_vb, t_fp, t_sp, t_act, cap, dim, rng_impl)


def slice_plan(plan, lo: int, hi: int):
    """Restrict a plan to the PE range [lo, hi) — every [P, ...] table
    sliced on its leading axis, other fields untouched.

    The generic segmenter behind lazily-overlapped plan emission
    (:class:`repro.distrib.runtime.PlanEmitter`): segment PEs are
    re-indexed to [0, hi - lo), so the caller owns the offset
    bookkeeping.  The slice drops ``reseed_fn`` (a segment is not a
    reseedable whole plan)."""
    P = plan.num_pes
    if not 0 <= lo < hi <= P:
        raise ValueError(f"bad PE range [{lo}, {hi}) for P={P}")
    upd = {}
    for f in dataclasses.fields(plan):
        v = getattr(plan, f.name)
        if isinstance(v, np.ndarray) and v.ndim >= 1 and v.shape[0] == P:
            upd[f.name] = v[lo:hi]
    upd["reseed_fn"] = None
    return dataclasses.replace(plan, **upd)


def _circumsphere_in_box(geom_a, geom_b, dim: int):
    """GEOM_CERT certificate for one simplex row: circumsphere of the
    (d+1) x d vertex block fully inside the region box.  Delegates to
    the shared Cramer predicate
    (:func:`repro.kernels.delaunay.circumsphere_in_box`) — the same
    arithmetic as :func:`repro.core.rdg.circumspheres` (the host
    planning pass) and as the Bowyer-Watson kernel's in-sphere test, so
    every side of the protocol agrees bit-for-bit; degenerate slivers
    (det == 0) fail the certificate."""
    from ..kernels.delaunay import circumsphere_in_box

    V = geom_a[: (dim + 1) * dim].reshape(dim + 1, dim)
    return circumsphere_in_box(V, geom_b[:dim], geom_b[dim: 2 * dim])


def _pair_fn(capacity: int, rng_impl: str,
             kinds: Sequence[int] = (GEOM_HYP,), dim: int = 2):
    """Per-pair device program, specialized to the geometry kinds in the
    plan (mirror of :func:`_edge_chunk_fn`).

    GEOM_HYP regenerates both polar cells' points from their hashed keys
    (bit-identical to the polar PointPlan stream) and evaluates the
    trig-free Eq. 9 threshold; GEOM_TORUS regenerates cube-cell points
    and runs the float32 r^2 test (bit-identical to the pairdist
    kernel); GEOM_CERT re-certifies a Delaunay simplex's circumsphere
    and emits its host-masked edges.  All emit canonical (max gid,
    min gid) edges; only branches for kinds actually present lower.
    """
    kinds = frozenset(int(k) for k in kinds) - {GEOM_EMPTY}
    N = capacity

    def hyp_features(kd, geom, scale):
        key = jax.random.wrap_key_data(kd, impl=rng_impl)
        u = counter_uniform(key, N, 2)
        clo, chi, ci, w = geom[0], geom[1], geom[2], geom[3]
        r = jnp.arccosh(clo + u[:, 0] * (chi - clo)) / scale
        theta = (ci + u[:, 1]) * w
        r = jnp.maximum(r, 1e-12)
        sh = jnp.sinh(r)
        return jnp.stack(
            [jnp.cos(theta), jnp.sin(theta), jnp.cosh(r) / sh, 1.0 / sh], axis=-1)

    def cube_points(kd, geom, g):
        key = jax.random.wrap_key_data(kd, impl=rng_impl)
        u = counter_uniform(key, N, dim)
        return ((geom[:dim] + u) / g).astype(jnp.float32)

    def one_pair(kind, kd_a, kd_b, cnt_a, cnt_b, gid_a, gid_b,
                 geom_a, geom_b, fp, self_pair, active):
        ii = jnp.arange(N, dtype=jnp.int64)
        I = jnp.broadcast_to(ii[:, None], (N, N))
        J = jnp.broadcast_to(ii[None, :], (N, N))
        valid = (ii[:, None] < cnt_a) & (ii[None, :] < cnt_b)
        once = jnp.where(self_pair, ii[:, None] < ii[None, :], True)
        ga = gid_a[0] + I
        gb = gid_b[0] + J
        hit = jnp.zeros((N, N), bool)

        if GEOM_HYP in kinds:
            fa = hyp_features(kd_a, geom_a, fp[0])
            fb = hyp_features(kd_b, geom_b, fp[0])
            acc = fa[:, 0][:, None] * fb[:, 0][None, :]
            acc += fa[:, 1][:, None] * fb[:, 1][None, :]
            acc -= fa[:, 2][:, None] * fb[:, 2][None, :]
            acc += fp[1] * (fa[:, 3][:, None] * fb[:, 3][None, :])
            hit = jnp.where(kind == GEOM_HYP, acc > 0, hit)

        if GEOM_TORUS in kinds:
            pa = cube_points(kd_a, geom_a, fp[0])
            pb = cube_points(kd_b, geom_b, fp[0])
            acc = jnp.zeros((N, N), jnp.float32)
            for d in range(dim):  # static tiny loop, same order as the kernel
                diff = pa[:, d][:, None] - pb[:, d][None, :]
                acc = acc + diff * diff
            hit = jnp.where(kind == GEOM_TORUS, acc <= fp[1].astype(jnp.float32), hit)

        if GEOM_CERT in kinds:
            cert = _circumsphere_in_box(geom_a, geom_b, dim)
            bit = (gid_b[0] >> jnp.clip(pair_slot_index(I, J, N), 0, 62)) & 1
            hit = jnp.where(kind == GEOM_CERT, (bit == 1) & cert, hit)
            kmax = gid_a.shape[0] - 1
            ga = jnp.where(kind == GEOM_CERT, gid_a[jnp.clip(I, 0, kmax)], ga)
            gb = jnp.where(kind == GEOM_CERT, gid_a[jnp.clip(J, 0, kmax)], gb)

        keep = hit & valid & once & active
        u = jnp.maximum(ga, gb)
        v = jnp.minimum(ga, gb)
        return jnp.stack([u, v], axis=-1).reshape(-1, 2), keep.reshape(-1)

    return one_pair


def pair_executor(plan: PairPlan, mesh: Mesh):
    """(jitted fn, sharded inputs); fn -> (edges [P,C,cap^2,2], keep).
    Facade over :func:`repro.distrib.runtime.executor`."""
    from . import runtime

    return runtime.executor(plan, mesh)


def run_pairs(plan: PairPlan, mesh: Optional[Mesh] = None, check: bool = True):
    """Execute a PairPlan; returns (edges [k, 2] int64, hlo_text).

    Works identically for every geometry kind (GEOM_HYP / GEOM_TORUS /
    GEOM_CERT): the output is the exact global edge set, since every
    candidate pair (or certified simplex edge) appears exactly once.
    Facade over :func:`repro.distrib.runtime.run`."""
    from . import runtime

    edges, keep, hlo = runtime.run(plan, mesh, check=check, want_hlo=True)
    return np.asarray(edges)[np.asarray(keep)], hlo


def active_pair_index(plan: PairPlan) -> np.ndarray:
    """int64 [K, 2] of (pe, slot) for every active candidate pair, in
    stream order — the PairPlan analog of :func:`owned_chunk_index`
    (every pair is globally unique by construction, so active == owned)."""
    return np.argwhere(plan.active).astype(np.int64)


def stream_pair_edges(plan: PairPlan, check: bool = False, batch: int = 1,
                      with_pe: bool = False, mesh: Optional[Mesh] = None,
                      prefetch: int = 2):
    """Yield edge buffers per active candidate pair, in wave order
    (streaming analog of stream_chunk_edges; pair validity is a
    scattered mask, not a prefix).  Facade over
    :func:`repro.distrib.runtime.stream_slots`.

    ``batch = 1`` yields (buffer [cap^2, 2], keep [cap^2]) per pair.
    ``batch > 1`` executes up to ``batch`` *same-PE* consecutive pairs
    per wave row and yields (buffer [b, cap^2, 2], keep [b, cap^2]) —
    large geometric plans have 10^4..10^6 candidate pairs, so per-pair
    dispatch overhead would dominate; batches never straddle a PE
    boundary, so per-PE attribution (and per-PE stream order) is
    preserved.  Peak memory is O(devices * batch * cap^2) either way,
    never O(total edges).  ``check`` asserts zero collectives on the
    lowered wave step itself (the shard_map'd dispatch, once per
    program signature).  ``with_pe`` prepends each buffer's owning PE
    (authoritative — consumers must not re-derive the batch grouping).
    """
    from . import runtime

    for pe, slots, payload, keep in runtime.stream_slots(
            plan, mesh=mesh, batch=batch, prefetch=prefetch, check=check):
        yield (int(pe), payload, keep) if with_pe else (payload, keep)
