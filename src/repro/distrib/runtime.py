"""repro.distrib.runtime — one mesh-aware, wave-streamed executor for
every plan type.

:mod:`repro.distrib.engine` used to carry three copy-paste executor/
run/stream triples (``edge_executor``/``run_edges``/``stream_chunk_edges``
for :class:`~repro.distrib.engine.ChunkPlan`, ``point_executor``/
``run_points`` for :class:`~repro.distrib.engine.PointPlan`,
``pair_executor``/``run_pairs``/``stream_pair_edges`` for
:class:`~repro.distrib.engine.PairPlan`).  Every one of them was the
same program with a different table: shard the ``[P, C, ...]`` plan
arrays over a mesh, ``vmap`` a kind-specialized per-slot function over
the table, assert the lowering is collective-free, and hand the results
back.  This module is that program written once.

A plan participates by implementing the :class:`PlanProgram` protocol —
three methods plus a static signature:

========================  ====================================================
``input_arrays()``        the plan's ``[P, C, ...]`` table arrays, in the
                          order its slot fn consumes them
``slot_fn()``             the kind-specialized per-slot device function:
                          ``(*slot_rows) -> (payload, valid_mask)``
``stream_index()``        ``[K, 2]`` of ``(pe, slot)`` for every slot that
                          contributes output, in pe-major stream order (the
                          ownership mask as an index: each global chunk /
                          candidate pair / cell appears exactly once)
``signature()``           hashable static program identity (shapes, kinds,
                          capacity, rng impl) — the compile-cache key
========================  ====================================================

On top of the protocol the runtime owns

* **run** (:func:`run`): the materializing path — one jitted
  ``shard_map`` step over the full table, compile-cached per
  ``(signature, mesh)``, with the zero-collective HLO assertion run at
  most once per cache entry (and never skipped for a caller that asked).

* **wave streaming** (:func:`stream_waves`): the scaling path.  The
  plan's owned slots are dealt to the mesh rows that already hold their
  table shards (contiguous PE ranges — the same slicing
  :func:`~repro.distrib.engine.deal_plan` uses for virtual plans), and
  each dispatch executes one ``[D, batch]`` slab of *next* slots for
  every mesh row simultaneously under ``shard_map`` — streaming uses
  the whole mesh, not the default device.  Batches never straddle a PE
  boundary, so every slab row belongs to exactly one virtual PE and
  per-PE stream order is preserved exactly: grouping the streamed rows
  by PE and concatenating reproduces :func:`run`'s output
  bit-for-bit.  Ragged final waves are padded with masked rows (same
  static shapes — one compile per program, never a retrace), and
  ``prefetch`` waves are kept in flight so wave ``k+1`` is
  dispatched before the host consumes wave ``k``.

* **plan/execute overlap** (:class:`PlanEmitter`): the cold-start path.
  Plan emission is communication-free too, so a plan can be emitted
  one PE-range segment at a time on a background planner thread while
  the runtime executes the previous segment's waves — mirroring the
  wave prefetch double-buffering one level up.  The first chunk waits
  for one segment's plan instead of the whole table's, and later
  segments are planned while earlier ones execute; per-PE stream order
  is preserved exactly.

* **meshes**: every entry point takes an explicit ``mesh=`` and accepts
  a multi-process ``jax.make_mesh``.  Table and slab inputs are built
  per process from the host plan (``jax.make_array_from_callback`` when
  the sharding is not fully addressable), and wave outputs are consumed
  shard-wise: each process sees only its addressable mesh rows
  (``Wave.rows`` is ``None`` elsewhere).  The zero-collective invariant
  is asserted on the lowered wave step itself, so the claim covers the
  exact program the mesh executes.
"""
from __future__ import annotations

import queue as _queue
import threading
from collections import deque
from dataclasses import dataclass
from typing import Callable, Dict, Iterator, Optional, Protocol, Tuple, runtime_checkable

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# the zero-collective check IS analyze's Pass-1 scanner (one
# implementation for the runtime assertion and the static CI gate)
from ..analyze.hloscan import assert_communication_free
from .engine import default_mesh
# host-side tracing only: spans wrap dispatch/consume boundaries on the
# host — nothing below ever closes over obs inside a jitted program
from .. import obs


# --------------------------------------------------------------------------
# the protocol
# --------------------------------------------------------------------------

@runtime_checkable
class PlanProgram(Protocol):
    """What a plan type exposes to execute on the runtime.

    Implemented by :class:`~repro.distrib.engine.ChunkPlan`,
    :class:`~repro.distrib.engine.PointPlan` and
    :class:`~repro.distrib.engine.PairPlan`; any future plan type that
    implements it gets run, wave streaming, caching and the
    zero-collective assertion for free."""

    @property
    def num_pes(self) -> int: ...

    def input_arrays(self) -> Tuple[np.ndarray, ...]: ...

    def slot_fn(self) -> Callable: ...

    def stream_index(self) -> np.ndarray: ...

    def signature(self) -> tuple: ...


def mesh_size(mesh: Mesh) -> int:
    return int(mesh.devices.size)


@functools.lru_cache(maxsize=None)
def mesh_for(P: int) -> Mesh:
    """The cached default 1-D mesh for P virtual PEs (largest device
    count that divides P, so the [P, ...] tables shard evenly)."""
    return default_mesh(P)


def _resolve_mesh(plan: PlanProgram, mesh: Optional[Mesh]) -> Mesh:
    mesh = mesh if mesh is not None else mesh_for(plan.num_pes)
    D = mesh_size(mesh)
    if plan.num_pes % D:
        raise ValueError(
            f"mesh of {D} devices cannot shard a {plan.num_pes}-PE plan: "
            f"the [P, C] tables split over the mesh rows, so P % devices "
            f"must be 0 (re-deal the plan or pass a smaller mesh)")
    return mesh


def _sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec(mesh.axis_names))


def _put(x, ns: NamedSharding):
    """Host array -> device array under ``ns``; per-process shard
    construction when the mesh spans processes (each process supplies
    only its addressable slice of the host table)."""
    if ns.is_fully_addressable:
        return jax.device_put(jnp.asarray(x), ns)
    arr = np.asarray(x)
    return jax.make_array_from_callback(arr.shape, ns, lambda idx: arr[idx])


def _consumable(arr):
    """Make a wave output consumable by this process.  Fully
    addressable (single-process) arrays are handed back as-is — they
    stay on device, so device-side consumers (the stats wedge replay)
    never pay a host round-trip and the host only blocks when it
    actually materializes a buffer.  A multi-process array is read
    through its addressable shards only (non-addressable rows are left
    zero — their ``Wave.rows`` entries are ``None``)."""
    if getattr(arr, "is_fully_addressable", True):
        return arr
    out = np.zeros(arr.shape, arr.dtype)
    for sh in arr.addressable_shards:
        out[sh.index] = np.asarray(sh.data)
    return out


def _named(step: Callable, name: str) -> Callable:
    """Give a step function its program's name: ``jax.jit`` calls the
    program ``jit_<name>``, which is what the device trace's ``XLA
    Modules`` line shows for each execution."""
    step.__name__ = step.__qualname__ = name
    return step


def _local_rows(mesh: Mesh) -> np.ndarray:
    """bool [D]: which mesh rows this process can address."""
    pi = jax.process_index()
    return np.array([d.process_index == pi for d in mesh.devices.ravel()])


# --------------------------------------------------------------------------
# compile cache (one entry per static program signature x mesh x mode)
# --------------------------------------------------------------------------

class _Entry:
    __slots__ = ("fn", "sharding", "checked")

    def __init__(self, fn, sharding):
        self.fn = fn
        self.sharding = sharding
        self.checked = False


_CACHE: Dict[tuple, _Entry] = {}


def cache_clear() -> None:
    _CACHE.clear()
    mesh_for.cache_clear()


# --------------------------------------------------------------------------
# run: the materializing full-table path
# --------------------------------------------------------------------------

def executor(plan: PlanProgram, mesh: Mesh):
    """(jitted fn, sharded inputs) for the plan's full-table SPMD step.

    ``fn(*inputs) -> (payload [P, C, ...], valid [P, C, L])``; ``valid``
    already folds in per-slot validity and ownership masks, so boolean
    extraction of ``payload`` by ``valid`` is the exact global output.
    This is the one executor behind the legacy ``edge_executor`` /
    ``point_executor`` / ``pair_executor`` facades."""
    spec = PartitionSpec(mesh.axis_names)
    one = plan.slot_fn()
    arrays = plan.input_arrays()

    def step(*tables):
        return jax.vmap(jax.vmap(one))(*tables)

    fn = jax.jit(jax.shard_map(
        _named(step, "run"), mesh=mesh, in_specs=(spec,) * len(arrays),
        out_specs=(spec, spec), check_vma=False))
    ns = _sharding(mesh)
    inputs = tuple(_put(a, ns) for a in arrays)
    return fn, inputs


def run(plan: PlanProgram, mesh: Optional[Mesh] = None, check: bool = True,
        want_hlo: bool = False):
    """Execute a plan's full table; returns ``(payload, valid, hlo)``.

    The compiled step is cached per ``(signature, mesh)``, so repeated
    runs of structurally identical plans never retrace; the
    zero-collective assertion runs at most once per cache entry
    (identical program => identical HLO) but is never skipped for a
    caller that asked for it.  ``hlo`` is the lowered text when
    ``want_hlo`` (or on the entry's first checked call), else None."""
    mesh = _resolve_mesh(plan, mesh)
    key = ("run", plan.signature(), mesh)
    ent = _CACHE.get(key)
    obs.event("compile_cache", kind="run", hit=ent is not None)
    if ent is None:
        fn, inputs = executor(plan, mesh)
        ent = _CACHE[key] = _Entry(fn, inputs[0].sharding)
    else:
        inputs = tuple(_put(a, ent.sharding) for a in plan.input_arrays())
    hlo = None
    if (check and not ent.checked) or want_hlo:
        lowered = ent.fn.lower(*inputs)
        hlo = lowered.as_text()
        if check:
            assert_communication_free(lowered)
            ent.checked = True
    with obs.trace("run/exec", phase="exec", mode="run"):
        payload, valid = ent.fn(*inputs)
    return payload, valid, hlo


def lower_run(plan: PlanProgram, mesh: Optional[Mesh] = None):
    """The ``jax.stages.Lowered`` of a plan's full-table run step.

    What :func:`run`'s ``check=True`` path asserts on and what
    :mod:`repro.analyze.programs` (Pass 1) scans — the same lowering,
    so the static gate verifies the exact program :func:`run`
    executes."""
    mesh = _resolve_mesh(plan, mesh)
    fn, inputs = executor(plan, mesh)
    return fn.lower(*inputs)


# --------------------------------------------------------------------------
# lazily segmented plans: plan/execute overlap
# --------------------------------------------------------------------------
#
# Cold-start latency of the streaming path is the whole plan's time plus
# the first wave's: the full [P, C] table is emitted before the first
# wave dispatches.  But plan
# emission is communication-free too — any PE range's rows are a pure
# function of (spec, P) — so the table can be emitted *per PE range*,
# and the range covering the first mesh pass can start executing while
# later ranges are still being planned.  PlanEmitter is that contract:
# ``build(lo, hi)`` emits the plan rows of global PEs [lo, hi) as a
# standalone PlanProgram (num_pes == hi - lo), and stream_waves runs a
# background planner thread feeding segments through a bounded queue —
# the same double-buffering shape as the wave prefetch deque, one level
# up.  The first chunk then waits for one segment's plan, not the whole
# table's; ``plan/overlap`` spans (builder thread) against ``wave/*``
# spans (consumer thread) make the pipelining visible in repro.obs
# traces, and the device trace shows the waves' execution beside them.

#: default number of plan segments when the emitter does not pin one
DEFAULT_SEGMENTS = 4


class PlanEmitter:
    """A plan emitted lazily, one PE-range segment at a time.

    ``build(lo, hi)`` must return a :class:`PlanProgram` holding exactly
    the rows of global PEs ``[lo, hi)`` re-indexed to ``[0, hi - lo)``
    — for table plans, field-by-field equal to
    :func:`repro.distrib.engine.slice_plan` of the full emission (the
    segment's *capacity* may be segment-local: per-slot draws are
    capacity-independent, so outputs are unchanged).  Family emitters
    whose per-PE rows are cheap to restrict implement ``build`` natively
    (cost ∝ ``(hi - lo) / P``); :meth:`from_plan` wraps an
    already-built plan for callers that only want the ordering contract.

    Segment boundaries are chosen at stream time: each segment's width
    is a multiple of the mesh row count D, so every segment shards over
    the same mesh.  Segments arrive in ascending-PE order and each
    preserves per-PE stream order, so the concatenated overlapped
    stream regroups to the exact per-PE order of the unsegmented plan.
    """

    def __init__(self, num_pes: int, build: Callable[[int, int], PlanProgram],
                 segments: int = 0):
        self.num_pes = int(num_pes)
        self.build = build
        self.segments = int(segments)

    @classmethod
    def from_plan(cls, plan: PlanProgram, segments: int = 0) -> "PlanEmitter":
        """Segment an already-built table plan via ``slice_plan`` (the
        ordering/overlap contract without lazy emission — useful for
        tests and for feeding the serve scheduler incrementally)."""
        from .engine import slice_plan

        return cls(plan.num_pes, lambda lo, hi: slice_plan(plan, lo, hi),
                   segments)

    def segment_bounds(self, D: int) -> Tuple[Tuple[int, int], ...]:
        """The (lo, hi) PE ranges streamed over a D-row mesh: ~equal
        widths, every width a multiple of D, ascending order."""
        if self.num_pes % D:
            raise ValueError(
                f"mesh of {D} devices cannot shard a {self.num_pes}-PE "
                f"emitter: P % devices must be 0")
        nb = self.num_pes // D
        k = max(1, min(self.segments or DEFAULT_SEGMENTS, nb))
        cuts = [nb * s // k * D for s in range(k + 1)]
        return tuple((cuts[s], cuts[s + 1]) for s in range(k)
                     if cuts[s + 1] > cuts[s])


def _plan_feed(emitter: PlanEmitter, D: int, depth: int = 2) -> _queue.Queue:
    """Start the background planner: builds segments in PE order into a
    bounded queue (planning runs at most ``depth`` segments ahead of
    execution).  Items are ``(index, lo, hi, plan)``, then ``None`` at
    exhaustion; a builder exception is forwarded and re-raised by the
    consumer."""
    q: _queue.Queue = _queue.Queue(maxsize=max(1, int(depth)))
    bounds = emitter.segment_bounds(D)

    def planner() -> None:
        try:
            for i, (lo, hi) in enumerate(bounds):
                with obs.trace("plan/overlap", phase="plan", segment=i,
                               segments=len(bounds), lo=lo, hi=hi):
                    seg = emitter.build(lo, hi)
                q.put((i, lo, hi, seg))
            q.put(None)
        except BaseException as e:  # forwarded to the consumer thread
            q.put(e)

    threading.Thread(target=planner, name="repro-plan-emitter",
                     daemon=True).start()
    return q


def _stream_emitter_waves(emitter: PlanEmitter, mesh: Optional[Mesh],
                          batch: int, prefetch: int,
                          check: bool) -> Iterator["Wave"]:
    """stream_waves over a lazily segmented plan: execute segment k's
    waves while the planner thread emits segment k+1."""
    mesh = mesh if mesh is not None else mesh_for(emitter.num_pes)
    D = mesh_size(mesh)
    feed = _plan_feed(emitter, D)
    while True:
        # un-phased span: stall waiting on the planner (nonzero only
        # when planning, not execution, is the bottleneck)
        with obs.trace("plan/overlap/wait"):
            item = feed.get()
        if item is None:
            return
        if isinstance(item, BaseException):
            raise item
        _, lo, _, seg = item
        for wave in stream_waves(seg, mesh=mesh, batch=batch,
                                 prefetch=prefetch, check=check):
            if lo:
                wave = Wave(payload=wave.payload, valid=wave.valid,
                            rows=tuple(None if r is None else (r[0] + lo, r[1])
                                       for r in wave.rows))
            yield wave


# --------------------------------------------------------------------------
# wave streaming: [D, batch] slabs of next slots for the whole mesh
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class WaveSchedule:
    """Host-side dealing of a plan's stream index onto mesh rows.

    ``sched[w, d, b] = (local_pe, slot)`` addresses row ``b`` of wave
    ``w`` on mesh row ``d`` *within that row's table shard* (virtual
    PEs are dealt to mesh rows in contiguous ranges — exactly how the
    ``[P, ...]`` tables shard, so the device-side gather is local by
    construction).  ``valid`` masks ragged padding rows; ``rows[w][d]``
    is ``(pe, slots)`` or ``None`` for an all-padding row.  Batches
    never straddle a PE boundary, so each slab row has one owning PE
    and per-PE stream order equals the plan's stream index order."""
    sched: np.ndarray       # int32 [W, D, B, 2] (local pe, slot)
    valid: np.ndarray       # bool  [W, D, B]
    rows: tuple             # [W][D] -> (pe, slots np.ndarray) | None
    batch: int              # B, clamped to the longest per-PE run

    @property
    def num_waves(self) -> int:
        return self.sched.shape[0]


def wave_schedule(plan: PlanProgram, D: int, batch: int = 1) -> WaveSchedule:
    index = np.asarray(plan.stream_index())
    P = plan.num_pes
    ppd = P // D
    starts = np.searchsorted(index[:, 0], np.arange(P + 1))
    per_pe = [index[starts[pe]: starts[pe + 1], 1] for pe in range(P)]
    B = max(1, min(int(batch), max((len(s) for s in per_pe), default=1)))
    dealt: list = [[] for _ in range(D)]
    for pe, slots in enumerate(per_pe):
        for s in range(0, len(slots), B):
            dealt[pe // ppd].append((pe, slots[s: s + B]))
    W = max((len(b) for b in dealt), default=0)
    sched = np.zeros((W, D, B, 2), np.int32)
    valid = np.zeros((W, D, B), bool)
    rows = [[None] * D for _ in range(W)]
    for d, batches in enumerate(dealt):
        for w, (pe, slots) in enumerate(batches):
            k = len(slots)
            sched[w, d, :k, 0] = pe - d * ppd
            sched[w, d, :k, 1] = slots
            valid[w, d, :k] = True
            rows[w][d] = (pe, np.asarray(slots))
    return WaveSchedule(sched, valid, tuple(tuple(r) for r in rows), B)


def _wave_fn(plan: PlanProgram, mesh: Mesh, n_tables: int, squeeze: bool):
    """The jitted shard_map'd wave step: gather each mesh row's next
    ``[B]`` slots from its local table shard, run the slot fn, and mask
    padding rows out of the validity output.  Each device's output
    block is its mesh row itself (``[B, ...]``, or ``[...]`` with the
    unit batch axis dropped when ``squeeze``: an unbatched stream), so
    the global outputs are ``[D*B, ...]`` sharded on their leading axis
    and a row is one device's buffer.  Named after the plan's kind (``wave_chunk``,
    ``wave_pair``, ``wave_point``)."""
    spec = PartitionSpec(mesh.axis_names)
    one = plan.slot_fn()

    def step(sched, valid, *tables):
        # blocks: sched [1, B, 2], valid [1, B], tables [P/D, C, ...]
        s, v = sched[0], valid[0]
        rows = [t[s[:, 0], s[:, 1]] for t in tables]      # local gather [B, ...]
        payload, ok = jax.vmap(one)(*rows)
        ok = ok & v[:, None]
        if squeeze:                                       # B == 1: a bitcast
            return payload[0], ok[0]
        return payload, ok

    kind = type(plan).__name__.lower().removesuffix("plan")
    return jax.jit(jax.shard_map(
        _named(step, f"wave_{kind}"), mesh=mesh,
        in_specs=(spec,) * (2 + n_tables), out_specs=(spec, spec),
        check_vma=False))


def _row_blocks(arr, D: int):
    """``(blocks, view)``: mesh row ``d``'s block of a wave output at
    ``blocks[d]``, and whether the blocks are the wave program's own
    device buffers.  On a single-process mesh each row is a device's
    addressable shard (zero-copy, nothing dispatched); a multi-process
    output is already on the host, so its rows are NumPy views of it."""
    n = arr.shape[0] // D
    if isinstance(arr, np.ndarray):
        return [arr[d * n: (d + 1) * n] for d in range(D)], False
    blocks = [None] * D
    for sh in arr.addressable_shards:
        blocks[(sh.index[0].start or 0) // n] = sh.data
    return blocks, True


@dataclass(frozen=True)
class Wave:
    """One executed ``[D, batch]`` slab: every mesh row's next slots.

    ``payload`` / ``valid`` are the wave program's outputs, ``[D*B,
    ...]`` sharded on the leading axis (``[D*cap, ...]`` for an
    unbatched stream), padding already masked: each device holds
    its mesh row's block.  ``rows[d]`` names the owning virtual PE and
    its slot ids (``None`` for an all-padding or non-addressable row).
    On a single-process mesh the outputs stay on the device — the host
    only blocks when a consumer materializes one.  Iterating
    :meth:`chunks` yields the per-PE view in pe order within the
    wave."""
    payload: object         # [D*B, ...] device array (host if multi-process)
    valid: object           # [D*B, L]
    rows: tuple             # [D] -> (pe, slots) | None

    def chunks(self) -> Iterator[Tuple[int, np.ndarray, object, object]]:
        """Yield ``(pe, slots, payload [B, ...], valid [B, L])`` per
        non-empty mesh row (``[cap, ...]`` / ``[cap]`` when unbatched).
        Each row is the wave program's own buffer on that row's device,
        handed out without an eager op.  Rows keep the full static
        batch shape — ragged tails beyond ``len(slots)`` are masked,
        never trimmed, so jitted downstream consumers see one shape per
        program and never retrace."""
        D = len(self.rows)
        payload = valid = None
        for d, row in enumerate(self.rows):
            if row is None:
                continue
            with obs.trace("wave/rows", phase="sink"):
                if payload is None:
                    payload, view = _row_blocks(self.payload, D)
                    valid, _ = _row_blocks(self.valid, D)
                obs.event("wave/row", view=view)
            yield row[0], row[1], payload[d], valid[d]


def lower_wave(plan: PlanProgram, mesh: Optional[Mesh] = None,
               batch: int = 1):
    """The ``jax.stages.Lowered`` of a plan's shard_map'd wave step.

    The streaming analog of :func:`lower_run`: Pass 1 of
    :mod:`repro.analyze` scans this module for every registered plan,
    so the zero-collective / no-host-callback / deterministic-PRNG
    contracts are verified on the program :func:`stream_waves` actually
    dispatches at that ``batch``, not a per-slot proxy.  Returns
    ``None`` for a plan with no owned slots (nothing would ever
    execute)."""
    mesh = _resolve_mesh(plan, mesh)
    D = mesh_size(mesh)
    ws = wave_schedule(plan, D, batch)
    if not ws.num_waves:
        return None
    arrays = plan.input_arrays()
    fn = _wave_fn(plan, mesh, len(arrays), batch <= 1)
    ns = _sharding(mesh)
    tables = tuple(_put(a, ns) for a in arrays)
    return fn.lower(_put(ws.sched[0], ns), _put(ws.valid[0], ns), *tables)


def stream_waves(
    plan,
    mesh: Optional[Mesh] = None,
    batch: int = 1,
    prefetch: int = 2,
    check: bool = False,
) -> Iterator[Wave]:
    """Stream a plan (or a lazily segmented one) as :class:`Wave` slabs.

    Each dispatch executes the next ``batch`` slots of *every* mesh row
    simultaneously; ``prefetch`` waves are kept in flight (wave ``k+1``
    dispatches before the host consumes wave ``k`` — JAX's async
    dispatch does the overlapping, the deque here just bounds it), so
    peak memory is O(prefetch · D · batch · capacity), never O(total
    output).  ``check=True`` asserts the zero-collective invariant on
    the lowered wave step itself — the shard_map'd program that actually
    runs, not a single slot's fn — once per program signature.

    Each mesh row's output is the wave program's own per-device buffer
    (:class:`Wave`), so handing a row to the consumer runs no eager op.
    An unbatched stream (``batch <= 1``) drops the unit batch axis
    inside the program, so its rows come out as ``[cap, ...]``, not
    ``[1, cap, ...]``.  On a mesh of more than one device each row stays
    on its own device.

    Per-PE stream order is exact: concatenating a PE's rows across
    waves reproduces its :func:`run` output prefix bit-for-bit, and on
    a single-row mesh the flattened wave order *is* pe-major run order.

    Passing a :class:`PlanEmitter` streams through the plan/execute
    overlap path: segments are built on a background thread (bounded
    queue, ``plan/overlap`` spans) while earlier segments' waves
    execute, and yielded ``Wave.rows`` carry *global* PE ids — the
    regrouped stream is identical to streaming the full plan.
    """
    if isinstance(plan, PlanEmitter):
        yield from _stream_emitter_waves(plan, mesh, batch, prefetch, check)
        return
    mesh = _resolve_mesh(plan, mesh)
    D = mesh_size(mesh)
    with obs.trace("wave/schedule", phase="exec", D=D, batch=batch):
        ws = wave_schedule(plan, D, batch)
    if not ws.num_waves:
        return
    with obs.trace("wave/setup", phase="exec"):
        arrays = plan.input_arrays()
        squeeze = batch <= 1
        key = ("wave", plan.signature(), mesh, ws.batch, squeeze)
        ent = _CACHE.get(key)
        obs.event("compile_cache", kind="wave", hit=ent is not None)
        if ent is None:
            fn = _wave_fn(plan, mesh, len(arrays), squeeze)
            ent = _CACHE[key] = _Entry(fn, _sharding(mesh))
        ns = ent.sharding
        tables = tuple(_put(a, ns) for a in arrays)
        if check and not ent.checked:
            assert_communication_free(ent.fn.lower(
                _put(ws.sched[0], ns), _put(ws.valid[0], ns), *tables))
            ent.checked = True
    local = _local_rows(mesh)

    def emit(rows, out) -> Wave:
        payload, valid = out
        with obs.trace("wave/sink", phase="sink"):
            kept = tuple(r if local[d] else None for d, r in enumerate(rows))
            return Wave(payload=_consumable(payload),
                        valid=_consumable(valid), rows=kept)

    pending: deque = deque()
    for w in range(ws.num_waves):
        with obs.trace("wave/dispatch", phase="exec", wave=w):
            out = ent.fn(_put(ws.sched[w], ns), _put(ws.valid[w], ns), *tables)
        pending.append((ws.rows[w], out))
        if len(pending) >= max(1, int(prefetch)):
            yield emit(*pending.popleft())
    while pending:
        yield emit(*pending.popleft())


# --------------------------------------------------------------------------
# slab execution: packed [D, B] rows from *different* plans (repro.serve)
# --------------------------------------------------------------------------
#
# Wave streaming above executes one plan's next slots.  The serving
# scheduler (repro.serve.scheduler) goes one step further: it packs
# ready slots from *many concurrent requests* — different plans, same
# static program — into one [D, B] slab.  The device step is the same
# shard_map'd vmap as _wave_fn minus the table gather: the host already
# assembled each row's inputs (a gather across plans is not expressible
# as a local table index), so the step consumes the row arrays directly.
# Compiles are cached per (signature, row shapes, mesh) — every slab of
# a packing group reuses one executable — and the zero-collective
# contract is asserted on the lowered slab step itself, once per entry.

def _slab_fn(slot_fn, mesh: Mesh, n_rows: int):
    spec = PartitionSpec(mesh.axis_names)

    def step(valid, *rows):
        # blocks: valid [1, B], rows [1, B, ...] — no cross-row indexing
        payload, ok = jax.vmap(slot_fn)(*(r[0] for r in rows))
        return payload[None], (ok & valid[0][:, None])[None]

    return jax.jit(jax.shard_map(
        _named(step, "slab"), mesh=mesh, in_specs=(spec,) * (1 + n_rows),
        out_specs=(spec, spec), check_vma=False))


def _slab_key(signature: tuple, valid: np.ndarray, rows, mesh: Mesh) -> tuple:
    return ("slab", signature, valid.shape,
            tuple((r.shape[1:], np.asarray(r).dtype.str) for r in rows), mesh)


def run_slab(slot_fn_thunk: Callable, signature: tuple, valid: np.ndarray,
             rows, mesh: Mesh, check: bool = True):
    """Execute one packed ``[D, B]`` slab; returns ``(payload, valid)``.

    ``rows`` are the per-slot input arrays (``[D, B, ...]``, one per
    table the slot fn consumes) assembled by the scheduler from any mix
    of source plans sharing the static program named by ``signature``;
    ``valid`` masks padding rows.  ``slot_fn_thunk`` is only called on
    a compile-cache miss, so steady-state dispatch never rebuilds the
    slot fn.  ``check=True`` asserts the zero-collective contract on
    the lowered slab step once per cache entry — the packed
    mixed-request program itself, not a proxy."""
    valid = np.asarray(valid, bool)
    key = _slab_key(signature, valid, rows, mesh)
    ent = _CACHE.get(key)
    obs.event("compile_cache", kind="slab", hit=ent is not None)
    if ent is None:
        fn = _slab_fn(slot_fn_thunk(), mesh, len(rows))
        ent = _CACHE[key] = _Entry(fn, _sharding(mesh))
    ns = ent.sharding
    inputs = (_put(valid, ns),) + tuple(_put(r, ns) for r in rows)
    if check and not ent.checked:
        assert_communication_free(ent.fn.lower(*inputs))
        ent.checked = True
        inputs = (_put(valid, ns),) + tuple(_put(r, ns) for r in rows)
    with obs.trace("slab/exec", phase="exec", mode="slab"):
        payload, ok = ent.fn(*inputs)
    return _consumable(payload), _consumable(ok)


def lower_slab(slot_fn: Callable, valid: np.ndarray, rows,
               mesh: Optional[Mesh] = None):
    """The ``jax.stages.Lowered`` of a packed slab step — what
    :func:`run_slab`'s ``check`` asserts on and what
    :mod:`repro.analyze.programs` scans for the serve family."""
    mesh = mesh if mesh is not None else mesh_for(np.asarray(valid).shape[0])
    fn = _slab_fn(slot_fn, mesh, len(rows))
    ns = _sharding(mesh)
    inputs = (_put(np.asarray(valid, bool), ns),) + tuple(
        _put(r, ns) for r in rows)
    return fn.lower(*inputs)


def stream_slots(
    plan,
    mesh: Optional[Mesh] = None,
    batch: int = 1,
    prefetch: int = 2,
    check: bool = False,
) -> Iterator[Tuple[int, np.ndarray, np.ndarray, np.ndarray]]:
    """Flattened :func:`stream_waves`: yield ``(pe, slots, payload,
    valid)`` per mesh-row batch, in wave order (pe-major on a
    single-row mesh).  The per-(pe, slot) consumer loop the front door
    and the legacy ``stream_*`` facades are built on.  Accepts a
    :class:`PlanEmitter` for the overlapped path (``pe`` is then the
    global PE id); rows are shaped as in :func:`stream_waves`."""
    for wave in stream_waves(plan, mesh=mesh, batch=batch,
                             prefetch=prefetch, check=check):
        yield from wave.chunks()
