"""Mixed-request slab scheduler: many plans, one device program.

The runtime's wave streamer executes one plan's next slots per
dispatch.  Serving wants the transpose: at any moment there are many
in-flight requests — different families, seeds and sizes — each a few
slots from done, and dispatching them one plan at a time would leave
the mesh mostly idle.  The scheduler packs *ready slots from different
requests* into shared ``[D, B]`` slabs (D mesh rows x B batch) and
executes them through :func:`repro.distrib.runtime.run_slab`.

This is sound because a slot is a pure function of its row — the
paper's communication-free invariant, one level down: chunk/pair rows
carry everything their device program reads, so rows from different
plans can sit in one slab without observing each other.  Two
plan-level facts make the packing *bit-exact*:

* **Capacity independence** — every per-slot draw is counter-indexed
  (:mod:`repro.core.prng`), so a chunk row executed at any capacity
  >= its own count yields the identical valid prefix, and a pair row's
  valid (i, j) hits are the same set in the same lexicographic order
  at any capacity >= its cell counts.  Slabs therefore run at a
  power-of-two *capacity class* and plans bucket into it.  The one
  exception is GEOM_CERT, whose per-edge emit bitmask is indexed by
  ``pair_slot_index(i, j, capacity)`` — those rows pack only with
  exact-capacity peers.

* **Kind dispatch is per row** — the engine's ``KIND_*`` / ``GEOM_*``
  branches select via ``jnp.where(kind == ...)`` per slot, so a slab
  may mix G(n,m), SBM and BA chunk rows (or RGG and RHG pair rows) and
  each row still takes exactly its plan's decode path.

Fault tolerance rides on the same purity: slab rows are placed by a
deterministic :class:`repro.distrib.fault.ChunkAssignment`; when mesh
rows "die" mid-slab the lost slots are retired and reissued onto the
surviving rows given by :func:`~repro.distrib.fault.reassign_after_failure`
— recomputation, never state transfer, and the delivered stream is
bit-identical because sinks reassemble by per-request sequence number.
"""
from __future__ import annotations

import queue as _queue
from collections import deque
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from .. import obs
from ..distrib import engine, fault, runtime

__all__ = ["SlabProgram", "Scheduler", "program_of"]


def _capacity_class(cap: int, floor: int) -> int:
    """Smallest power-of-two >= cap (>= floor): the shared slab capacity."""
    c = floor
    while c < cap:
        c <<= 1
    return c


@dataclass(frozen=True)
class SlabProgram:
    """The static device program one packing group shares.

    Any plan whose rows this program can execute bit-identically maps
    to the same ``SlabProgram`` (see :func:`program_of`), and every
    slab of the group reuses one compiled executable keyed by
    :meth:`signature`.
    """
    plan_kind: str            # "chunk" | "pair"
    capacity: int             # shared slab capacity (class bound, or exact)
    W: int                    # PRNG key words
    rng_impl: str
    kinds: Tuple[int, ...]    # KIND_* / GEOM_* branches the program lowers
    dim: int = 2              # pair: spatial dimension (static decode)
    log_n: int = 0            # chunk: RMAT descent depth (0 = no RMAT branch)
    rmat_thr: Tuple[int, ...] = ()  # chunk: RMAT descent thresholds
    K: int = 1                # pair: gid words
    G: int = 1                # pair: geometry features
    F: int = 1                # pair: float params

    def signature(self) -> tuple:
        return ("serve", self.plan_kind, self.capacity, self.W, self.rng_impl,
                self.kinds, self.dim, self.log_n, self.rmat_thr, self.K,
                self.G, self.F)

    def slot_fn(self):
        if self.plan_kind == "chunk":
            return engine._edge_chunk_fn(self.capacity, self.rng_impl,
                                         self.kinds, self.log_n, self.rmat_thr)
        return engine._pair_fn(self.capacity, self.rng_impl, self.kinds,
                               self.dim)

    def slab_arrays(self, D: int, B: int) -> List[np.ndarray]:
        """Fresh row tables for one ``[D, B]`` slab, padding-initialized
        exactly like the plan emitters pad their tables (geom = 1s)."""
        if self.plan_kind == "chunk":
            return [np.zeros((D, B), np.int32),            # kind (EMPTY)
                    np.zeros((D, B, self.W), np.uint32),   # key_data
                    np.zeros((D, B), np.int64),            # universe
                    np.zeros((D, B), np.int64),            # count
                    np.zeros((D, B, 3), np.int64),         # params
                    np.zeros((D, B, 4), np.float64),       # fparams
                    np.zeros((D, B), bool)]                # owned
        return [np.zeros((D, B), np.int32),                # kind (EMPTY)
                np.zeros((D, B, self.W), np.uint32),       # key_a
                np.zeros((D, B, self.W), np.uint32),       # key_b
                np.zeros((D, B), np.int64),                # count_a
                np.zeros((D, B), np.int64),                # count_b
                np.zeros((D, B, self.K), np.int64),        # gid_a
                np.zeros((D, B, self.K), np.int64),        # gid_b
                np.ones((D, B, self.G), np.float64),       # geom_a
                np.ones((D, B, self.G), np.float64),       # geom_b
                np.zeros((D, B, self.F), np.float64),      # fparams
                np.zeros((D, B), bool),                    # self_pair
                np.zeros((D, B), bool)]                    # active

    def gather_rows(self, plan) -> List[np.ndarray]:
        """Plan rows in stream order, padded to this program's widths:
        ``[S, ...]`` per input table (S = number of streamed slots)."""
        index = np.asarray(plan.stream_index(), np.int64).reshape(-1, 2)
        i, j = index[:, 0], index[:, 1]
        vals = [np.asarray(a[i, j]) for a in plan.input_arrays()]
        if self.plan_kind == "pair":
            for p, fill in ((5, 0), (6, 0), (7, 1.0), (8, 1.0), (9, 0.0)):
                width = (self.K, self.K, self.G, self.G, self.F)[p - 5]
                v = vals[p]
                if v.shape[-1] > width:
                    raise ValueError(
                        f"plan width {v.shape[-1]} exceeds program width "
                        f"{width} for input {p}")
                if v.shape[-1] < width:
                    out = np.full(v.shape[:-1] + (width,), fill, v.dtype)
                    out[..., : v.shape[-1]] = v
                    vals[p] = out
        return vals


def program_of(plan) -> SlabProgram:
    """The packing group a plan's slots execute under.

    Chunk plans of one capacity class share a program lowering all
    sampled kinds + BA (RMAT plans additionally key on their static
    descent depth and thresholds), so G(n,m)/G(n,p)/SBM/BA rows pack
    together.  Pair plans without CERT rows share the HYP+TORUS program
    per (capacity class, dim), so RGG and RHG rows pack together; CERT
    plans key on their exact capacity (the emit bitmask is
    capacity-indexed).
    """
    if isinstance(plan, engine.ChunkPlan):
        log_n = plan.rmat_log_n
        kinds = sorted(set(engine.SAMPLED_KINDS) | {engine.KIND_BA}
                       | ({engine.KIND_RMAT} if log_n else set()))
        return SlabProgram("chunk", _capacity_class(plan.capacity, 64),
                           plan.key_data.shape[-1], plan.rng_impl,
                           tuple(kinds), log_n=log_n,
                           rmat_thr=plan.rmat_thresholds)
    if isinstance(plan, engine.PairPlan):
        W = plan.key_a.shape[-1]
        if engine.GEOM_CERT in plan.kinds_present:
            return SlabProgram("pair", plan.capacity, W, plan.rng_impl,
                               plan.kinds_present, dim=plan.dim,
                               K=plan.gid_a.shape[-1],
                               G=plan.geom_a.shape[-1],
                               F=plan.fparams.shape[-1])
        return SlabProgram("pair", _capacity_class(plan.capacity, 8), W,
                           plan.rng_impl,
                           (engine.GEOM_HYP, engine.GEOM_TORUS),
                           dim=plan.dim, K=1, G=max(4, plan.dim), F=2)
    raise TypeError(f"no slab program for plan type {type(plan).__name__}")


class _Group:
    """One packing group: a program plus its FIFO of pending slots."""
    __slots__ = ("program", "queue")

    def __init__(self, program: SlabProgram):
        self.program = program
        self.queue: deque = deque()   # (sink, seq, row-tuple)


class _Admission:
    """One in-flight lazily segmented request: the background planner's
    segment feed plus the request's running sequence base."""
    __slots__ = ("feed", "sink", "base")

    def __init__(self, feed: _queue.Queue, sink):
        self.feed = feed
        self.sink = sink
        self.base = 0


class Scheduler:
    """Packs pending slots from all in-flight requests into slabs.

    ``enqueue`` appends a plan's slots (in its stream order) to the
    FIFO of their packing group; each ``tick`` drains up to ``D * B``
    slots from one group into a slab and demuxes the results to the
    per-request sinks.  Requests admitted between ticks join partially
    drained queues, so their slots ride in the very next slab alongside
    older requests' remainders — continuous batching.
    """

    def __init__(self, mesh, slab_batch: int = 8, check: bool = True,
                 registry: Optional[obs.Registry] = None):
        self.mesh = mesh
        self.D = runtime.mesh_size(mesh)
        self.B = int(slab_batch)
        self.check = check
        self._groups: Dict[tuple, _Group] = {}
        self._admissions: List[_Admission] = []
        self._rr = 0
        self._fault: Optional[Tuple[int, Tuple[int, ...]]] = None
        self.slabs = 0
        self.slots = 0
        self.reissued = 0
        self.registry = registry if registry is not None \
            else obs.Registry("repro_serve_")
        r = self.registry
        self._m_slabs = r.counter("slabs_total", "slabs executed")
        self._m_slots = r.counter("slots_total", "slots executed")
        self._m_reissued = r.counter(
            "reissued_total", "slots recomputed after mesh-row faults")
        self._m_fill = r.histogram(
            "slab_fill_fraction", "occupied fraction of each [D, B] slab",
            buckets=(0.1, 0.25, 0.5, 0.75, 0.9, 1.0))
        r.gauge("queue_depth", "pending slots across packing groups",
                fn=lambda: float(self.pending))
        r.gauge("packing_groups", "live packing groups",
                fn=lambda: float(len(self._groups)))

    def enqueue(self, plan, sink) -> Optional[int]:
        """Admit one request's plan; returns its slot count.

        Accepts a :class:`repro.distrib.runtime.PlanEmitter` too: plan
        segments are then emitted on a background planner thread and
        admitted incrementally as they arrive — early segments' slots
        ride slabs while later PE ranges are still being planned, so a
        request's first results land before its plan is fully emitted.
        The sink's global sequence numbering (segment base + in-segment
        stream order) equals the full plan's stream order, so delivery
        stays bit-identical; returns ``None`` (the total slot count is
        unknown until the last segment lands)."""
        if isinstance(plan, runtime.PlanEmitter):
            self._admissions.append(
                _Admission(runtime._plan_feed(plan, 1), sink))
            self._admit_ready()
            return None
        S = self._admit(plan, sink, 0)
        sink.expect(S)
        return S

    def _admit(self, plan, sink, base: int) -> int:
        """Append one plan's slots (stream order, seqs from ``base``)
        to their packing group's FIFO; returns the slot count."""
        prog = program_of(plan)
        key = prog.signature()
        group = self._groups.get(key)
        if group is None:
            group = self._groups[key] = _Group(prog)
        vals = group.program.gather_rows(plan)
        S = len(vals[0])
        for seq in range(S):
            group.queue.append((sink, base + seq, tuple(v[seq] for v in vals)))
        return S

    def _admit_ready(self, block: bool = False) -> None:
        """Drain finished plan segments from background planners into
        packing groups.  All scheduler mutation happens here, on the
        consumer thread — planner threads only build tables.  With
        ``block=True`` (drain, nothing else runnable) wait for one
        segment if no planner has produced anything yet."""
        progressed = False
        for adm in list(self._admissions):
            while adm in self._admissions:
                try:
                    item = adm.feed.get_nowait()
                except _queue.Empty:
                    break
                progressed = True
                self._apply_segment(adm, item)
        if block and not progressed and self._admissions:
            adm = self._admissions[0]
            self._apply_segment(adm, adm.feed.get())

    def _apply_segment(self, adm: _Admission, item) -> None:
        if item is None:          # planner exhausted: total now known
            self._admissions.remove(adm)
            adm.sink.expect(adm.base)
            return
        if isinstance(item, BaseException):
            self._admissions.remove(adm)
            raise item
        _i, _lo, _hi, seg = item
        adm.base += self._admit(seg, adm.sink, adm.base)

    @property
    def pending(self) -> int:
        return sum(len(g.queue) for g in self._groups.values())

    @property
    def emitting(self) -> bool:
        """True while any admitted request's background planner is
        still emitting segments (more slots will arrive)."""
        return bool(self._admissions)

    def wait_segment(self) -> None:
        """Block until at least one pending segment has been admitted
        (no-op when nothing is emitting): the idle-but-emitting path of
        :meth:`drain` and the service loop."""
        self._admit_ready(block=True)

    def inject_fault(self, dead_rows, at_slab: Optional[int] = None) -> None:
        """Arm a one-shot failure: the given mesh rows die during slab
        ``at_slab`` (default: the next one).  Their results are
        discarded and the lost slots reissued onto survivors."""
        when = self.slabs if at_slab is None else int(at_slab)
        self._fault = (when, tuple(int(d) for d in dead_rows))

    def tick(self) -> bool:
        """Execute one slab from the next non-empty group (round-robin
        across groups so no family starves).  False when idle."""
        self._admit_ready()
        groups = [g for g in self._groups.values() if g.queue]
        if not groups:
            return False
        group = groups[self._rr % len(groups)]
        self._rr += 1
        take = min(len(group.queue), self.D * self.B)
        entries = [group.queue.popleft() for _ in range(take)]
        assignment = fault.ChunkAssignment(take, tuple(range(self.D)))
        placement = self._place(range(take), assignment.worker_of)
        self._execute(group, entries, placement, assignment)
        return True

    def _place(self, ids, worker_of) -> Dict[int, Tuple[int, int]]:
        """Deterministic slot -> (mesh row, column) placement; callers
        hand in the assignment's worker map (or the survivor remap)."""
        cols: Dict[int, int] = {}
        out: Dict[int, Tuple[int, int]] = {}
        for k in ids:
            d = worker_of(k)
            b = cols.get(d, 0)
            if b < self.B:
                out[k] = (d, b)
                cols[d] = b + 1
        return out

    def _assemble(self, prog: SlabProgram, entries, placement):
        """Fill one ``[D, B]`` slab's valid mask + row tables."""
        valid = np.zeros((self.D, self.B), bool)
        rows = prog.slab_arrays(self.D, self.B)
        for k, (d, b) in placement.items():
            valid[d, b] = True
            for arr, val in zip(rows, entries[k][2]):
                arr[d, b] = val
        return valid, rows

    def peek_slab(self):
        """Assemble (but neither dequeue nor execute) the next slab:
        ``(program, valid, rows)``.  The :mod:`repro.analyze.programs`
        registration hook — what it lowers is exactly what
        :meth:`tick` would run."""
        groups = [g for g in self._groups.values() if g.queue]
        if not groups:
            raise RuntimeError("no pending slots to assemble")
        group = groups[self._rr % len(groups)]
        take = min(len(group.queue), self.D * self.B)
        entries = [group.queue[k] for k in range(take)]
        assignment = fault.ChunkAssignment(take, tuple(range(self.D)))
        placement = self._place(range(take), assignment.worker_of)
        valid, rows = self._assemble(group.program, entries, placement)
        return group.program, valid, rows

    def _execute(self, group: _Group, entries, placement, assignment) -> None:
        prog = group.program
        valid, rows = self._assemble(prog, entries, placement)
        payload, ok = runtime.run_slab(prog.slot_fn, prog.signature(), valid,
                                       rows, self.mesh, check=self.check)
        payload, ok = np.asarray(payload), np.asarray(ok)
        self.slabs += 1
        self.slots += len(placement)
        self._m_slabs.inc()
        self._m_slots.inc(len(placement))
        self._m_fill.observe(len(placement) / float(self.D * self.B))
        self.registry.counter(
            "group_slabs_total", "slabs per packing group",
            labels={"group": prog.plan_kind}).inc()

        dead: set = set()
        if self._fault is not None and self.slabs > self._fault[0]:
            dead = set(self._fault[1])
            self._fault = None

        lost = []
        with obs.trace("serve/deliver", phase="sink", slab=self.slabs):
            for k, (d, b) in placement.items():
                sink, seq, _ = entries[k]
                if d in dead:
                    lost.append(k)
                else:
                    sink.deliver(seq, payload[d, b], ok[d, b])

        if lost:
            # retire-and-reissue: the deterministic survivor map decides
            # where every lost slot recomputes (zero state transfer).
            remap = fault.reassign_after_failure(assignment, sorted(dead))
            self.reissued += len(lost)
            self._m_reissued.inc(len(lost))
            obs.event("fault_reissue", lost=len(lost), dead=sorted(dead))
            remaining = lost
            while remaining:
                placed = self._place(remaining, remap.worker_of)
                self._execute(group, entries, placed, remap)
                remaining = [k for k in remaining if k not in placed]

    def drain(self) -> None:
        while True:
            if self.tick():
                continue
            if not self.emitting:
                return
            # idle but a background planner is still emitting: wait for
            # its next segment instead of spinning
            self.wait_segment()
