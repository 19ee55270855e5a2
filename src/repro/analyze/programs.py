"""The registered-program inventory Pass 1 walks.

Every device program the generator stack can execute is enumerated
here: all eight spec families' plans (ChunkPlan for the sampled
families, PairPlan + PointPlan for the geometric ones), each lowered
through *both* runtime paths — the materializing full-table ``run``
step and the shard_map'd **wave** step that streaming dispatches — on a
representative mesh, plus the declared-float32 kernel entry points.
The specs are deliberately tiny (n ≈ 64): contract violations are
properties of the lowered *structure* (a collective lowers at n = 64
exactly as it does at n = 2^30), so the gate stays cheap enough to run
on every push.

Each case carries a :class:`~repro.analyze.hloscan.Contract`:

* chunk programs — the baseline generator contract (no collectives /
  host callbacks / dynamic shapes; ``rng_bit_generator`` allowed — the
  'rbg' perf path never recomputes a slot twice),
* pair & point programs — additionally no ``rng_bit_generator``
  (recomputed cells must draw identically in every vmap row), and
* float32 kernels — additionally no f64 promotion.

:func:`scan_programs` attaches static FLOP / HBM-byte estimates from
:class:`repro.launch.hlocost.HloCost` to every program signature, so
the same report that proves the contracts also seeds the roofline
model (``repro.tune``'s cost tables start here).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from .hloscan import (Contract, FLOAT32_KERNEL_CONTRACT, GENERATOR_CONTRACT,
                      RECOMPUTE_CONTRACT, ScanReport, scan_text)

FAMILIES = ("gnm", "gnp", "ba", "rmat", "sbm", "rgg", "rhg", "rdg", "serve")

# modes a plan lowers through: the materializing run step and the
# shard_map'd wave step (what streaming actually executes)
MODES = ("run", "wave")

DEFAULT_P = 4
DEFAULT_BATCH = 4


def small_specs() -> Dict[str, object]:
    """One tiny spec per family — structure-representative lowerings."""
    from ..api import BA, GNM, GNP, RDG, RGG, RHG, RMAT, SBM

    n = 64
    return {
        "gnm": GNM(n=n, m=2 * n, seed=7, chunks=8),
        "gnp": GNP(n=n, p=0.05, seed=7, chunks=8),
        "ba": BA(n=n, d=2, seed=7),
        "rmat": RMAT(n=n, m=2 * n, seed=7, chunks=8),
        "sbm": SBM(n=n, blocks=2, p_in=0.2, p_out=0.02, seed=7),
        "rgg": RGG(n=n, radius=0.25, seed=7, chunks=8),
        "rhg": RHG(n=n, avg_deg=4.0, gamma=2.7, seed=7),
        "rdg": RDG(n=32, seed=7, chunks=8),
    }


@dataclass(frozen=True)
class ProgramCase:
    """One lowerable program: a plan (or kernel) on a mesh, with its
    contract.  ``lower()`` returns the ``jax.stages.Lowered``."""
    name: str               # e.g. "rgg/pair/wave"
    family: str
    plan_kind: str          # chunk | point | pair | kernel
    mode: str               # run | wave | call
    contract: Contract
    lower: Callable[[], object]
    signature: tuple = ()


def _plan_cases(family: str, spec, P: int, batch: int,
                mesh=None) -> Iterator[ProgramCase]:
    from ..distrib import engine, runtime

    plans: List[Tuple[str, object]] = []
    plan = spec.plan(P)
    kind = {engine.ChunkPlan: "chunk", engine.PairPlan: "pair",
            engine.PointPlan: "point"}[type(plan)]
    plans.append((kind, plan))
    point_plan = getattr(spec, "point_plan", None)
    if point_plan is not None:
        plans.append(("point", point_plan(P)))

    for kind, p in plans:
        contract = GENERATOR_CONTRACT if kind == "chunk" else RECOMPUTE_CONTRACT
        for mode in MODES:
            if mode == "run":
                low = (lambda p=p: runtime.lower_run(p, mesh))
            else:
                low = (lambda p=p: runtime.lower_wave(p, mesh, batch=batch))
            yield ProgramCase(
                name=f"{family}/{kind}/{mode}", family=family, plan_kind=kind,
                mode=mode, contract=contract, lower=low,
                signature=p.signature())


def _serve_cases(P: int, mesh=None) -> Iterator[ProgramCase]:
    """The serving tier's packed mixed-request slab programs.

    The scheduler packs ready slots from *different* requests into one
    [D, B] slab; the programs lowered here are exactly what
    ``runtime.run_slab`` executes (and ``check``-asserts) in
    production: a chunk slab mixing G(n,m) and BA rows under the
    GENERATOR contract, and a pair slab mixing RGG (GEOM_TORUS) and
    RHG (GEOM_HYP) rows under the RECOMPUTE contract (packed cells are
    recomputed across rows, so nondeterministic RNG is a violation).
    """
    from ..api import BA, GNM, RGG, RHG
    from ..distrib import runtime
    from ..serve.scheduler import Scheduler
    from ..serve.sinks import Sink

    n = 64
    mixes = {
        "chunk": (GENERATOR_CONTRACT,
                  [GNM(n=n, m=2 * n, seed=7, chunks=8),
                   BA(n=n, d=2, seed=9)]),
        "pair": (RECOMPUTE_CONTRACT,
                 [RGG(n=n, radius=0.25, seed=7, chunks=8),
                  RHG(n=n, avg_deg=4.0, gamma=2.7, seed=9)]),
    }
    use_mesh = mesh if mesh is not None else runtime.mesh_for(P)
    for kind, (contract, specs) in mixes.items():
        sch = Scheduler(use_mesh, slab_batch=4, check=False)
        for spec in specs:
            sch.enqueue(spec.plan(P), Sink())
        prog, valid, rows = sch.peek_slab()

        def low(prog=prog, valid=valid, rows=rows, m=use_mesh):
            return runtime.lower_slab(prog.slot_fn(), valid, rows, m)

        yield ProgramCase(
            name=f"serve/{kind}/slab", family="serve", plan_kind=kind,
            mode="slab", contract=contract, lower=low,
            signature=prog.signature())


def _kernel_cases() -> Iterator[ProgramCase]:
    """The kernel entry points.

    The pairmask tiles are declared float32 (f64 promotion is a
    violation: the TORUS r² test is pinned so engine and kernel agree
    bit-for-bit).  The batched Delaunay triangulator is the opposite —
    f64 *by design* (its Cramer circumsphere predicate must match the
    engine's GEOM_CERT re-check bit-for-bit), so it carries the
    RECOMPUTE contract: no collectives, host callbacks, dynamic shapes,
    or rng_bit_generator (the kernel draws nothing; points arrive
    pre-generated)."""
    import jax
    import jax.numpy as jnp

    from ..kernels.delaunay import (cavity_capacity, group_size,
                                    simplex_capacity)
    from ..kernels.delaunay.ref import delaunay_ref
    from ..kernels.pairmask.ops import pair_mask

    def lower_euclid():
        a = jax.ShapeDtypeStruct((128, 8), jnp.float32)
        s = jax.ShapeDtypeStruct((), jnp.float32)
        return pair_mask.lower(a, a, s, tile="euclid", dim=2)

    yield ProgramCase(
        name="kernels/pairmask/euclid", family="kernels", plan_kind="kernel",
        mode="call", contract=FLOAT32_KERNEL_CONTRACT, lower=lower_euclid,
        signature=("pairmask", "euclid", 128, 8))

    for dim, n in ((2, 64), (3, 64)):
        def lower_dt(dim=dim, n=n):
            pts = jax.ShapeDtypeStruct((4, n, dim), jnp.float64)
            cnt = jax.ShapeDtypeStruct((4,), jnp.int32)
            return delaunay_ref.lower(
                pts, cnt, dim=dim, num_simplices=simplex_capacity(n, dim),
                cavity=cavity_capacity(dim), group=group_size(dim))

        yield ProgramCase(
            name=f"kernels/delaunay/ref{dim}d", family="kernels",
            plan_kind="kernel", mode="call", contract=RECOMPUTE_CONTRACT,
            lower=lower_dt, signature=("delaunay", "ref", dim, n))


def iter_programs(
    families: Optional[Sequence[str]] = None,
    P: int = DEFAULT_P,
    batch: int = DEFAULT_BATCH,
    mesh=None,
    kernels: bool = True,
) -> Iterator[ProgramCase]:
    """Yield every registered program case (filtered by ``families``)."""
    want = list(families) if families else list(FAMILIES)
    unknown = [f for f in want if f not in FAMILIES + ("kernels",)]
    if unknown:
        raise ValueError(f"unknown families {unknown}; know {FAMILIES}")
    specs = small_specs()
    for family in want:
        if family == "kernels":
            continue
        if family == "serve":
            yield from _serve_cases(P, mesh)
            continue
        yield from _plan_cases(family, specs[family], P, batch, mesh)
    if kernels and (families is None or "kernels" in want):
        yield from _kernel_cases()


@dataclass
class ProgramReport:
    """Pass-1 verdict + static cost estimate for one program."""
    name: str
    plan_kind: str
    mode: str
    signature: tuple
    scan: ScanReport
    flops: Optional[int] = None
    bytes: Optional[int] = None
    error: Optional[str] = None

    @property
    def ok(self) -> bool:
        return self.error is None and self.scan.ok

    def to_json(self) -> dict:
        out = {
            "name": self.name,
            "plan_kind": self.plan_kind,
            "mode": self.mode,
            "signature": [str(s) for s in self.signature],
            "flops": self.flops,
            "bytes": self.bytes,
            "ok": self.ok,
        }
        out.update(self.scan.to_json())
        if self.error:
            out["error"] = self.error
        return out


def scan_case(case: ProgramCase, with_cost: bool = True) -> ProgramReport:
    """Lower one case, scan its module, optionally attach HLO costs."""
    try:
        lowered = case.lower()
        if lowered is None:  # empty plan: no program will ever execute
            return ProgramReport(case.name, case.plan_kind, case.mode,
                                 case.signature, ScanReport())
        scan = scan_text(lowered.as_text(), case.contract)
    except Exception as e:  # lowering itself failing is a finding, not a crash
        return ProgramReport(case.name, case.plan_kind, case.mode,
                             case.signature, ScanReport(), error=f"{e!r}")
    flops = nbytes = None
    if with_cost:
        try:
            from ..launch.hlocost import HloCost

            cost = HloCost.from_lowered(lowered)
            flops, nbytes = cost.flops, cost.bytes
        except Exception as e:
            return ProgramReport(case.name, case.plan_kind, case.mode,
                                 case.signature, scan, error=f"cost: {e!r}")
    return ProgramReport(case.name, case.plan_kind, case.mode,
                         case.signature, scan, flops=flops, bytes=nbytes)


def scan_programs(
    families: Optional[Sequence[str]] = None,
    P: int = DEFAULT_P,
    batch: int = DEFAULT_BATCH,
    mesh=None,
    with_cost: bool = True,
    kernels: bool = True,
) -> List[ProgramReport]:
    """Pass 1 over the whole registered inventory."""
    return [scan_case(c, with_cost=with_cost)
            for c in iter_programs(families, P=P, batch=batch, mesh=mesh,
                                   kernels=kernels)]


def scan_spec(spec, P: int = DEFAULT_P, *, mesh=None, batch: int = DEFAULT_BATCH,
              with_cost: bool = False, name: str = "spec") -> List[ProgramReport]:
    """Pass 1 for one user-supplied spec (the :func:`repro.api.verify_contracts`
    backend): every plan the spec emits, through both runtime paths."""
    return [scan_case(c, with_cost=with_cost)
            for c in _plan_cases(name, spec, P, batch, mesh)]
