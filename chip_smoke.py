#!/usr/bin/env python3
"""Drive the generator's main path once on a TPU and check every result.

    python3 chip_smoke.py             # one chip: stream, validate, RDG, serve
    python3 chip_smoke.py --chips 4   # four chips: sharded stream, fault drill

Everything runs in this one process, through the public entry points
(``repro.api``, ``repro.serve``, ``repro.stats``).  Each phase checks its
own output and prints one line: sizes, edges, seconds and the check it
passed.  The seconds are smoke times of a single run, compilation
included; they are not benchmark metrics.  A failed check raises, and
the script exits non-zero.  On any platform but a TPU it exits non-zero
at once, naming the platform it found: it never falls back to the CPU.

The last line of standard output is one JSON object naming the device,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

import repro  # noqa: E402,F401  (x64 + the compile cache, before any compile)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro import obs  # noqa: E402
from repro.api import (BA, GNM, GNP, RDG, RGG, RHG, generate,  # noqa: E402
                       iter_edge_chunks, make_service)
from repro.stats import validate  # noqa: E402

# the README's massive streaming example (2^26 vertices, 2^30 edges)
STREAM = dict(n=1 << 26, m=1 << 30, P=1024)


def _mesh(devices) -> Mesh:
    return Mesh(np.array(devices), ("pe",))


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


class CompileClock:
    """Sums XLA backend compile seconds (a persistent-cache hit counts
    only its read), through ``jax.monitoring``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1


# ---------------------------------------------------------------- stream

_M1 = np.uint64(0xBF58476D1CE4E5B9)
_M2 = np.uint64(0x94D049BB133111EB)


def _mix64(x):
    """splitmix64's finalizer: a bijection of uint64 with full avalanche."""
    x = (x ^ (x >> np.uint64(30))) * _M1
    x = (x ^ (x >> np.uint64(27))) * _M2
    return x ^ (x >> np.uint64(31))


@jax.jit
def chunk_summary(buf, mask):
    """[valid edges, self-loops, position-keyed 64-bit digest] of one
    streamed chunk, computed where the chunk lives."""
    u = buf[..., 0].astype(jnp.uint64)
    v = buf[..., 1].astype(jnp.uint64)
    pos = jnp.arange(mask.size, dtype=jnp.uint64).reshape(mask.shape)
    h = _mix64(_mix64(u ^ (pos << np.uint64(32))) ^ v)
    digest = jnp.sum(jnp.where(mask, h, np.uint64(0)), dtype=jnp.uint64)
    return jnp.stack([jnp.sum(mask, dtype=jnp.int64),
                      jnp.sum(mask & (u == v), dtype=jnp.int64),
                      jax.lax.bitcast_convert_type(digest, jnp.int64)])


def stream_gnm(n: int, m: int, P: int, mesh: Mesh, keep=()):
    """Stream directed G(n, m) through ``iter_edge_chunks`` on ``mesh``.

    Returns ``(per_pe, kept, seconds)``: ``per_pe[pe]`` is the host
    ``[chunks, 3]`` summary rows of that PE in stream order, ``kept[pe]``
    the host edges of each PE in ``keep``."""
    spec = GNM(n=n, m=m, directed=True, seed=0)
    t0 = time.perf_counter()
    rows, kept = [], {pe: [] for pe in keep}
    for c in iter_edge_chunks(spec, P, mesh=mesh, check=True):
        s = chunk_summary(c.buffer, c.mask)
        rows.append((c.pe, c.count, s))
        if c.pe in kept:
            kept[c.pe].append(c.edges())
    # each chunk's summary stays on its mesh row's device: stack on the host
    summary = np.stack(jax.device_get([s for _, _, s in rows])) if rows else \
        np.zeros((0, 3), np.int64)
    seconds = time.perf_counter() - t0
    counts = np.array([k for _, k, _ in rows], np.int64)
    _check(int(summary[:, 0].sum()) == m,
           f"stream holds {int(summary[:, 0].sum())} edges, want m={m}")
    _check(np.array_equal(summary[:, 0], counts),
           "a chunk's valid edges differ from its planned count")
    _check(not summary[:, 1].any(),
           f"{int(summary[:, 1].sum())} self-loops in the stream")
    per_pe: dict = {}
    for (pe, _, _), row in zip(rows, summary):
        per_pe.setdefault(pe, []).append(row)
    per_pe = {pe: np.stack(r) for pe, r in per_pe.items()}
    kept = {pe: np.concatenate(e) if e else np.zeros((0, 2), np.int64)
            for pe, e in kept.items()}
    return per_pe, kept, seconds


def reference_edges(n: int, m: int, P: int, pe: int, mesh: Mesh):
    """The edges of one PE of the same stream, computed on ``mesh``."""
    from repro.distrib import engine, runtime

    plan = GNM(n=n, m=m, directed=True, seed=0).plan(P)
    part = engine.slice_plan(plan, pe, pe + 1)
    out = [np.asarray(w.payload)[np.asarray(w.valid)]
           for w in runtime.stream_waves(part, mesh=mesh)]
    return np.concatenate(out) if out else np.zeros((0, 2), np.int64)


def spot_pes(P: int, k: int = 3, seed: int = 0):
    rest = np.random.default_rng(seed).choice(np.arange(1, P - 1), k, False)
    return sorted({0, P - 1, *map(int, rest)})


def phase_stream(n: int, m: int, P: int, mesh: Mesh, ref_mesh: Mesh):
    """Stream G(n, m) on ``mesh``: exactly m edges, no self-loops, and the
    first, last and seeded-random PEs' chunks equal to the same chunks
    computed on ``ref_mesh`` (the host CPU) bit for bit."""
    spots = spot_pes(P)
    per_pe, kept, secs = stream_gnm(n, m, P, mesh, keep=spots)
    for pe in spots:
        ref = reference_edges(n, m, P, pe, ref_mesh)
        _check(np.array_equal(kept[pe], ref),
               f"PE {pe}: device chunk differs from the CPU reference")
    chunks = sum(len(r) for r in per_pe.values())
    print(f"stream GNM n=2^{n.bit_length() - 1} m=2^{m.bit_length() - 1} "
          f"P={P}: {m} edges in {chunks} chunks, {secs:.1f}s; sum==m, "
          f"no self-loops, PEs {spots} bit-identical to the CPU backend")
    return secs


# -------------------------------------------------------------- validate

def phase_validate(n: int, P: int):
    """``stats.validate`` gates of ``python -m repro.stats`` at size n."""
    for spec in (GNP(n=n, p=16.0 / n, seed=1),
                 RHG(n=n, avg_deg=8, gamma=2.7, seed=1)):
        t0 = time.perf_counter()
        report = validate(spec, P)
        secs = time.perf_counter() - t0
        _check(report.passed, f"validation gate failed:\n{report}")
        print(f"validate {report.family} n={n} P={P}: "
              f"{report.stats.num_edges} edges, {secs:.1f}s; "
              f"{len(report.checks)} gates PASS "
              f"({', '.join(c.name for c in report.checks)})")


# ------------------------------------------------------------------- RDG

def _rdg_edges(n: int, P: int):
    with obs.capture() as tr:
        g = generate(RDG(n=n, seed=5), P)
    resumed = [r for r in tr.spans() if r.name == "plan/rdg/qhull_resume"]
    _check(not resumed, f"RDG n={n} P={P} resumed chunks on Qhull")
    return g.edges


def phase_rdg(n: int, Ps=(8, 1)):
    """2d RDG: the same edge set at every P, and exactly 3n edges (Euler
    on the torus), with no chunk resumed on the Qhull fallback."""
    t0 = time.perf_counter()
    sets = []
    for P in Ps:
        e = _rdg_edges(n, P)
        _check(len(e) == 3 * n, f"RDG n={n} P={P}: {len(e)} edges, want 3n")
        sets.append(e[np.lexsort((e[:, 1], e[:, 0]))])
    for P, e in zip(Ps[1:], sets[1:]):
        _check(np.array_equal(e, sets[0]),
               f"RDG edge set at P={P} differs from P={Ps[0]}")
    print(f"rdg 2d n={n} P={list(Ps)}: {3 * n} edges each, "
          f"{time.perf_counter() - t0:.1f}s; equal sets, exactly 3n, "
          f"no Qhull resume")


# ----------------------------------------------------------------- serve

SERVE_SHAPES = (
    lambda s: GNM(n=512, m=1024, seed=s, chunks=8),
    lambda s: GNP(n=512, p=0.004, seed=s, chunks=8),
    lambda s: BA(n=256, d=2, seed=s),
    lambda s: RGG(n=256, radius=0.12, seed=s),
)


def phase_serve(requests: int, P: int):
    """Mixed-family requests through ``make_service``, each identical to
    ``generate(spec, P)``."""
    specs = [SERVE_SHAPES[i % 4](1000 + i) for i in range(requests)]
    t0 = time.perf_counter()
    svc = make_service(P, slab_batch=16)
    graphs = svc.serve(specs)
    secs = time.perf_counter() - t0
    for spec, g in zip(specs, graphs):
        _check(np.array_equal(g.edges, generate(spec, P).edges),
               f"served {spec} differs from generate")
    st = svc.stats
    print(f"serve {requests} mixed requests P={P}: "
          f"{sum(g.m for g in graphs)} edges, {secs:.1f}s; {st['slabs']} "
          f"slabs, each request bit-identical to generate")


# ------------------------------------------------------------ four chips

def phase_four_chips(n: int, m: int, P: int, devices):
    """The GNM stream sharded over four chips equals, PE by PE, the same
    stream on one chip; the compiled wave step holds no collective; and a
    served drill that loses one of four mesh rows mid-slab still returns
    ``generate``'s graphs."""
    from repro.analyze.hloscan import assert_communication_free
    from repro.distrib import runtime

    four, one = _mesh(devices[:4]), _mesh(devices[:1])
    _check(four.devices.size == 4, "the mesh does not hold 4 devices")
    wave = runtime.lower_wave(GNM(n=n, m=m, directed=True, seed=0).plan(P),
                              four)
    assert_communication_free(wave.compile())
    spots = spot_pes(P)
    sharded, kept4, secs4 = stream_gnm(n, m, P, four, keep=spots)
    single, kept1, secs1 = stream_gnm(n, m, P, one, keep=spots)
    _check(sharded.keys() == single.keys(), "PE sets differ across meshes")
    for pe in single:
        _check(np.array_equal(sharded[pe], single[pe]),
               f"PE {pe}: 4-chip stream differs from the 1-chip stream")
    for pe in spots:
        _check(np.array_equal(kept4[pe], kept1[pe]),
               f"PE {pe}: 4-chip edges differ from the 1-chip edges")
    print(f"stream GNM n=2^{n.bit_length() - 1} m=2^{m.bit_length() - 1} "
          f"P={P} on 4 chips {secs4:.1f}s vs 1 chip {secs1:.1f}s: per-PE "
          f"regrouped streams identical, compiled wave step has no "
          f"collective")

    specs = [GNM(n=256, m=800, seed=s, chunks=16) for s in range(3)] + \
        [RGG(n=96, radius=0.15, seed=9)]
    svc = make_service(8, mesh=four, slab_batch=4)
    tickets = [svc.submit(s) for s in specs]
    svc.inject_fault([1], at_slab=1)
    t0 = time.perf_counter()
    svc.drain()
    secs = time.perf_counter() - t0
    _check(svc.scheduler.reissued > 0, "the fault drill reissued nothing")
    for spec, t in zip(specs, tickets):
        _check(np.array_equal(t.result().edges, generate(spec, 8).edges),
               f"fault drill: {spec} differs from generate")
    print(f"fault drill P=8 on 4 chips, row 1 lost at slab 1: "
          f"{svc.scheduler.reissued} slots reissued, {secs:.1f}s; "
          f"every request bit-identical to generate")


# ------------------------------------------------------------------ main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip phase")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found platform {dev.platform!r}, not a TPU; "
              f"nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1
    from repro.kernels import interpret_mode
    _check(not interpret_mode(), "Pallas would interpret on this platform")

    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_four_chips(STREAM["n"], STREAM["m"], STREAM["P"], devices)
    else:
        phase_stream(STREAM["n"], STREAM["m"], STREAM["P"],
                     mesh=_mesh(devices[:1]),
                     ref_mesh=_mesh(jax.devices("cpu")[:1]))
        phase_validate(1 << 18, 8)
        phase_rdg(1 << 16)
        phase_serve(64, 8)
    print(f"total {time.perf_counter() - t0:.1f}s; backend compiles: "
          f"{clock.count}, {clock.seconds:.1f}s (compile cache "
          f"{jax.config.jax_compilation_cache_dir})")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
