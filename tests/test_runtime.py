"""repro.distrib.runtime — the one mesh-aware, wave-streamed executor:
stream == run bit-identity and P-invariance for all three plan types,
ragged final waves padded (never retraced), the zero-collective check
on the actual wave dispatch (once per program signature), and
whole-mesh wave execution on 8 devices."""
import contextlib
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.api import GNM, RGG, RHG, generate, iter_edge_chunks, iter_points
from repro.core import rgg
from repro.distrib import engine, runtime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

GNM_SPEC = GNM(n=400, m=3000, seed=11, chunks=10)
RGG_SPEC = RGG(n=300, radius=0.07, seed=11)
RHG_SPEC = RHG(n=300, avg_deg=6, gamma=2.7, seed=4)


def _plan_of(kind: str, P: int):
    if kind == "chunk":
        return GNM_SPEC.plan(P)
    if kind == "pair":
        return RHG_SPEC.plan(P)
    return rgg.rgg_point_plan(RGG_SPEC.seed, RGG_SPEC.n, RGG_SPEC.radius,
                              P, 2, chunk_P=16)


def _reassemble(plan, **stream_kw) -> np.ndarray:
    """Group streamed rows by PE and concatenate the valid payload —
    the documented reconstruction of the run output from wave prefixes
    (per-PE stream order is exact; PEs concatenate pe-major).  Every
    row is the wave program's own buffer: one device's array, ``[B,
    ...]`` batched, or ``[...]`` with the unit batch axis dropped."""
    unbatched = stream_kw.get("batch", 1) <= 1
    per_pe = {}
    for pe, _, payload, valid in runtime.stream_slots(plan, **stream_kw):
        assert isinstance(payload, jax.Array) and isinstance(valid, jax.Array)
        assert len(payload.devices()) == 1
        assert valid.devices() == payload.devices()
        assert valid.ndim == (1 if unbatched else 2)
        assert payload.shape[:valid.ndim] == valid.shape
        per_pe.setdefault(pe, []).append(np.asarray(payload)[np.asarray(valid)])
    if not per_pe:
        return np.zeros((0,))
    return np.concatenate([x for pe in sorted(per_pe) for x in per_pe[pe]])


def _run_flat(plan) -> np.ndarray:
    payload, valid, _ = runtime.run(plan, check=False)
    return np.asarray(payload)[np.asarray(valid)]


COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@contextlib.contextmanager
def _no_compiles():
    """Fail if any program compiles inside the block (a ``jax.monitoring``
    listener): with the wave programs built, handing rows to the
    consumer must run no eager slice or getitem."""
    compiled = []

    def listen(event, duration, fun_name=None, **_):
        if event == COMPILE_EVENT:
            compiled.append(fun_name)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert compiled == [], compiled


# ------------------------------------------- stream == run bit-identity

@pytest.mark.parametrize("kind", ["chunk", "point", "pair"])
@pytest.mark.parametrize("batch", [1, 4])
@pytest.mark.parametrize("prefetch", [1, 2])
def test_stream_equals_run_bit_identical(kind, batch, prefetch):
    """Concatenating wave prefixes (grouped by PE) reproduces the
    materializing run output exactly, for every plan type, batch and
    prefetch depth, plain and through the overlapped plan emitter.  The
    rows are the wave programs' own buffers: once the programs are built
    (streaming waves without taking rows), handing the rows out compiles
    nothing."""
    plan = _plan_of(kind, 4)
    want = _run_flat(plan)
    assert len(want) > 0
    jax.clear_caches()     # an eager op another test compiled would hide
    for source in (lambda: plan,
                   lambda: runtime.PlanEmitter.from_plan(plan, 2)):
        for _ in runtime.stream_waves(source(), batch=batch,
                                      prefetch=prefetch):
            pass
        with _no_compiles():
            streamed = _reassemble(source(), batch=batch, prefetch=prefetch)
        np.testing.assert_array_equal(streamed, want)


def _row_sorted(a: np.ndarray) -> np.ndarray:
    """Rows sorted lexicographically (rows stay intact — a column-wise
    sort would destroy the pairing and pass on swapped endpoints)."""
    a = a.reshape(len(a), -1)
    return a[np.lexsort(a.T[::-1])]


@pytest.mark.parametrize("kind", ["chunk", "point", "pair"])
def test_streamed_output_P_invariant(kind):
    """The streamed multiset is bit-identically machine-size invariant:
    P in {1, 2, 8} produce the same rows (row-lexicographic comparison),
    and each P's stream reassembles to its own run output."""
    ref = None
    for P in (1, 2, 8):
        plan = _plan_of(kind, P)
        streamed = _reassemble(plan, batch=4)
        np.testing.assert_array_equal(streamed, _run_flat(plan))
        s = _row_sorted(streamed)
        if ref is None:
            ref = s
        np.testing.assert_array_equal(s, ref)


# ------------------------------------------------- wave schedule contract

def test_wave_schedule_never_straddles_pe_and_pads_ragged():
    """5 owned slots per PE at batch=4 -> waves of 4 and a ragged 1;
    padding rows are masked out, slot order per PE is preserved, and no
    batch mixes PEs."""
    plan = GNM_SPEC.plan(2)
    index = plan.stream_index()
    per_pe = [index[index[:, 0] == pe, 1] for pe in (0, 1)]
    assert any(len(s) % 4 for s in per_pe)  # the instance has ragged tails
    ws = runtime.wave_schedule(plan, D=1, batch=4)
    expect_waves = sum(-(-len(s) // 4) for s in per_pe)  # sum of ceils: no straddle
    assert ws.batch == 4 and ws.num_waves == expect_waves
    seen = {0: [], 1: []}
    for w in range(ws.num_waves):
        row = ws.rows[w][0]
        assert row is not None
        pe, slots = row
        assert 1 <= len(slots) <= 4
        assert ws.valid[w, 0, : len(slots)].all()
        assert not ws.valid[w, 0, len(slots):].any()  # ragged tail masked
        seen[pe].extend(slots.tolist())
    for pe in (0, 1):
        np.testing.assert_array_equal(seen[pe], per_pe[pe])


def test_ragged_final_wave_does_not_retrace():
    """Ragged last waves reuse the same compiled wave step (padded to
    the static batch shape): exactly one executable per program."""
    runtime.cache_clear()
    plan = GNM_SPEC.plan(2)
    streamed = _reassemble(plan, batch=4)
    np.testing.assert_array_equal(streamed, _run_flat(plan))
    wave_fns = [e.fn for k, e in runtime._CACHE.items() if k[0] == "wave"]
    assert len(wave_fns) == 1
    assert wave_fns[0]._cache_size() == 1  # one trace covers every wave


def test_batch_clamps_to_longest_pe_run():
    """A huge batch on a plan with few slots per PE must not pad every
    wave with dead rows: the slab batch clamps to the longest per-PE
    run (one wave per PE here, no padding beyond the ragged tail)."""
    plan = GNM_SPEC.plan(2)
    index = plan.stream_index()
    longest = max(int((index[:, 0] == pe).sum()) for pe in (0, 1))
    ws = runtime.wave_schedule(plan, D=1, batch=4096)
    assert ws.batch == longest and ws.num_waves == 2


# --------------------------------------------- the check=True wave assert

def test_check_asserts_on_wave_dispatch_once_per_signature(monkeypatch):
    """The old streams only lowered the *first slot's* fn; the runtime
    must assert zero collectives on the shard_map'd wave step itself,
    and exactly once per program signature across repeated streams."""
    runtime.cache_clear()
    calls = []
    real = runtime.assert_communication_free

    def spy(lowered):
        calls.append(lowered.as_text())
        return real(lowered)

    monkeypatch.setattr(runtime, "assert_communication_free", spy)
    plan = RHG_SPEC.plan(2)
    for _ in range(2):  # second stream: same signature, cached + checked
        for _ in runtime.stream_waves(plan, batch=4, check=True):
            pass
    assert len(calls) == 1
    # the asserted program is the wave step (slab-indexed gather), not a
    # single slot's fn: it consumes the [D, B, 2] schedule operand
    assert "tensor<1x4x2xi32>" in calls[0]


def test_engine_stream_facades_check_lowers_wave_step(monkeypatch):
    """The legacy stream entry points inherit the fixed check hole."""
    runtime.cache_clear()
    calls = []
    monkeypatch.setattr(runtime, "assert_communication_free",
                        lambda lowered: calls.append(1))
    plan = GNM_SPEC.plan(2)
    for _ in engine.stream_chunk_edges(plan, check=True):
        pass
    for _ in engine.stream_chunk_edges(plan, check=True):
        pass
    assert len(calls) == 1


# ----------------------------------------------------- point streaming

def test_stream_points_matches_run_points():
    """The PointPlan streaming path: masked streamed positions
    reassemble to run_points' masked output exactly, unbatched
    (``[cap, dim]`` rows) and batched."""
    plan = _plan_of("point", 4)
    pts, mask, hlo = engine.run_points(plan, check=True)
    assert not engine.collective_ops_in(hlo)
    for batch in (1, 2):
        per_pe = {}
        for pe, buf, m in engine.stream_points(plan, batch=batch,
                                               with_pe=True):
            assert buf.shape == ((pts.shape[2:]) if batch == 1
                                 else (2, *pts.shape[2:]))
            per_pe.setdefault(pe, []).append(np.asarray(buf)[np.asarray(m)])
        streamed = np.concatenate(
            [x for pe in sorted(per_pe) for x in per_pe[pe]])
        np.testing.assert_array_equal(streamed, pts[mask])
        assert len(streamed) == RGG_SPEC.n


@pytest.mark.parametrize("kind", ["chunk", "pair"])
def test_engine_stream_facades_match_run(kind):
    """The legacy unbatched facades hand out ``[cap, 2]`` buffers whose
    valid edges (the ``count`` prefix of a chunk, the ``keep`` mask of
    a pair) reassemble to the run output."""
    plan = _plan_of(kind, 4)
    payload, _, _ = runtime.run(plan, check=False)
    per_pe = {}
    if kind == "chunk":
        for pe, buf, count in engine.stream_chunk_edges(plan, with_pe=True):
            assert buf.shape == payload.shape[2:]
            per_pe.setdefault(pe, []).append(np.asarray(buf)[:count])
    else:
        for pe, buf, keep in engine.stream_pair_edges(plan, with_pe=True):
            assert buf.shape == payload.shape[2:]
            per_pe.setdefault(pe, []).append(np.asarray(buf)[np.asarray(keep)])
    streamed = np.concatenate([x for pe in sorted(per_pe) for x in per_pe[pe]])
    np.testing.assert_array_equal(streamed, _run_flat(plan))


def test_iter_points_streams_graph_positions():
    """api.iter_points: the O(capacity) route to Graph.points — the
    streamed positions are exactly the materialized ones (as sets; gid
    order is recovered per PE, positions are what matter here), at
    batch 1 (the default) and batch 2."""
    g = generate(RGG_SPEC, 4, return_points=True)
    b = {tuple(np.round(p, 12)) for p in g.points}
    for batch in (1, 2):
        chunks = list(iter_points(RGG_SPEC, 4, batch=batch))
        assert all(c.mask.ndim == (1 if batch == 1 else 2) for c in chunks)
        streamed = np.concatenate([c.points() for c in chunks])
        assert streamed.shape == g.points.shape
        assert {tuple(np.round(p, 12)) for p in streamed} == b


def test_iter_points_rejects_non_geometric_specs():
    with pytest.raises(TypeError, match="no vertex positions"):
        next(iter_points(GNM_SPEC, 2))


# ------------------------------------------------- mesh argument contract

def test_mesh_must_divide_plan_pes():
    plan = GNM_SPEC.plan(3)
    mesh = engine.default_mesh(1)
    # 1 device divides 3 PEs; a fabricated 2-row requirement cannot be
    # built on this machine, so exercise the validation path directly
    assert runtime.mesh_size(mesh) == 1
    with pytest.raises(ValueError, match="must be 0"):
        runtime._resolve_mesh(_FakePlan(3), _FakeMesh(2))


class _FakeMesh:
    def __init__(self, size):
        self.devices = np.empty(size, dtype=object)


class _FakePlan:
    def __init__(self, P):
        self.num_pes = P


# ------------------------------------------------- 8-device wave execution

def _run_with_devices(snippet: str, ndev: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["PYTHONPATH"] = os.pathsep.join(
        os.path.join(REPO, d) for d in ("src", "tests"))
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(snippet)],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_wave_streaming_uses_whole_mesh_and_matches_generate():
    """On a real 8-device mesh, every wave slab spans all 8 mesh rows
    (streaming uses the whole mesh, not the default device) and the
    per-PE reassembly reproduces generate() bit-for-bit for a ChunkPlan
    and two PairPlan families, at batch 1 and 2, plain and overlapped.
    Each chunk is its row's own buffer, on that mesh row's device
    alone, and handing chunks out compiles nothing once the wave
    programs are built."""
    out = _run_with_devices("""
        import numpy as np, jax
        from repro.api import (GNM, RGG, RHG, generate, iter_edge_chunks,
                               plan_emitter)
        from repro.distrib import runtime
        from test_runtime import _no_compiles

        assert len(jax.devices()) == 8
        for spec in (GNM(n=1024, m=8000, seed=5, chunks=16),
                     RGG(n=1024, radius=0.05, seed=3),
                     RHG(n=1024, avg_deg=8, gamma=2.8, seed=3)):
            P = 8
            plan = spec.plan(P)
            waves = list(runtime.stream_waves(plan, batch=2))
            D = len(waves[0].rows)
            assert D == 8, D  # one slab row per mesh device
            # each device's output block is its row: [D*B, ...] over 8
            assert len(waves[0].payload.sharding.device_set) == 8
            assert waves[0].payload.shape[0] == D * 2
            devices = runtime.mesh_for(P).devices.ravel()
            g = generate(spec, P)
            for batch in (1, 2):
                for overlap in (0, 4):
                    source = (plan_emitter(spec, P, segments=overlap)
                              if overlap else plan)
                    for _ in runtime.stream_waves(source, batch=batch):
                        pass
                    with _no_compiles():
                        chunks = list(iter_edge_chunks(
                            spec, P, batch=batch, overlap=overlap))
                    per_pe = {}
                    for c in chunks:
                        row = {devices[c.pe * D // P]}
                        assert c.buffer.devices() == c.mask.devices() == row
                        assert c.mask.ndim == (1 if batch == 1 else 2)
                        per_pe.setdefault(c.pe, []).append(c.edges())
                    streamed = np.concatenate(
                        [e for pe in sorted(per_pe) for e in per_pe[pe]])
                    np.testing.assert_array_equal(streamed, g.edges)
        print("WAVE8OK")
    """)
    assert "WAVE8OK" in out
