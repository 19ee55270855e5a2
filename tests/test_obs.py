"""repro.obs: tracer semantics, its mirror into the JAX profiler's
trace, spans that never wait for the device, the stream's host spans,
metrics exposition, phase attribution, the lint-role carve-out, and the
roofline model."""
import json
import os
import threading

import jax
import numpy as np
import pytest

from repro import obs
from repro.analyze.lint import RULE_WALLCLOCK, lint_paths, role_of
from repro.api import BA, GNM, GNP, RHG, RMAT, SBM, generate, iter_edge_chunks
from repro.core import rgg
from repro.distrib import runtime
from repro.launch import roofline
from repro.launch.hlocost import HloCost

_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src", "repro")


# ---------------------------------------------------------------- tracer

def test_disabled_trace_is_shared_null_singleton():
    obs.disable()
    obs.tracer().clear()
    s1 = obs.trace("anything", phase="plan")
    s2 = obs.trace("else")
    assert s1 is obs.NULL_SPAN and s2 is obs.NULL_SPAN
    with s1:
        s1.set(ignored=True)
    obs.event("also-ignored", hit=True)
    assert obs.tracer().spans() == []


def test_spans_nest_with_parent_ids():
    with obs.capture() as tr:
        with obs.trace("outer", phase="plan"):
            with obs.trace("inner", phase="exec"):
                pass
        with obs.trace("sibling"):
            pass
    recs = {r.name: r for r in tr.spans()}
    assert recs["inner"].parent_id == recs["outer"].span_id
    assert recs["outer"].parent_id == 0
    assert recs["sibling"].parent_id == 0
    assert recs["inner"].dur_ns <= recs["outer"].dur_ns


def test_phase_totals_shadow_same_phase_descendants():
    with obs.capture() as tr:
        with obs.trace("plan/outer", phase="plan"):
            # a reseed emitter re-entering its cold emitter: the nested
            # plan span must not double-count
            with obs.trace("plan/inner", phase="plan"):
                pass
            with obs.trace("exec/inner", phase="exec"):
                pass
    totals = tr.phase_totals()
    recs = {r.name: r for r in tr.spans()}
    assert totals["plan_s"] == pytest.approx(recs["plan/outer"].seconds)
    assert totals["exec_s"] == pytest.approx(recs["exec/inner"].seconds)
    assert totals["sink_s"] == 0.0


def test_span_set_attaches_attrs_and_events_nest():
    with obs.capture() as tr:
        with obs.trace("work", phase="exec") as sp:
            sp.set(rows=7)
            obs.event("marker", hit=True)
    recs = {r.name: r for r in tr.spans()}
    assert recs["work"].attrs["rows"] == 7
    assert recs["marker"].instant
    assert recs["marker"].parent_id == recs["work"].span_id
    assert recs["marker"].seconds == 0.0


def test_tracer_thread_safety_separate_stacks():
    with obs.capture() as tr:
        def worker(i):
            with obs.trace(f"t{i}", phase="exec"):
                pass
        threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
        with obs.trace("main-span", phase="plan"):
            for t in threads:
                t.start()
            for t in threads:
                t.join()
    recs = {r.name: r for r in tr.spans()}
    # spans on other threads must not parent under the main thread's span
    for i in range(4):
        assert recs[f"t{i}"].parent_id == 0
    assert len({r.span_id for r in tr.spans()}) == len(tr.spans())


def test_export_chrome_schema(tmp_path):
    path = tmp_path / "trace.json"
    with obs.capture() as tr:
        with obs.trace("span", phase="exec", n=3):
            obs.event("evt", hit=False)
        tr.export_chrome(str(path))
    data = json.loads(path.read_text())
    evs = data["traceEvents"]
    assert {e["ph"] for e in evs} == {"X", "i"}
    x = next(e for e in evs if e["ph"] == "X")
    i = next(e for e in evs if e["ph"] == "i")
    assert x["name"] == "span" and x["cat"] == "exec" and x["dur"] >= 0
    assert set(x) >= {"name", "cat", "ph", "ts", "dur", "pid", "tid", "args"}
    assert x["args"]["n"] == 3
    assert i["s"] == "t"
    assert "phases" in data["otherData"]


def test_capture_restores_previous_tracer():
    obs.disable()
    before = obs.tracer()
    with obs.capture() as tr:
        assert obs.tracer() is tr and obs.is_enabled()
    assert obs.tracer() is before and not obs.is_enabled()


class _AnnotationSpy:
    """Stands in for ``jax.profiler.TraceAnnotation``: records the names
    opened and whether each was closed."""

    def __init__(self):
        self.opened, self.closed = [], 0

    def __call__(self, name, **kw):
        spy = self

        class _Annotation:
            def __enter__(self):
                spy.opened.append(name)
                return self

            def __exit__(self, *exc):
                spy.closed += 1
                return False

        return _Annotation()


@pytest.mark.parametrize("enabled", [True, False])
def test_span_mirrors_into_a_profiler_annotation(monkeypatch, enabled):
    """An enabled span opens a ``TraceAnnotation`` of its own name (so
    it lands in the JAX profiler's trace on the device's clock) and
    closes it; a disabled one opens none."""
    spy = _AnnotationSpy()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", spy)
    obs.disable()
    if enabled:
        with obs.capture():
            with obs.trace("wave/rows", phase="sink"):
                with obs.trace("stream/chunk"):
                    pass
        assert spy.opened == ["wave/rows", "stream/chunk"]
        assert spy.closed == 2
    else:
        with obs.trace("wave/rows", phase="sink"):
            pass
        assert spy.opened == [] and spy.closed == 0


# ------------------------------------------- no span waits for the device

def _gnm_plan():
    return GNM(n=128, m=600, seed=2, chunks=8).plan(2)


def _flat(payload, valid):
    return np.asarray(payload)[np.asarray(valid)]


def _stream(plan):
    return [_flat(p, v) for _, _, p, v in runtime.stream_slots(plan, batch=2)]


def _run(plan):
    return [_flat(*runtime.run(plan, check=False)[:2])]


def _slab(plan):
    mesh = runtime.mesh_for(plan.num_pes)
    ppd = plan.num_pes // runtime.mesh_size(mesh)
    rows = tuple(a[::ppd, :2] for a in plan.input_arrays())
    valid = np.ones(rows[0].shape[:2], bool)
    return [_flat(*runtime.run_slab(plan.slot_fn, plan.signature(), valid,
                                    rows, mesh, check=False))]


@pytest.mark.parametrize("execute", [_stream, _run, _slab],
                         ids=["stream_waves", "run", "run_slab"])
def test_traced_execution_never_blocks(monkeypatch, execute):
    """Under ``obs.capture()`` the runtime dispatches exactly as it does
    untraced: no ``jax.block_until_ready`` anywhere, the same output."""
    plan = _gnm_plan()
    obs.disable()
    want = execute(plan)
    calls = []
    real = jax.block_until_ready

    def spy(x):
        calls.append(x)
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", spy)
    with obs.capture() as tr:
        got = execute(plan)
    assert calls == []
    assert tr.spans(), "tracing was on, so the runtime recorded spans"
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("spec,batch", [
    (GNM(n=256, m=1500, seed=3, chunks=8), 1),
    (RHG(n=300, avg_deg=6, gamma=2.7, seed=4), 4),
], ids=["gnm-chunk-batch1", "rhg-pair-batch4"])
def test_stream_host_spans_count_chunks(spec, batch):
    """``wave/rows`` (a row handed out by ``Wave.chunks``) and
    ``stream/chunk`` (building each ``EdgeChunk``) open once per chunk
    yielded, ``wave/setup`` once per plan; none is left open across a
    yield, so each closes before the consumer sees its chunk."""
    with obs.capture() as tr:
        n = 0
        for _ in iter_edge_chunks(spec, 2, batch=batch):
            n += 1
            assert tr._stack() == []
    names = [r.name for r in tr.spans()]
    assert n > 1
    assert names.count("wave/rows") == n
    assert names.count("stream/chunk") == n
    assert names.count("wave/setup") == 1


@pytest.mark.parametrize("spec,batch", [
    (GNM(n=256, m=1500, seed=3, chunks=8), 1),
    (RHG(n=300, avg_deg=6, gamma=2.7, seed=4), 4),
], ids=["gnm-chunk-batch1", "rhg-pair-batch4"])
def test_wave_row_event_marks_each_row_a_view(spec, batch):
    """``wave/row`` fires once per yielded row, and on a single-process
    mesh every row is the wave program's own buffer (``view=True``)."""
    with obs.capture() as tr:
        n = sum(1 for _ in iter_edge_chunks(spec, 2, batch=batch))
    rows = [r for r in tr.spans() if r.name == "wave/row"]
    assert n > 1 and len(rows) == n
    assert all(r.instant and r.attrs["view"] is True for r in rows)


def test_device_programs_are_named_by_step():
    """The wave, run and slab steps lower to programs named for the step
    (``jit_<name>`` in the device trace's ``XLA Modules`` line), not all
    ``jit_step``."""
    chunk = _gnm_plan()
    pair = RHG(n=300, avg_deg=6, gamma=2.7, seed=4).plan(2)
    point = rgg.rgg_point_plan(11, 300, 0.07, 2, 2, chunk_P=16)
    for plan, name in ((chunk, "wave_chunk"), (pair, "wave_pair"),
                       (point, "wave_point")):
        assert f"@jit_{name}" in runtime.lower_wave(plan).as_text()
    assert "@jit_run" in runtime.lower_run(chunk).as_text()
    rows = tuple(a[:1, :2] for a in chunk.input_arrays())
    low = runtime.lower_slab(chunk.slot_fn(), np.ones((1, 2), bool), rows,
                             runtime.mesh_for(1))
    assert "@jit_slab" in low.as_text()


# ---------------------------------------------------------------- metrics

def test_counter_monotonic():
    c = obs.Counter("c")
    c.inc()
    c.inc(2)
    assert c.value == 3
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_callback_reads_live():
    box = [1.0]
    g = obs.Gauge("g", fn=lambda: box[0])
    assert g.value == 1.0
    box[0] = 5.0
    assert g.value == 5.0


def test_histogram_buckets_and_percentile():
    h = obs.Histogram("h", buckets=(1.0, 10.0))
    for v in (0.5, 2.0, 20.0):
        h.observe(v)
    samples = dict(((n, labels), v) for n, labels, v in h.samples())
    assert samples[("h_bucket", (("le", "1"),))] == 1
    assert samples[("h_bucket", (("le", "10"),))] == 2
    assert samples[("h_bucket", (("le", "+Inf"),))] == 3
    assert samples[("h_count", ())] == 3
    assert h.percentile(0.5) == 2.0
    assert h.percentile(1.0) == 20.0


def test_registry_render_parse_round_trip():
    r = obs.Registry("x_")
    r.counter("reqs_total", "requests").inc(4)
    r.gauge("depth").set(2)
    r.histogram("lat_seconds", buckets=(0.1, 1.0)).observe(0.05)
    parsed = obs.parse_exposition(r.render())
    assert parsed["x_reqs_total"] == 4
    assert parsed["x_depth"] == 2
    assert parsed['x_lat_seconds_bucket{le="0.1"}'] == 1
    assert parsed["x_lat_seconds_count"] == 1


def test_parse_exposition_rejects_untyped_samples():
    with pytest.raises(ValueError):
        obs.parse_exposition("mystery_metric 1\n")


def test_registry_get_or_create_idempotent():
    r = obs.Registry()
    assert r.counter("a") is r.counter("a")
    assert r.counter("a", labels={"k": "v"}) is not r.counter("a")


# ------------------------------------------------- end-to-end attribution

def test_generate_traced_has_all_three_phases():
    spec = GNM(n=128, m=300, seed=1)
    generate(spec, 2)  # warm compile so exec span times the cached fn
    with obs.capture() as tr:
        generate(spec, 2)
    names = {r.name for r in tr.spans()}
    assert "plan/gnm" in names and "run/exec" in names and "extract" in names
    totals = tr.phase_totals()
    assert totals["plan_s"] > 0 and totals["exec_s"] > 0 and totals["sink_s"] > 0


@pytest.mark.parametrize("spec,span", [
    (GNM(n=64, m=100, seed=1), "plan/gnm"),
    (GNP(n=64, p=0.05, seed=1), "plan/gnp"),
    (BA(n=32, d=2, seed=1), "plan/ba"),
    (RMAT(n=1 << 5, m=64, seed=1), "plan/rmat"),
    (SBM(n=48, blocks=2, p_in=0.2, p_out=0.05, seed=1), "plan/sbm"),
])
def test_every_family_opens_its_plan_span(spec, span):
    with obs.capture() as tr:
        spec.plan(2)
    assert span in {r.name for r in tr.spans()}


def test_reseed_span_shadows_inner_plan_span():
    spec = GNM(n=128, m=300, seed=1)
    plan = spec.plan(2)
    with obs.capture() as tr:
        plan.reseed(2)
    recs = {r.name: r for r in tr.spans()}
    assert recs["plan/reseed"].attrs["reseed"] is True
    assert tr.phase_totals()["plan_s"] == pytest.approx(
        recs["plan/reseed"].seconds)


def test_disabled_tracing_records_nothing_through_generate():
    obs.disable()
    obs.tracer().clear()
    generate(GNM(n=64, m=100, seed=3), 2)
    assert obs.tracer().spans() == []


def test_compile_cache_events_hit_and_miss():
    from repro.distrib import runtime

    spec = GNM(n=64, m=128, seed=5)
    runtime.cache_clear()
    try:
        with obs.capture() as tr:
            generate(spec, 2)
            generate(spec, 2)
        evs = [r for r in tr.spans() if r.name == "compile_cache"]
        assert [e.attrs["hit"] for e in evs] == [False, True]
    finally:
        runtime.cache_clear()


# ---------------------------------------------------------------- linting

def test_obs_role_is_wallclock_exempt():
    assert role_of("src/repro/obs/tracer.py") == "obs"
    assert role_of("src/repro/obs/metrics.py") == "obs"
    findings = lint_paths([os.path.join(_SRC, "obs")])
    assert [f for f in findings if f.rule == RULE_WALLCLOCK] == []


def test_instrumented_emitters_stay_lint_clean():
    findings = lint_paths([os.path.join(_SRC, "core"),
                           os.path.join(_SRC, "distrib")])
    assert findings == [], "\n".join(f.format() for f in findings)


# --------------------------------------------------------------- roofline

_TOY_HLO = """
HloModule toy

ENTRY %main (p0: f32[128,128], p1: f32[128,128]) -> f32[128,128] {
  %p0 = f32[128,128] parameter(0)
  %p1 = f32[128,128] parameter(1)
  ROOT %dot = f32[128,128] dot(%p0, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""


def test_roofline_floor_and_fraction():
    peaks = roofline.Peaks(flops_per_s=1e9, bytes_per_s=1e9)
    assert roofline.roofline_seconds(2e9, 1e9, peaks) == pytest.approx(2.0)
    assert roofline.achieved_fraction(2e9, 1e9, 4.0, peaks) == pytest.approx(0.5)
    assert roofline.achieved_fraction(2e9, 1e9, 0.0, peaks) is None


def test_program_summary_from_hlo_cost():
    cost = HloCost(_TOY_HLO)
    assert cost.flops == 2 * 128 * 128 * 128
    peaks = roofline.Peaks(flops_per_s=1e9, bytes_per_s=1e12)
    s = roofline.program_summary(cost, measured_s=cost.flops / 1e9 * 2, peaks=peaks)
    assert s["bound"] == "compute"
    assert s["achieved_fraction"] == pytest.approx(0.5)


def test_peaks_keyed_by_device_kind():
    """v5e's published peaks by its JAX device kind; any kind not in
    the table is an error, never a default."""
    p = roofline.peaks_for("TPU v5 lite")
    assert p.flops_per_s == 197e12 and p.int8_ops_per_s == 393e12
    assert p.bytes_per_s == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.peaks_for("cpu")
