"""The second attribution of a profiler trace (idle time by the
innermost program or benchmark span open), on hand-made intervals and
on two small traces recorded on one TPU v5e, one with the program's
spans in it (``bench/data/rhg-stream-tiny.xplane.pb.gz``); and the
reader of ``rows_ms_per_wave``."""
import gzip
import importlib.util
import shutil
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench.harness import attribution as at
from bench.harness import xplane

BENCH = Path(__file__).resolve().parents[1]

# a 100-ns window on one device: ops of the program at [10, 20] and
# [70, 80]; one at [40, 50] inside a benchmark program (idle, as
# device_idle.stream counts it)
WINDOW = (0.0, 100.0)
OPS = [("%a", 10.0, 10.0), ("%b", 40.0, 10.0), ("%c", 70.0, 10.0)]
MODULES = [("jit_wave_pair(1)", 10.0, 10.0), ("jit_bench_digest(2)", 38.0, 14.0),
           ("jit_wave_pair(1)", 70.0, 10.0)]
SPANS = [(0.0, 60.0, "bench/next_chunk"), (5.0, 30.0, "plan/rhg"),
         (5.0, 8.0, "plan/rhg/cells"), (32.0, 36.0, "wave/dispatch"),
         (55.0, 58.0, "wave/rows"), (62.0, 90.0, "bench/consume")]


@pytest.fixture()
def hand():
    return at.attribute([(OPS, MODULES)], SPANS, WINDOW)


def test_benchmark_programs_count_as_idle():
    assert at.program_busy(OPS, MODULES, WINDOW) == [[10.0, 20.0], [70.0, 80.0]]


def test_nesting_charges_the_innermost_span(hand):
    ns = {p: s * 1e9 for p, s in hand.paths.items()}
    nc = "bench/next_chunk"
    assert ns == pytest.approx({
        (nc,): 5 + 2 + 19 + 2,
        (nc, "plan/rhg"): 2 + 10,
        (nc, "plan/rhg", "plan/rhg/cells"): 3,
        (nc, "wave/dispatch"): 4,
        (nc, "wave/rows"): 3,
        ("bench/consume",): 8 + 10,
        (): 2 + 10,
    })
    labels = dict(hand.labels())
    assert labels[at.NO_SPAN] == pytest.approx(12e-9)
    assert labels["plan/rhg/cells"] == pytest.approx(3e-9)


def test_groups_and_sum(hand):
    assert hand.plan_share() == pytest.approx(15.0)
    assert hand.wave_host_share() == pytest.approx(7.0)
    assert hand.unspanned_share() == pytest.approx(28.0)
    # the labels sum to 1 - program_busy / window
    assert hand.idle_s / hand.window_s == pytest.approx(0.8, abs=1e-9)
    assert sum(s for _, s in hand.labels()) == pytest.approx(hand.idle_s, abs=1e-18)


def test_a_name_opened_twice_is_one_step():
    segs = at.span_segments([(0.0, 10.0, "bench/consume"),
                             (0.0, 10.0, "bench/consume"),
                             (2.0, 4.0, "wave/sink")], (0.0, 10.0))
    assert [p for _, _, p in segs] == [("bench/consume",),
                                       ("bench/consume", "wave/sink"),
                                       ("bench/consume",)]


def test_spans_cut_at_the_window():
    segs = at.span_segments([(-5.0, 3.0, "wave/sink"), (98.0, 120.0, "plan/gnm")],
                            (0.0, 100.0))
    assert segs == [(0.0, 3.0, ("wave/sink",)), (3.0, 98.0, ()),
                    (98.0, 100.0, ("plan/gnm",))]


def test_span_names():
    for name in ("plan/rhg", "wave/rows", "bench/next_chunk", "plan/overlap/wait"):
        assert at.SPAN_NAME.match(name)
    for name in ("PjitFunction(step)", "ParseArguments", "extract",
                 "tpu::System::Execute=>Done"):
        assert not at.SPAN_NAME.match(name)


def _unzip(tmp_path_factory, name):
    path = tmp_path_factory.mktemp("trace") / name.removesuffix(".gz")
    with gzip.open(BENCH / "data" / name) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


def test_labels_sum_to_device_idle_on_a_recorded_trace(tmp_path_factory):
    """The GNM trace holds the benchmark's spans alone: every label is
    one of them or no span, and the labels sum to its idle time."""
    path = _unzip(tmp_path_factory, "gnm-stream-tiny.xplane.pb.gz")
    a, s = at.idle_by_span(path), xplane.reduce_trace(path)
    assert a.window_s == pytest.approx(s.window_s, abs=1e-12)
    assert a.idle_s / a.window_s == pytest.approx(
        1.0 - s.program_busy_s / s.window_s, abs=1e-9)
    assert {n for n, _ in a.labels()} <= {
        "bench/next_chunk", "bench/drain", "bench/first_chunk",
        "bench/consume", at.NO_SPAN}


def test_recorded_rhg_trace_names_the_program_spans(tmp_path_factory):
    """A profiled ``rhg-d16.stream-s20`` half-window with ``repro.obs``
    on, at n = 2^14 and 256 pairs a wave, cut to 0.5 s (seed 2147500001,
    recorded by ``bench/attribute.py --keep-trace``).  Kept is what the
    reductions read: the device's ``XLA Ops`` and ``XLA Modules`` and the
    host thread that holds ``bench/window``; the event stats, the other
    lines and the host metadata plane were dropped, which leaves both
    reductions' results unchanged.  The idle time falls in the plan, the
    dispatch and the row slices, and the labels sum to it."""
    path = _unzip(tmp_path_factory, "rhg-stream-tiny.xplane.pb.gz")
    a, s = at.idle_by_span(path), xplane.reduce_trace(path)
    labels = dict(a.labels())
    for name in ("plan/rhg", "wave/dispatch", "wave/rows"):
        assert labels[name] > 0.005, name
    assert a.idle_s / a.window_s == pytest.approx(
        1.0 - s.program_busy_s / s.window_s, abs=1e-9)
    assert sum(labels.values()) == pytest.approx(a.idle_s, abs=1e-12)
    assert a.window_s == pytest.approx(0.503229138, abs=1e-9)
    assert labels["wave/rows"] == pytest.approx(0.236545099, abs=1e-9)
    assert a.unspanned_share() < 10.0
    # the pair step is named for itself in the device trace
    assert "jit_wave_pair" in s.programs and "jit_step" not in s.programs


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _span(name, seconds):
    return SimpleNamespace(name=name, seconds=seconds)


def test_rows_reader_reads_row_and_chunk_spans_per_wave():
    read = _reader("rows_ms_per_wave")
    rd = SimpleNamespace(spans=[_span("wave/rows", 0.002), _span("stream/chunk", 0.001),
                                _span("wave/dispatch", 0.5)], host={"waves": 2})
    assert read(rd) == pytest.approx(1.5)


@pytest.mark.parametrize("spans,waves", [
    ([], 4),                                        # a program without these spans
    ([_span("wave/dispatch", 0.001)], 4),
    ([_span("wave/rows", 0.001)], 0),
])
def test_rows_reader_returns_none_where_its_input_is_missing(spans, waves):
    assert _reader("rows_ms_per_wave")(
        SimpleNamespace(spans=spans, host={"waves": waves})) is None


@pytest.mark.parametrize("cell,listed", [("gnm-ef16.stream-s26", False),
                                         ("rhg-d16.stream-s20", True)])
def test_rows_metric_only_where_the_host_leads(cell, listed):
    """GNM's row slice waits for the wave step, so its spans would time
    the device: the metric is read in the host-bound cell alone."""
    from bench.harness.cell import find_cell, load_benchmark

    readers = find_cell(load_benchmark(), cell).readers()
    assert ("rows_ms_per_wave" in readers) is listed
