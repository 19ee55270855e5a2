"""The trace reduction, checked against a small trace recorded on one
TPU v5e (``bench/data/gnm-stream-tiny.xplane.pb.gz``: a traced
``gnm-ef16.stream-s26`` window cut to 0.42 s) and against hand-made
intervals."""
import gzip
import shutil
from pathlib import Path

import pytest

from bench.harness import xplane

DATA = Path(__file__).resolve().parents[1] / "data"


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "tiny.xplane.pb"
    with gzip.open(DATA / "gnm-stream-tiny.xplane.pb.gz") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return xplane.reduce_trace(str(path))


def test_window_and_busy_time(tiny):
    assert tiny.devices == 1
    assert tiny.window_s == pytest.approx(0.422117464, abs=1e-9)
    assert tiny.busy_s == pytest.approx(0.385836573, abs=1e-9)
    assert 0 < tiny.idle_share < 1


def test_ops_and_programs(tiny):
    assert tiny.ops[0][0] == "sort.32"
    assert tiny.ops[0][1] == pytest.approx(0.206290614, abs=1e-9)
    assert [s for _, s in tiny.ops] == sorted((s for _, s in tiny.ops), reverse=True)
    assert tiny.programs["jit_step"] == pytest.approx(0.378131741, abs=1e-9)
    assert tiny.program_seconds(bench=True) == pytest.approx(0.000641376, abs=1e-9)
    # every op lies inside some program: ops' union never exceeds programs'
    assert tiny.busy_s <= sum(tiny.programs.values()) + 1e-9


def test_idle_gaps_by_host_span(tiny):
    names = [n for n, _ in tiny.gaps]
    assert names[0] == "bench/next_chunk"
    assert tiny.gaps[0][1] == pytest.approx(0.031523417, abs=1e-9)
    idle = sum(s for _, s in tiny.gaps)
    assert idle == pytest.approx(tiny.window_s - tiny.busy_s, abs=1e-9)
    assert tiny.longest_gaps[0] == ("bench/next_chunk", pytest.approx(0.027330512, abs=1e-9))


def test_merge_overlapping_intervals():
    assert xplane._merge([(0, 2), (1, 3), (5, 6), (6, 7)]) == [[0, 3], [5, 7]]


def test_op_and_program_names():
    assert xplane._op_name("%sort.32 = (u32[1]) sort(u32[1] %x)") == "sort.32"
    assert xplane._program_name("jit_step(123)") == "jit_step"


def test_program_busy_leaves_out_benchmark_programs(tiny):
    own = tiny.busy_s - tiny.program_busy_s
    assert 0 < own <= tiny.program_seconds(bench=True) + 1e-9
    assert tiny.program_busy_s == pytest.approx(0.385195351, abs=1e-9)
