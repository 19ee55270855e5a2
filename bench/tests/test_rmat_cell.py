"""The R-MAT cell's comparison, run end to end on the CPU at a size a
test run holds (n = 2^12, P = 64, a grid of 64 chunks, 8 sampled
chunks): the program comes out correct; the control (the reference with
the permutation left out) and each fault of ``bench/faults.py`` planted
in the timed path come out not correct.  And the reader of
``rmat_ns_per_level`` on hand-built readings."""
import importlib.util
import io
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench.faults import FAULTS, broken_stream
from bench.harness.runner import Readings, run

CELL = "rmat-g500.stream-s26"
SEED = 2**31 + 12345
SMALL = {"log_n": 12, "P": 64, "chunks": 64, "sample": 8}
BENCH = Path(__file__).resolve().parents[1]


def _run(**kw):
    res = run(CELL, SEED, 4.0, False, t_start=time.perf_counter(),
              require_tpu=False, traffic=dict(SMALL),
              out=io.StringIO(), err=io.StringIO(), **kw)
    assert res["attempted"] > 0
    return res


def test_program_is_correct():
    res = _run()
    assert res["correct"]
    assert set(res["checks"]) == {"chunk_count_mismatch",
                                  "sampled_digest_mismatch",
                                  "self_loop_mismatch"}


def test_control_is_not_correct():
    res = _run(control=True)
    assert not res["correct"]
    assert res["checks"]["sampled_digest_mismatch"]["value"] > 0


@pytest.mark.parametrize("fault", list(FAULTS))
def test_fault_is_not_correct(monkeypatch, fault):
    from repro import api

    monkeypatch.setattr(api, "iter_edge_chunks", broken_stream(fault))
    assert not _run()["correct"]


def _reader():
    path = BENCH / "metrics" / "rmat_ns_per_level.py"
    spec = importlib.util.spec_from_file_location("rmat_ns_per_level", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _readings(spans):
    trace = SimpleNamespace(program_seconds=lambda: 2.6)
    return Readings(CELL, spans, trace, {"edges": 10**8}, {}, 0, {})


def test_ns_per_level_reads_the_plan_span():
    read = _reader()
    span = SimpleNamespace(name="plan/rmat", attrs={"levels": 26, "chunks": 1024})
    assert read(_readings([span])) == pytest.approx(1.0)
    assert read(_readings([])) is None
    old = SimpleNamespace(name="plan/rmat", attrs={"P": 1024})
    assert read(_readings([old])) is None
