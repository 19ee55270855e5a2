"""Each cell's comparison, run end to end on the CPU at a size a test run
holds, with the harness's look for a chip skipped: the program comes
out correct; the control (the reference in the program's place, with a
guarantee broken or in float32) and each fault of ``bench/faults.py``
planted in the timed path come out not correct."""
import io
import time

import pytest

from bench.faults import FAULTS, broken_stream
from bench.harness.runner import run

SEED = 2**31 + 12345
SMALL = {
    "gnm-ef16.stream-s26": {"log_n": 12, "P": 64, "chunks": 64, "sample": 8},
    "rhg-d16.stream-s20": {"log_n": 14, "batch": 256, "edge_stride": 2},
}


def _run(cell, **kw):
    res = run(cell, SEED, 4.0, False, t_start=time.perf_counter(),
              require_tpu=False, traffic=dict(SMALL[cell]),
              out=io.StringIO(), err=io.StringIO(), **kw)
    assert res["attempted"] > 0
    return res


@pytest.mark.parametrize("cell", list(SMALL))
def test_program_is_correct(cell):
    assert _run(cell)["correct"]


@pytest.mark.parametrize("cell", list(SMALL))
def test_control_is_not_correct(cell):
    assert not _run(cell, control=True)["correct"]


@pytest.mark.parametrize("fault", list(FAULTS))
@pytest.mark.parametrize("cell", list(SMALL))
def test_fault_is_not_correct(monkeypatch, cell, fault):
    from repro import api

    monkeypatch.setattr(api, "iter_edge_chunks", broken_stream(fault))
    assert not _run(cell)["correct"]


def test_vertex_digest_matches_the_reference_at_the_threshold():
    """A sampled vertex's count and digest match its neighbour set with
    any of its threshold pairs added, and nothing else."""
    from bench.reference.rhg import StreamCheck, _hash

    xs, amb = [3, 9, 12], [40, 41]
    for extra in ([], [40], [41], [40, 41]):
        got = xs + extra
        assert StreamCheck._matches(len(got), _hash(got), xs, amb)
    assert not StreamCheck._matches(4, _hash(xs + [42]), xs, amb)
    assert not StreamCheck._matches(2, _hash(xs[:2]), xs, amb)
    assert not StreamCheck._matches(4, _hash(xs + [3]), xs, amb)
