"""The unified GraphSpec -> plan -> run/stream API (repro.api): machine-
size invariance for every family, streaming == batch execution, engine
coverage (zero collectives) for all eight spec types, and bit-identity
of the new engine chunk kinds against their reference generators."""
import numpy as np
import pytest

from repro.api import (
    BA,
    GNM,
    GNP,
    RDG,
    RGG,
    RHG,
    RMAT,
    SBM,
    EdgeChunk,
    Graph,
    generate,
    iter_edge_chunks,
)
from repro.core import ba, graph, rmat, sbm
from repro.distrib import engine


def _es(e):
    return {tuple(x) for x in np.asarray(e, np.int64)}


ALL_SPECS = [
    GNM(n=200, m=900, seed=17),
    GNM(n=200, m=900, directed=True, seed=3),
    GNP(n=200, p=0.03, seed=5),
    GNP(n=200, p=0.02, directed=True, seed=5),
    BA(n=128, d=2, seed=5),
    RMAT(n=1 << 9, m=2000, seed=1),
    SBM(n=300, blocks=6, p_in=0.2, p_out=0.01, seed=5),
    RGG(n=300, radius=0.07, seed=11),
    RHG(n=400, avg_deg=8, gamma=2.8, seed=23),
    RDG(n=300, seed=318),
]


# ------------------------------------------------ machine-size invariance

@pytest.mark.parametrize("spec", ALL_SPECS, ids=lambda s: type(s).__name__)
def test_generate_edge_set_invariant_in_P(spec):
    """The instance is a function of the spec (virtual chunk grid), not
    of the PE count: P in {1, 2, 4} must yield identical edge sets."""
    sets = [_es(generate(spec, P).edges) for P in (1, 2, 4)]
    assert sets[0] == sets[1] == sets[2]
    assert len(sets[0]) > 0


def _regroup(chunks):
    """The documented reconstruction: per-PE stream order is exact, so
    grouping chunks by owning PE and concatenating pe-major reproduces
    the generate order on any device count (on a 1-device mesh the flat
    stream order already is generate order)."""
    per_pe = {}
    for c in chunks:
        per_pe.setdefault(c.pe, []).append(c.edges())
    return np.concatenate([e for pe in sorted(per_pe) for e in per_pe[pe]],
                          axis=0)


@pytest.mark.parametrize("spec", ALL_SPECS, ids=lambda s: type(s).__name__)
def test_iter_edge_chunks_concatenates_to_generate(spec):
    """Streaming is exact: per-PE chunk order and content match the
    batch run bit-for-bit, independent of local device count."""
    g = generate(spec, 4)
    streamed = _regroup(iter_edge_chunks(spec, 4))
    np.testing.assert_array_equal(streamed, g.edges)


def test_stream_buffers_bounded_by_plan_capacity():
    """The memory contract: every streamed buffer is one [cap, 2] chunk
    buffer — peak memory O(capacity), never O(total edges)."""
    spec = GNM(n=4000, m=60_000, seed=9)
    plan = spec.plan(4)
    seen = 0
    for chunk in iter_edge_chunks(spec, 4):
        assert chunk.buffer.shape == (plan.capacity, 2)
        seen += chunk.count
    assert seen == spec.m
    assert plan.capacity * plan.num_pes < spec.m  # buffers << total edges


def test_generate_returns_graph_metadata():
    g = generate(GNM(n=100, m=400, seed=1), 2)
    assert isinstance(g, Graph)
    assert (g.n, g.m, g.directed) == (100, 400, False)
    assert g.degrees().sum() == 2 * g.m


# ---------------------------------------- engine coverage, zero collectives

@pytest.mark.parametrize("spec", ALL_SPECS, ids=lambda s: type(s).__name__)
def test_all_spec_types_execute_through_engine(spec):
    """Every spec emits an engine plan and its SPMD lowering contains
    zero collectives (asserted on the HLO, not assumed)."""
    plan = spec.plan(4)
    if isinstance(plan, engine.ChunkPlan):
        _, hlo = engine.run_edges(plan)
    elif isinstance(plan, engine.PairPlan):
        _, hlo = engine.run_pairs(plan)
    else:
        _, _, hlo = engine.run_points(plan)
    assert not engine.collective_ops_in(hlo)


def test_engine_ba_bit_identical_to_sequential_reference():
    seed, n, d = 5, 200, 3
    edges, hlo = engine.run_edges(ba.ba_plan(seed, n, d, 4))
    assert not engine.collective_ops_in(hlo)
    np.testing.assert_array_equal(edges, ba.ba_sequential_reference(seed, n, d))


def test_engine_rmat_bit_identical_to_reference():
    seed, log_n, m, P = 1, 10, 5000, 4
    edges, hlo = engine.run_edges(rmat.rmat_plan(seed, log_n, m, P))
    assert not engine.collective_ops_in(hlo)
    ref = np.concatenate([rmat.rmat_pe(seed, log_n, m, P, pe) for pe in range(P)])
    np.testing.assert_array_equal(edges, ref)


def test_engine_sbm_matches_host_union_no_sort_dedup():
    """Canonical region ownership: engine per-PE concatenation equals
    the host union exactly, with no duplicate edges to dedup."""
    args = (5, 300, 6, 0.2, 0.01)
    plan = sbm.sbm_plan(*args, P=4)
    edges, hlo = engine.run_edges(plan)
    assert not engine.collective_ops_in(hlo)
    assert not graph.has_duplicates(edges)
    assert _es(edges) == _es(sbm.sbm_union(*args))
    # the plan mirrors cross-owner regions (recomputation) but owns each once
    assert plan.total_edges == len(edges)


def test_sbm_plan_regions_recomputed_on_both_owners():
    """Region (i, j) with i % P != j % P appears on both block owners'
    rows (the paper's recomputation bound), owned by exactly one."""
    plan = sbm.sbm_plan(9, 500, 6, 0.1, 0.02, P=3)
    key_rows = {}
    for pe in range(plan.num_pes):
        for c in range(plan.chunks_per_pe):
            if plan.kind[pe, c] == engine.KIND_EMPTY:
                continue
            k = plan.key_data[pe, c].tobytes()
            key_rows.setdefault(k, []).append(bool(plan.owned[pe, c]))
    assert any(len(v) == 2 for v in key_rows.values())
    for owners in key_rows.values():
        assert sum(owners) == 1  # exactly one owner per region


def test_rhg_pair_plan_matches_bruteforce_oracle():
    """The candidate-pair windows cover every adjacent pair: engine
    edges == O(n^2) oracle over the same (engine-layout) vertex set."""
    spec = RHG(n=500, avg_deg=6, gamma=2.6, seed=13)
    g = generate(spec, 4, return_points=True)
    from repro.core.rhg import rhg_brute_edges

    brute = rhg_brute_edges(g.points[:, 0], g.points[:, 1], spec.params.R)
    assert _es(g.edges) == _es(brute)
    assert not graph.has_duplicates(g.edges)


def test_deal_plan_conserves_owned_chunks():
    spec = GNM(n=300, m=2000, seed=4, chunks=12)
    p1, p3 = spec.plan(1), spec.plan(3)
    assert p1.num_pes == 1 and p3.num_pes == 3
    assert p1.total_edges == p3.total_edges == spec.m
    assert _es(engine.run_edges(p1)[0]) == _es(engine.run_edges(p3)[0])


def test_rbg_rng_impl_through_engine():
    """The 'rbg' perf path lowers, runs and stays collective-free; it is
    a different PRNG, so the instance differs from threefry."""
    spec = GNM(n=256, m=1200, directed=True, seed=7)
    tf = generate(spec, 4)
    rbg = generate(spec, 4, rng_impl="rbg")
    assert tf.m == rbg.m == 1200
    assert not graph.has_duplicates(rbg.edges)
    assert _es(tf.edges) != _es(rbg.edges)


# ------------------------------------------------------------- regressions

def test_degrees_empty_edge_array():
    """graph.degrees used to crash on asarray([]) (shape (0,) has no
    column axis); it must return zeros."""
    for empty in ([], np.zeros((0, 2), np.int64), np.asarray([])):
        d = graph.degrees(empty, 5)
        np.testing.assert_array_equal(d, np.zeros(5, np.int64))
    assert generate(GNP(n=50, p=0.0, seed=1), 2).degrees().sum() == 0


def test_edge_chunk_materialization():
    c = EdgeChunk(buffer=np.arange(10).reshape(5, 2), count=3)
    assert c.edges().shape == (3, 2)
    c = EdgeChunk(buffer=np.arange(10).reshape(5, 2),
                  mask=np.array([True, False, True, False, False]))
    assert c.edges().shape == (2, 2)
