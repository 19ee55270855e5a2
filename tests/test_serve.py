"""Serving tier: plan-cache reseeds, packed mixed-request slabs,
continuous batching, fault reissue — all bit-identical to generate()."""
import dataclasses

import numpy as np
import pytest

from repro.api import (BA, GNM, GNP, RDG, RGG, RHG, RMAT, SBM, generate,
                       serve)
from repro.serve import PlanCache, Service, program_of, spec_shape

# ---------------------------------------------------------------- fixtures

def mixed_specs():
    """Eight families, distinct seeds — one of each shape."""
    return [
        GNM(n=128, m=400, seed=11),
        GNM(n=128, m=400, seed=12),            # same shape, new seed
        GNM(n=128, m=400, directed=True, seed=13),
        GNP(n=100, p=0.06, seed=5),
        BA(n=90, d=2, seed=3),
        RMAT(n=1 << 6, m=120, seed=9),
        SBM(n=96, blocks=3, p_in=0.2, p_out=0.02, seed=4),
        RGG(n=80, radius=0.2, seed=2),
        RHG(n=70, avg_deg=4.0, gamma=2.7, seed=8),
        RDG(n=40, seed=6),
    ]


def assert_graphs_equal(got, spec, P):
    ref = generate(spec, P)
    assert got.n == ref.n and got.directed == ref.directed
    np.testing.assert_array_equal(got.edges, ref.edges,
                                  err_msg=f"{spec} P={P}")


# ------------------------------------------------------- serve == generate

@pytest.mark.parametrize("P", [1, 2, 8])
def test_serve_matches_generate_mixed_families(P):
    """Concurrent mixed-family requests == per-request generate(),
    bit-for-bit, at several virtual PE counts."""
    specs = mixed_specs()
    svc = Service(P)
    for spec, g in zip(specs, svc.serve(specs)):
        assert_graphs_equal(g, spec, P)
    assert svc.stats["cache"]["hits"] >= 1  # the repeated GNM shape


def test_serve_64_concurrent_requests():
    """The acceptance-scale run: 64 concurrent requests across four
    families with distinct seeds, packed into shared slabs."""
    shapes = [
        lambda s: GNM(n=256, m=700, seed=s, chunks=8),
        lambda s: GNP(n=256, p=0.01, seed=s, chunks=8),
        lambda s: BA(n=128, d=2, seed=s),
        lambda s: RGG(n=96, radius=0.15, seed=s),
    ]
    specs = [shapes[i % 4](1000 + i) for i in range(64)]
    svc = Service(2, slab_batch=16)
    graphs = svc.serve(specs)
    for spec, g in zip(specs, graphs):
        assert_graphs_equal(g, spec, 2)
    st = svc.stats
    assert st["cache"]["hits"] == 60 and st["cache"]["misses"] == 4
    # packing really shares slabs: far fewer dispatches than slots
    assert st["slabs"] < st["slots"] / 4


def test_serve_function_front_door():
    specs = [GNM(n=64, m=100, seed=1), RGG(n=50, radius=0.25, seed=2)]
    for spec, g in zip(specs, serve(specs, 2)):
        assert_graphs_equal(g, spec, 2)


# ------------------------------------------------------------- plan cache

def test_spec_shape_excludes_seed():
    assert spec_shape(GNM(n=64, m=100, seed=1)) == spec_shape(
        GNM(n=64, m=100, seed=999))
    assert spec_shape(GNM(n=64, m=100, seed=1)) != spec_shape(
        GNM(n=64, m=101, seed=1))
    assert spec_shape(GNM(n=64, m=100, seed=1)) != spec_shape(
        GNP(n=64, p=0.1, seed=1))


def plans_equal(a, b):
    assert type(a) is type(b)
    for f in dataclasses.fields(a):
        if f.name == "reseed_fn":
            continue
        x, y = getattr(a, f.name), getattr(b, f.name)
        if isinstance(x, np.ndarray):
            np.testing.assert_array_equal(x, y, err_msg=f.name)
        else:
            assert x == y, (f.name, x, y)


@pytest.mark.parametrize("make", [
    lambda s: GNM(n=128, m=300, seed=s),
    lambda s: GNM(n=128, m=300, directed=True, seed=s),
    lambda s: GNP(n=100, p=0.05, seed=s),
    lambda s: BA(n=90, d=2, seed=s),
    lambda s: RMAT(n=1 << 6, m=120, seed=s),
    lambda s: SBM(n=96, blocks=3, p_in=0.2, p_out=0.02, seed=s),
    lambda s: RGG(n=80, radius=0.2, seed=s),
    lambda s: RHG(n=70, avg_deg=4.0, gamma=2.7, seed=s),
    lambda s: RDG(n=40, seed=s),
], ids=["gnm", "gnm-dir", "gnp", "ba", "rmat", "sbm", "rgg", "rhg", "rdg"])
def test_plan_cache_hit_reseed_equals_cold(make):
    """A cache hit reseeded to the request's seed == the cold plan for
    that seed, field by field — the tentpole invariant."""
    cache = PlanCache()
    cache.plan(make(7), 3, "threefry2x32")          # cold (miss)
    hot = cache.plan(make(8), 3, "threefry2x32")    # hit -> reseed
    assert cache.hits == 1 and cache.misses == 1
    plans_equal(hot, make(8).plan(3))


def test_plan_cache_lru_eviction():
    cache = PlanCache(capacity=2)
    for m in (100, 110, 120):
        cache.plan(GNM(n=64, m=m, seed=1), 1, "threefry2x32")
    assert cache.evictions == 1 and len(cache) == 2
    cache.plan(GNM(n=64, m=100, seed=2), 1, "threefry2x32")  # evicted: miss
    assert cache.misses == 4 and cache.hits == 0
    cache.plan(GNM(n=64, m=120, seed=3), 1, "threefry2x32")  # still warm
    assert cache.hits == 1


# -------------------------------------------------- packing & mixed slabs

def test_chunk_families_share_a_packing_group():
    """G(n,m) and BA rows execute under one slab program (KIND_*
    dispatch is per row), as do RGG and RHG rows (GEOM_* dispatch)."""
    a = program_of(GNM(n=128, m=300, seed=1).plan(2))
    b = program_of(BA(n=150, d=2, seed=2).plan(2))
    if a.capacity == b.capacity:  # same capacity class -> same program
        assert a.signature() == b.signature()
    assert a.kinds == b.kinds  # both lower the full sampled+BA dispatch
    g = program_of(RGG(n=80, radius=0.2, seed=1).plan(2))
    h = program_of(RHG(n=70, avg_deg=4.0, gamma=2.7, seed=2).plan(2))
    assert g.kinds == h.kinds  # HYP + TORUS in one program
    cert = program_of(RDG(n=40, seed=3).plan(2))
    assert cert.kinds != g.kinds  # CERT packs only with exact-capacity peers


# ------------------------------------------------- streaming & admission

def test_continuous_batching_preserves_chunk_order():
    """A request admitted mid-drain rides partially drained slabs, and
    both requests' chunk streams stay in per-request plan order."""
    first = GNM(n=256, m=900, seed=1, chunks=16)
    second = GNM(n=256, m=900, seed=2, chunks=16)
    svc = Service(2, slab_batch=4)
    t1 = svc.submit(first, sink="chunks")
    parts, t2 = [], None
    for i, chunk in enumerate(t1.chunks()):
        parts.append(chunk.edges())
        if i == 1:  # admit mid-stream, into partially drained queues
            t2 = svc.submit(second)
    np.testing.assert_array_equal(np.concatenate(parts),
                                  generate(first, 2).edges)
    assert_graphs_equal(t2.result(), second, 2)


def test_stats_sink_matches_graph():
    spec = SBM(n=96, blocks=3, p_in=0.2, p_out=0.02, seed=4)
    svc = Service(2)
    r = svc.submit(spec, sink="stats").result()
    g = generate(spec, 2)
    assert r["num_edges"] == g.m
    np.testing.assert_array_equal(r["degrees"], g.degrees())


def test_empty_request_yields_empty_graph():
    # m = 0 still enqueues its (count-0) chunk rows; the sink must
    # still produce a well-formed empty edge list.
    g = Service(1).submit(GNM(n=16, m=0, seed=1)).result()
    assert g.m == 0 and g.edges.shape == (0, 2)


# ----------------------------------------------------------- fault model

def test_fault_reissue_parity_multirow():
    """Killing a mesh row mid-slab reissues its slots onto survivors
    (reassign_after_failure) with bit-identical delivery.  Runs
    in-process when the host exposes >= 2 devices (CI forces 8 via
    XLA_FLAGS); the single-device case is covered by
    tests/test_distrib.py::test_failure_recovery_is_exact in a
    subprocess."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices (covered by test_distrib subprocess)")
    specs = [GNM(n=256, m=800, seed=s, chunks=16) for s in range(3)] + \
            [RGG(n=96, radius=0.15, seed=9)]
    svc = Service(len(jax.devices()), slab_batch=4)
    tickets = [svc.submit(s) for s in specs]
    svc.inject_fault([0, 1], at_slab=1)
    svc.drain()
    assert svc.scheduler.reissued > 0
    for spec, t in zip(specs, tickets):
        assert_graphs_equal(t.result(), spec, len(jax.devices()))


# ------------------------------------------------- stats & observability

def test_stats_counts_requests_and_queue():
    """The operational stats the ISSUE calls out: submitted/completed/
    in-flight/queue depth, live at every point of the request cycle."""
    svc = Service(2, slab_batch=4)
    st = svc.stats
    assert st["submitted"] == 0 and st["completed"] == 0
    assert st["inflight"] == 0 and st["queue_depth"] == 0

    tickets = [svc.submit(GNM(n=128, m=400, seed=s, chunks=8))
               for s in range(3)]
    st = svc.stats
    assert st["submitted"] == 3 and st["completed"] == 0
    assert st["inflight"] == 3 and st["queue_depth"] > 0

    svc.drain()
    st = svc.stats
    assert st["completed"] == 3 and st["inflight"] == 0
    assert st["queue_depth"] == 0
    assert all(t.done for t in tickets)


def test_metrics_exposition_parses_and_counts():
    from repro.obs import parse_exposition

    svc = Service(2, slab_batch=4)
    svc.serve(mixed_specs())
    parsed = parse_exposition(svc.metrics())
    n = len(mixed_specs())
    assert parsed["repro_serve_requests_submitted_total"] == n
    assert parsed["repro_serve_requests_completed_total"] == n
    assert parsed["repro_serve_inflight_requests"] == 0
    assert parsed["repro_serve_slabs_total"] == svc.stats["slabs"]
    assert parsed["repro_serve_plan_cache_hits"] == svc.stats["cache"]["hits"]
    assert parsed["repro_serve_ticket_latency_seconds_count"] == n
    assert svc.latency_percentile(0.5) is not None


def test_ticket_latency_stamped_under_mid_drain_admission():
    """Latency must be admission-to-completion per ticket even when a
    request is admitted into a partially drained queue."""
    svc = Service(2, slab_batch=4)
    t1 = svc.submit(GNM(n=256, m=900, seed=1, chunks=16), sink="chunks")
    t2 = None
    for i, _ in enumerate(t1.chunks()):
        if i == 0:  # admit mid-stream
            t2 = svc.submit(GNM(n=128, m=300, seed=2, chunks=8))
    svc.drain()
    assert t2 is not None and t2.done
    assert t1.latency is not None and t1.latency >= 0
    assert t2.latency is not None and t2.latency >= 0
    # t2 was admitted strictly after t1 yet completed inside t1's drain;
    # its latency window must be its own, not the service's
    assert svc.stats["completed"] == 2


# ---------------------------------------------------- contracts & errors

def test_packed_slab_programs_pass_contracts():
    """The registered serve-family slab programs lower clean: zero
    collectives, and no nondeterministic RNG on the recompute (pair)
    path."""
    from repro.analyze.programs import iter_programs, scan_case

    reports = [scan_case(c, with_cost=False)
               for c in iter_programs(families=["serve"], kernels=False)]
    assert len(reports) == 2
    for r in reports:
        assert r.ok, (r.name, r.error, [f.detail for f in r.scan.findings])


def test_unknown_sink_rejected():
    with pytest.raises(TypeError):
        Service(1).submit(GNM(n=16, m=10, seed=1), sink="bogus")
