"""repro.stats: streaming analytics (per-PE accumulators, clustering
samplers) and the paper-§7 statistical validation gates.

The acceptance-scale tests run the real thing: chi-square of an ER
G(n,p) degree distribution against Binomial and an RHG power-law tail
fit against 2*alpha + 1 at n = 2^18 on 8 virtual PEs, streamed —
the edge list is never materialized.
"""
import numpy as np
import pytest

from repro.api import BA, GNM, GNP, RHG, RMAT, SBM, generate
from repro.stats import (
    collect,
    expected_model,
    validate,
)

SMALL_SPECS = [
    GNP(n=1024, p=0.01, seed=3),
    GNM(n=1024, m=4096, seed=7),
    GNM(n=512, m=3000, directed=True, seed=5),
    BA(n=512, d=3, seed=9),
    RHG(n=768, avg_deg=8, gamma=2.9, seed=1),
    SBM(n=600, blocks=6, p_in=0.05, p_out=0.005, seed=2),
]


# ------------------------------------------------------- collect correctness

@pytest.mark.parametrize("spec", SMALL_SPECS, ids=lambda s: type(s).__name__)
def test_collect_degrees_match_generate(spec):
    """Streamed accumulation == degrees of the materialized graph."""
    r = collect(spec, 4)
    g = generate(spec, 4)
    assert r.num_edges == g.m
    if spec.directed:
        out_deg = np.bincount(g.edges[:, 0], minlength=g.n)
        in_deg = np.bincount(g.edges[:, 1], minlength=g.n)
        np.testing.assert_array_equal(r.degree.degrees, out_deg)
        np.testing.assert_array_equal(r.in_degree.degrees, in_deg)
    else:
        np.testing.assert_array_equal(r.degree.degrees, g.degrees())
    assert r.degree.deg_sum == int(r.degree.degrees.sum())
    assert r.degree.deg_max == int(r.degree.degrees.max())


@pytest.mark.parametrize("spec", SMALL_SPECS, ids=lambda s: type(s).__name__)
def test_collect_P_invariant(spec):
    """collect(spec, P=1) == collect(spec, P=8) for exact metrics: the
    streamed multiset and the ownership split are both P-independent."""
    r1, r8 = collect(spec, 1), collect(spec, 8)
    assert r1.num_edges == r8.num_edges
    np.testing.assert_array_equal(r1.degree.degrees, r8.degree.degrees)
    np.testing.assert_array_equal(r1.degree.log2_hist, r8.degree.log2_hist)
    assert (r1.degree.deg_sum, r1.degree.deg_sumsq, r1.degree.deg_max) == \
           (r8.degree.deg_sum, r8.degree.deg_sumsq, r8.degree.deg_max)


def test_clustering_P_invariant_and_exact():
    """Sampled clustering is exact per sampled vertex (vs a brute-force
    adjacency matrix) and P-invariant (hashed deterministic sample)."""
    spec = GNP(n=300, p=0.05, seed=9)
    r = collect(spec, 2, metrics=("degree", "clustering"), cluster_samples=32)
    g = generate(spec, 1)
    adj = np.zeros((spec.n, spec.n), bool)
    adj[g.edges[:, 0], g.edges[:, 1]] = True
    adj |= adj.T
    cc = r.clustering
    for si, s in enumerate(cc.sample):
        nb = np.nonzero(adj[s])[0]
        assert len(nb) == cc.degree[si]
        assert adj[np.ix_(nb, nb)].sum() // 2 == cc.triangles[si]
    r8 = collect(spec, 8, metrics=("degree", "clustering"), cluster_samples=32)
    np.testing.assert_array_equal(cc.triangles, r8.clustering.triangles)
    np.testing.assert_array_equal(cc.degree, r8.clustering.degree)
    assert cc.global_cc == r8.clustering.global_cc


def test_clustering_neighbor_cap_is_hard_and_exact():
    """Samples past neighbor_cap drop their stored neighbors mid-stream
    (hard memory bound) but still report their exact degree, and are
    excluded from the estimate."""
    spec = GNP(n=400, p=0.05, seed=13)
    cap = 15
    r = collect(spec, 2, metrics=("degree", "clustering"),
                cluster_samples=48, neighbor_cap=cap)
    cc = r.clustering
    true_deg = generate(spec, 1).degrees()[cc.sample]
    np.testing.assert_array_equal(cc.degree, true_deg)  # exact even past cap
    assert (cc.degree > cap).any()  # the cap actually triggered
    np.testing.assert_array_equal(cc.valid, (cc.degree >= 2) & (cc.degree <= cap))
    assert (cc.triangles[~cc.valid] == 0).all()


def test_clustering_empty_sample_is_a_noop():
    """cluster_samples=0 must degrade to an empty (all-zero) report,
    not crash on empty-array indexing."""
    r = collect(GNP(n=128, p=0.05, seed=1), 2,
                metrics=("degree", "clustering"), cluster_samples=0)
    assert len(r.clustering.sample) == 0
    assert r.clustering.global_cc == 0.0 and r.clustering.mean_local_cc == 0.0


def test_vertex_ownership_owner_of_agrees_with_split():
    """The two VertexOwnership views are one convention: owner_of(v)
    names the section whose split() part contains v."""
    from repro.stats import VertexOwnership

    own = VertexOwnership(n=1000, P=7)
    ids = np.random.default_rng(0).integers(0, 1000, 400)
    owners = own.owner_of(ids)
    assert ((own.bounds[owners] <= ids) & (ids < own.bounds[owners + 1])).all()
    for pe, part in enumerate(own.split(ids)):
        np.testing.assert_array_equal(part, np.sort(ids[owners == pe]))


def test_clustering_requires_undirected():
    with pytest.raises(ValueError, match="undirected"):
        collect(BA(n=64, d=2, seed=1), 2, metrics=("degree", "clustering"))


def test_unknown_metric_rejected():
    with pytest.raises(ValueError, match="unknown metric"):
        collect(GNP(n=64, p=0.1, seed=1), 2, metrics=("degree", "pagerank"))


def test_binned_mode_drops_exact_array_keeps_exact_summaries():
    """The huge-n path: no O(n) degree array in the merged report, but
    the log2 histogram and moments stay exact (== the exact path's)."""
    spec = GNP(n=4096, p=0.004, seed=4)
    rb = collect(spec, 4, mode="binned")
    re = collect(spec, 4, mode="exact")
    assert rb.degree.degrees is None and re.degree.degrees is not None
    np.testing.assert_array_equal(rb.degree.log2_hist, re.degree.log2_hist)
    assert rb.degree.deg_sum == re.degree.deg_sum
    assert rb.degree.deg_max == re.degree.deg_max
    assert rb.degree.num_isolated == re.degree.num_isolated


def test_degree_counts_is_exact_histogram():
    spec = GNM(n=512, m=2048, seed=11)
    r = collect(spec, 4)
    np.testing.assert_array_equal(
        r.degree_counts(), np.bincount(r.degree.degrees))
    assert r.degree_counts().sum() == spec.n


# --------------------------------------------------- model validation gates

def test_validate_er_chi_square_vs_binomial_2_18():
    """Acceptance gate: ER G(n,p) at n=2^18 on 8 PEs — the exact degree
    distribution passes chi-square against Binomial(n-1, p), streamed."""
    rep = validate(GNP(n=1 << 18, p=20.0 / (1 << 18), seed=11), 8)
    assert rep.passed, str(rep)
    chi = next(c for c in rep.checks if c.name == "degree-chi2")
    assert chi.passed and chi.pvalue > 1e-3
    assert rep.stats.num_edges > 2_500_000  # actually at scale


def test_validate_rhg_tail_exponent_2_18():
    """Acceptance gate: RHG at n=2^18 on 8 PEs — fitted power-law tail
    exponent matches the closed form 2*alpha + 1 == gamma."""
    spec = RHG(n=1 << 18, avg_deg=6, gamma=2.7, seed=2)
    rep = validate(spec, 8, batch=512)
    assert rep.passed, str(rep)
    tail = next(c for c in rep.checks if c.name == "tail-exponent")
    assert tail.expected == pytest.approx(2.7)
    mean = next(c for c in rep.checks if c.name == "mean-degree")
    assert mean.observed == pytest.approx(6.0, rel=0.1)


@pytest.mark.parametrize("spec", [
    GNM(n=2048, m=8192, seed=5),
    BA(n=2048, d=4, seed=7),
    SBM(n=1500, blocks=5, p_in=0.03, p_out=0.003, seed=3),
    RMAT(n=1 << 11, m=16000, seed=1),
], ids=lambda s: type(s).__name__)
def test_validate_smoke_other_families(spec):
    rep = validate(spec, 4)
    assert rep.passed, str(rep)


def test_chi_square_rejects_wrong_law():
    """Power, not just level: the same degree counts that pass against
    the true Binomial law must *reject* a 1.3x-off one."""
    from scipy import stats as sps

    from repro.stats import chi_square_gof

    spec = GNP(n=4096, p=0.004, seed=3)
    obs = collect(spec, 4).degree_counts()
    k = np.arange(len(obs))
    right = spec.n * sps.binom.pmf(k, spec.n - 1, spec.p)
    wrong = spec.n * sps.binom.pmf(k, spec.n - 1, 1.3 * spec.p)
    assert chi_square_gof(obs, right).pvalue > 1e-3
    assert chi_square_gof(obs, wrong).pvalue < 1e-6


def test_ks_discrete_level_and_power():
    """The conservative KS companion gate: passes the true Binomial
    law, rejects a 1.5x-off one."""
    from scipy import stats as sps

    from repro.stats import ks_discrete

    spec = GNP(n=4096, p=0.004, seed=3)
    deg = collect(spec, 4).degree.degrees
    k = np.arange(deg.max() + 1)
    assert ks_discrete(deg, sps.binom.cdf(k, spec.n - 1, spec.p)).pvalue > 1e-3
    assert ks_discrete(deg, sps.binom.cdf(k, spec.n - 1, 1.5 * spec.p)).pvalue < 1e-6


def test_expected_model_pmfs_are_distributions():
    for spec in (GNP(n=256, p=0.05, seed=1), GNM(n=256, m=900, seed=1),
                 SBM(n=240, blocks=4, p_in=0.1, p_out=0.01, seed=1)):
        m = expected_model(spec, kmax=255)
        assert m.degree_pmf is not None
        assert m.degree_pmf.sum() == pytest.approx(1.0, abs=1e-6)
        mu = float((np.arange(len(m.degree_pmf)) * m.degree_pmf).sum())
        assert mu == pytest.approx(m.mean_degree, rel=0.01)


# ----------------------------------------------------- api front-door wiring

def test_api_reexports_collect_validate():
    from repro import api

    spec = GNP(n=256, p=0.03, seed=2)
    r = api.collect(spec, 2)
    assert r.num_edges == generate(spec, 2).m
    assert api.validate(spec, 2).passed


def test_edge_chunks_carry_owning_pe():
    """The engine's ownership masks are surfaced per streamed chunk."""
    from repro.api import iter_edge_chunks

    spec = GNM(n=512, m=4000, seed=3)
    pes = [c.pe for c in iter_edge_chunks(spec, 4)]
    assert set(pes) <= set(range(4)) and len(set(pes)) > 1
    rhg = RHG(n=400, avg_deg=6, gamma=2.8, seed=1)
    for batch in (1, 64):
        chunks = list(iter_edge_chunks(rhg, 4, batch=batch))
        assert all(c.pe in range(4) for c in chunks)
        per_pe = {}
        for c in chunks:  # per-PE order is exact on any device count
            per_pe.setdefault(c.pe, []).append(c.edges())
        streamed = np.concatenate(
            [e for pe in sorted(per_pe) for e in per_pe[pe]])
        np.testing.assert_array_equal(streamed, generate(rhg, 4).edges)
