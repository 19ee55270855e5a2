"""The legacy per-family union entry points are deprecated shims: they
must warn, and they must still produce exactly api.generate's output.
Same contract for the legacy shard.py sharded entry points, now
deprecated onto repro.distrib.runtime facades."""
import numpy as np
import pytest

from repro.api import BA, GNM, GNP, RMAT, generate
from repro.core import ba, er, rmat


def _es(e):
    return {tuple(x) for x in np.asarray(e, np.int64)}


@pytest.mark.parametrize("shim,args,spec,P", [
    (er.gnm_directed, (3, 100, 400), GNM(n=100, m=400, directed=True, seed=3, chunks=2), 2),
    (er.gnm_undirected, (5, 100, 300), GNM(n=100, m=300, seed=5, chunks=2), 2),
    (er.gnp_undirected, (7, 100, 0.05), GNP(n=100, p=0.05, seed=7, chunks=2), 2),
    (ba.ba_union, (9, 100, 3), BA(n=100, d=3, seed=9), 2),
    (rmat.rmat_union, (1, 8, 900), RMAT(n=1 << 8, m=900, seed=1), 2),
], ids=lambda x: getattr(x, "__name__", ""))
def test_shim_warns_and_matches_generate(shim, args, spec, P):
    with pytest.warns(DeprecationWarning, match="deprecated shim"):
        legacy = shim(*args, P)
    np.testing.assert_array_equal(legacy, generate(spec, P).edges)
    assert _es(legacy) == _es(generate(spec, P).edges)


# ------------------------------------------- legacy shard.py entry points

def test_run_gnm_directed_sharded_warns_and_matches_runtime():
    from repro.distrib import engine, runtime, shard

    seed, n, m = 7, 200, 900
    mesh = engine.default_mesh(1)
    with pytest.warns(DeprecationWarning, match="deprecated shim"):
        legacy, hlo = shard.run_gnm_directed_sharded(seed, n, m, mesh)
    assert not engine.collective_ops_in(hlo)
    plan = er.gnm_directed_plan(seed, n, m, 1)
    edges, keep, _ = runtime.run(plan, mesh)
    np.testing.assert_array_equal(legacy, np.asarray(edges)[np.asarray(keep)])
    # and the shim's instance is exactly the chunks=P api instance
    assert _es(legacy) == _es(
        generate(GNM(n=n, m=m, directed=True, seed=seed, chunks=1), 1).edges)


def test_gnm_directed_sharded_warns_and_executes():
    from repro.distrib import engine, shard

    mesh = engine.default_mesh(1)
    with pytest.warns(DeprecationWarning, match="deprecated shim"):
        fn, inputs = shard.gnm_directed_sharded(3, 100, 400, mesh)
    edges, keep = fn(*inputs)
    assert int(np.asarray(keep).sum()) == 400


def test_rgg_points_sharded_warns_and_matches_runtime():
    from repro.core import rgg
    from repro.distrib import engine, runtime, shard

    seed, n, r = 2, 500, 0.05
    mesh = engine.default_mesh(1)
    with pytest.warns(DeprecationWarning, match="deprecated shim"):
        fn, inputs = shard.rgg_points_sharded(seed, n, r, mesh)
    pts, mask = fn(*inputs)
    ref_pts, ref_mask, _ = runtime.run(rgg.rgg_point_plan(seed, n, r, 1, 2), mesh)
    np.testing.assert_array_equal(np.asarray(pts), np.asarray(ref_pts))
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(ref_mask))
    assert int(np.asarray(mask).sum()) == n