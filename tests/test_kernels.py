"""Per-kernel correctness: pallas_call (interpret mode on the CPU) vs pure-jnp ref,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.pairdist.ops import pad_points
from repro.kernels.pairdist.pairdist import pairdist_mask
from repro.kernels.pairdist.ref import pairdist_mask_ref


@pytest.mark.parametrize("m,n", [(128, 128), (256, 128), (128, 384), (512, 512)])
@pytest.mark.parametrize("dim", [2, 3])
def test_pairdist_matches_ref(m, n, dim):
    k = jax.random.key(m * n + dim)
    a = jax.random.uniform(k, (m, 8), dtype=jnp.float32)
    b = jax.random.uniform(jax.random.fold_in(k, 1), (n, 8), dtype=jnp.float32)
    r2 = 0.05
    got = pairdist_mask(a, b, r2, dim=dim)
    want = pairdist_mask_ref(a, b, r2, dim=dim)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("block", [64, 128, 256])
def test_pairdist_block_shapes(block):
    k = jax.random.key(0)
    a = jax.random.uniform(k, (256, 8), dtype=jnp.float32)
    b = jax.random.uniform(jax.random.fold_in(k, 1), (256, 8), dtype=jnp.float32)
    got = pairdist_mask(a, b, 0.1, dim=2, block_m=block, block_n=block)
    want = pairdist_mask_ref(a, b, 0.1, dim=2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pairdist_inf_padding_never_matches():
    pts = jnp.array([[0.1, 0.1], [0.2, 0.2]])
    padded = pad_points(pts)
    assert padded.shape == (128, 8)
    m = pairdist_mask(padded, padded, 1e9, dim=2)
    m = np.asarray(m)
    assert m[:2, :2].all()
    assert not m[2:, :].any() and not m[:, 2:].any()


def test_pairdist_threshold_is_inclusive():
    a = jnp.zeros((128, 8), jnp.float32)
    b = jnp.zeros((128, 8), jnp.float32).at[:, 0].set(0.5)
    m = pairdist_mask(a, b, 0.25, dim=2)
    assert np.asarray(m).all()  # dist^2 == r^2 exactly -> edge (<=)


# ----------------------------------------------------------------- pairmask

from repro.kernels.pairmask.pairmask import TILES, pair_mask
from repro.kernels.pairmask.ref import pair_mask_ref


def _tile_inputs(tile, m, n):
    k = jax.random.key(m * 31 + n)
    if tile == "euclid":
        a = jax.random.uniform(k, (m, 8), dtype=jnp.float32)
        b = jax.random.uniform(jax.random.fold_in(k, 1), (n, 8), dtype=jnp.float32)
        return a, b, 0.05
    from repro.kernels.hypdist.ops import precompute_features
    r = np.asarray(jax.random.uniform(k, (m,), minval=3.0, maxval=14.0))
    th = np.asarray(jax.random.uniform(jax.random.fold_in(k, 1), (m,),
                                       maxval=2 * np.pi))
    q = jnp.asarray(precompute_features(r, th))
    c = jnp.asarray(precompute_features(r[: n], th[: n])) if n <= m else None
    if c is None:
        r2 = np.asarray(jax.random.uniform(jax.random.fold_in(k, 2), (n,),
                                           minval=3.0, maxval=14.0))
        th2 = np.asarray(jax.random.uniform(jax.random.fold_in(k, 3), (n,),
                                            maxval=2 * np.pi))
        c = jnp.asarray(precompute_features(r2, th2))
    return q, c, np.cosh(14.0)


@pytest.mark.parametrize("tile", TILES)
@pytest.mark.parametrize("m,n", [(128, 128), (256, 384)])
def test_pair_mask_tiles_match_shared_ref(tile, m, n):
    """Both geometry kinds are tiles of one kernel: pallas_call output
    == the shared jnp reference for every tile kind."""
    a, b, s = _tile_inputs(tile, m, n)
    got = pair_mask(a, b, s, tile=tile, dim=2)
    want = pair_mask_ref(a, b, s, tile=tile, dim=2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("tile", TILES)
def test_pair_mask_facades_delegate(tile):
    """pairdist_mask / hypdist_mask are exact facades over pair_mask."""
    a, b, s = _tile_inputs(tile, 128, 128)
    unified = np.asarray(pair_mask(a, b, s, tile=tile, dim=3))
    if tile == "euclid":
        facade = pairdist_mask(a, b, s, dim=3)
    else:
        from repro.kernels.hypdist.hypdist import hypdist_mask as _hm
        facade = _hm(a, b, s)
    np.testing.assert_array_equal(unified, np.asarray(facade))


def test_pair_mask_rejects_unknown_tile():
    a = jnp.zeros((128, 8), jnp.float32)
    with pytest.raises(ValueError, match="unknown tile"):
        pair_mask(a, a, 1.0, tile="minkowski")
    with pytest.raises(ValueError, match="unknown tile"):
        pair_mask_ref(a, a, 1.0, tile="minkowski")


# ------------------------------------------------------------------ hypdist

from repro.kernels.hypdist.hypdist import hypdist_mask
from repro.kernels.hypdist.ops import pad_features, precompute_features
from repro.kernels.hypdist.ref import hypdist_mask_ref


def _random_features(key, n, R, dtype):
    import jax.random as jr
    r = jr.uniform(key, (n,), minval=0.3 * R, maxval=R)
    th = jr.uniform(jr.fold_in(key, 1), (n,), minval=0.0, maxval=2 * np.pi)
    return jnp.asarray(precompute_features(np.asarray(r), np.asarray(th), dtype=dtype))


@pytest.mark.parametrize("m,n", [(128, 128), (256, 128), (384, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_hypdist_matches_ref(m, n, dtype):
    R = 14.0
    q = _random_features(jax.random.key(m + n), m, R, dtype)
    c = _random_features(jax.random.key(m * n), n, R, dtype)
    got = hypdist_mask(q, c, np.cosh(R))
    want = hypdist_mask_ref(q, c, np.cosh(R))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_hypdist_matches_true_hyperbolic_distance():
    """Eq. 9 kernel == direct acosh evaluation of Eq. 4 (f64)."""
    rng = np.random.default_rng(0)
    n, R = 100, 12.0
    r = rng.uniform(0.3 * R, R, n)
    th = rng.uniform(0, 2 * np.pi, n)
    f = pad_features(precompute_features(r, th))
    got = np.asarray(hypdist_mask(jnp.asarray(f), jnp.asarray(f), np.cosh(R)))[:n, :n]
    arg = (np.cosh(r)[:, None] * np.cosh(r)[None, :]
           - np.sinh(r)[:, None] * np.sinh(r)[None, :] * np.cos(th[:, None] - th[None, :]))
    dist = np.arccosh(np.maximum(arg, 1.0))
    want = dist < R
    np.fill_diagonal(want, True)  # kernel does not exclude self-pairs
    disagree = (got.astype(bool) != want)
    # borderline float disagreements only; none expected at this scale
    assert disagree.sum() == 0


def test_hypdist_padding_rows_never_match():
    import warnings

    from repro.kernels.hypdist.ops import cosh_threshold

    f = precompute_features(np.array([8.0, 9.0]), np.array([0.1, 0.2]))
    p = pad_features(f)
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # cosh overflow must stay silent
        thr = cosh_threshold(1000.0)
        m = np.asarray(hypdist_mask(jnp.asarray(p), jnp.asarray(p), thr))
    assert not m[2:, :].any() and not m[:, 2:].any()


def test_cosh_threshold_matches_cosh_and_never_overflows():
    import warnings

    from repro.kernels.hypdist.ops import cosh_threshold

    for R in (0.0, 1.0, 14.0, 100.0, 699.0):
        assert cosh_threshold(R) == pytest.approx(np.cosh(R), rel=1e-15)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        for R in (701.0, 1000.0, 1e6):
            v = cosh_threshold(R)
            assert np.isfinite(v) and v > 0


# --------------------------------------------------------------------- hist

from repro.kernels.hist.hist import LOG2_BINS, hist_counts
from repro.kernels.hist.ops import (
    bincount_ids,
    degree_histogram,
    log2_histogram,
    pad_values,
)
from repro.kernels.hist.ref import hist_counts_ref, log2_bin_ref


@pytest.mark.parametrize("n,num_bins", [(1024, 64), (5000, 300), (2048, 1000)])
@pytest.mark.parametrize("log2", [False, True])
def test_hist_matches_ref(n, num_bins, log2):
    v = np.random.default_rng(n + num_bins).integers(0, 4 * num_bins, n)
    got = np.asarray(hist_counts(pad_values(v), num_bins=num_bins,
                                 log2=log2))[:num_bins]
    want = np.asarray(hist_counts_ref(v, num_bins=num_bins, log2=log2))
    np.testing.assert_array_equal(got, want)
    assert got.sum() == n  # every non-negative value lands in some bin


@pytest.mark.parametrize("block_v,block_b", [(256, 64), (1024, 128), (2048, 256)])
def test_hist_block_shapes(block_v, block_b):
    v = np.random.default_rng(0).integers(0, 500, 4096)
    got = np.asarray(hist_counts(pad_values(v, block=block_v), num_bins=500,
                                 block_v=block_v, block_b=block_b))[:500]
    np.testing.assert_array_equal(got, np.bincount(v, minlength=500))


def test_hist_padding_rows_count_nowhere():
    padded = pad_values(np.array([3, 3, 7]))
    assert padded.shape == (1024, 1) and int((padded >= 0).sum()) == 3
    got = np.asarray(degree_histogram(np.array([3, 3, 7]), 16))
    assert got.sum() == 3 and got[3] == 2 and got[7] == 1


def test_hist_log2_bin_semantics():
    """bin 0 <- 0; bin 1+k <- [2^k, 2^(k+1)): the log-binned degree
    histogram used at huge n."""
    v = np.array([0, 1, 2, 3, 4, 7, 8, 1 << 20, (1 << 31) - 1])
    bins = np.asarray(log2_bin_ref(v))
    np.testing.assert_array_equal(bins, [0, 1, 2, 2, 3, 3, 4, 21, 31])
    h = np.asarray(log2_histogram(v))
    assert h.shape == (LOG2_BINS,)
    np.testing.assert_array_equal(h, np.bincount(bins, minlength=LOG2_BINS))


def test_hist_overflow_clamps_to_last_bin():
    got = np.asarray(degree_histogram(np.array([1, 5, 99, 1000]), 8))
    assert got[7] == 2 and got.sum() == 4  # 99 and 1000 clamp into bin 7


def test_bincount_ids_both_paths_match_numpy():
    """Scatter-add dispatch: Pallas one-hot kernel below the bin limit,
    XLA scatter above — identical counts either way."""
    ids = np.random.default_rng(1).integers(0, 3000, 10_000)
    np.testing.assert_array_equal(np.asarray(bincount_ids(ids, 3000)),
                                  np.bincount(ids, minlength=3000))
    np.testing.assert_array_equal(np.asarray(bincount_ids(ids, 6000)),
                                  np.bincount(ids, minlength=6000))


def test_bincount_ids_drops_out_of_range_on_both_paths():
    """Sentinel / out-of-range ids must be dropped, not clamped into the
    last bin, on both sides of SCATTER_BINS_LIMIT."""
    ids = np.array([0, 1, 1, 99, 10_000])
    for length in (100, 5000):  # kernel path, XLA scatter path
        got = np.asarray(bincount_ids(ids, length))
        assert got.sum() == 4 and got[0] == 1 and got[1] == 2 and got[99] == 1
