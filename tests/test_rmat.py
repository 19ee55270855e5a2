"""Graph 500's Kronecker instance: the label permutation, the chunk grid,
and the program against the benchmark's plain reference."""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import RMAT, generate, iter_edge_chunks
from repro.core import rmat
from repro.core.prng import device_key

ROOT = Path(__file__).resolve().parents[1]

PROBS = [(0.57, 0.19, 0.19, 0.05), (0.25, 0.25, 0.25, 0.25),
         (0.45, 0.15, 0.15, 0.25)]


def _reference():
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench.reference import rmat as ref
    return ref


def _key(seed):
    return device_key(seed, rmat._TAG_RMAT)


def _pi(seed, log_n, x):
    return np.asarray(rmat.permute(jnp.asarray(x, jnp.int64),
                                   rmat.perm_constants(_key(seed), log_n), log_n))


@pytest.mark.parametrize("log_n", range(1, 17))
def test_permutation_is_a_bijection(log_n):
    ids = np.arange(1 << log_n)
    assert np.array_equal(np.sort(_pi(3, log_n, ids)), ids)


def test_seeds_give_different_permutations():
    ids = np.arange(1 << 10)
    assert not np.array_equal(_pi(3, 10, ids), _pi(4, 10, ids))


def _pi_by_definition(seed, log_n, xs):
    """The docstring's two rounds in Python integers: the constants'
    full 64 bits, reduced mod 2^L at every step."""
    from jax import random

    kp = random.fold_in(_key(seed), rmat._TAG_PERM)
    c = []
    for j in range(4):
        h, lo = (int(w) for w in random.key_data(random.fold_in(kp, j))[:2])
        c.append((h << 32) | lo)
    mod = 1 << log_n
    out = []
    for x in xs:
        for r in range(2):
            x = ((x + c[2 * r]) * (c[2 * r + 1] | 1)) % mod
            x = int(format(x, f"0{log_n}b")[::-1], 2)
        out.append(x)
    return out


@pytest.mark.parametrize("log_n", [10, 26, 32, 40])
def test_permutation_follows_its_definition(log_n):
    """uint32 up to L = 32, uint64 beyond: both are the definition."""
    xs = [0, 1, 5, (1 << log_n) - 1] + list(
        np.random.default_rng(log_n).integers(0, 1 << log_n, 60))
    xs = [int(x) for x in xs]
    assert _pi(7, log_n, xs).tolist() == _pi_by_definition(7, log_n, xs)


def _regroup(chunks):
    per_pe = {}
    for c in chunks:
        per_pe.setdefault(c.pe, []).append(c.edges())
    return np.concatenate([e for pe in sorted(per_pe) for e in per_pe[pe]])


def _rows(e):
    return e[np.lexsort(e.T[::-1])]


@pytest.mark.parametrize("batch", [1, 2])
@pytest.mark.parametrize("P,chunks", [(1, 1), (1, 4), (4, 4), (4, 16)])
def test_stream_equals_generate_for_every_grid(P, chunks, batch):
    """Streamed PE by PE it is generate's output; every P and grid give
    the same edges (each edge's labels depend on its id alone)."""
    spec = RMAT(n=1 << 9, m=3000, seed=11, chunks=chunks)
    g = generate(spec, P).edges
    np.testing.assert_array_equal(
        _regroup(iter_edge_chunks(spec, P, batch=batch)), g)
    whole = generate(RMAT(n=1 << 9, m=3000, seed=11, chunks=1), 1).edges
    np.testing.assert_array_equal(_rows(g), _rows(whole))
    assert g.shape == (3000, 2) and g.min() >= 0 and g.max() < 1 << 9


def test_program_equals_the_plain_reference():
    """Chunk by chunk at log_n 10, against bench/reference/rmat.py, which
    shares no code with the program."""
    ref = _reference()
    seed, log_n, m, k = 2**31 + 77, 10, 16 << 10, 16
    spec = RMAT(n=1 << log_n, m=m, seed=seed, chunks=k)
    seen = 0
    for c in iter_edge_chunks(spec, k):
        e, ok = ref.chunk_edges(seed, log_n, m, k, c.pe, spec.probs)
        np.testing.assert_array_equal(c.edges(), np.asarray(e)[np.asarray(ok)])
        seen += 1
    assert seen == k


@pytest.mark.parametrize("n", [3 << 4, 0, 1, 1000])
def test_n_not_a_power_of_two_raises(n):
    with pytest.raises(ValueError, match="power of two"):
        RMAT(n=n, m=10)


def test_plan_span_carries_levels_and_chunks():
    from repro import obs

    with obs.capture() as tr:
        RMAT(n=1 << 7, m=100, seed=1, chunks=8).plan(2)
    rec = [r for r in tr.spans() if r.name == "plan/rmat"]
    assert len(rec) == 1
    assert rec[0].attrs["levels"] == 7 and rec[0].attrs["chunks"] == 8


@pytest.mark.parametrize("probs", PROBS)
def test_thresholds_equal_the_reference(probs):
    """The host's ceil(t 2^52) of t = a, a + b, a + b + c are the plain
    reference's (exact for (0.25, ...), where t 2^52 is an integer)."""
    thr = rmat.thresholds(probs)
    assert thr == tuple(int(t) for t in _reference().thresholds(probs))
    assert all(isinstance(t, int) and 0 <= t <= 1 << 52 for t in thr)


def _float64_answer(w, t):
    """u >= t for jax.random.uniform's float64 u = (w >> 12) / 2^52."""
    return np.float64(w >> 12) / 2.0**52 >= t


@pytest.mark.parametrize("probs", PROBS)
def test_words_at_a_threshold_give_the_float64_answer(probs):
    """Words at T << 12, one below it and the last word of its step,
    compared on their uint32 halves, give float64's answer."""
    a, b, c = probs[:3]
    words, ts, thr = [], [], []
    for T, t in zip(rmat.thresholds(probs), (a, a + b, a + b + c)):
        for w in (T << 12, (T << 12) - 1, (T << 12) + 4095, 0, (1 << 64) - 1):
            words.append(w)
            ts.append(t)
            thr.append(T)
    w = np.array(words, np.uint64)
    hi, lo = jnp.asarray(w >> 32, jnp.uint32), jnp.asarray(w, jnp.uint32)
    got = [bool(rmat._at_least(hi[i], lo[i], T)) for i, T in enumerate(thr)]
    assert got == [bool(_float64_answer(x, t)) for x, t in zip(words, ts)]
    assert got[:3] == [True, False, True]


def _float64_descent(key, eid, a, b, c, log_n):
    """The descent on float64 uniforms: the oracle of the integer one."""
    from repro.core.prng import fold_in64

    uu = jax.random.uniform(fold_in64(key, eid), (log_n,), dtype=jnp.float64)
    quad = ((uu >= a).astype(jnp.int64) + (uu >= a + b).astype(jnp.int64)
            + (uu >= a + b + c).astype(jnp.int64))
    bits = jnp.arange(log_n - 1, -1, -1, dtype=jnp.int64)
    return (jnp.sum((quad >= 2).astype(jnp.int64) << bits),
            jnp.sum((quad % 2) << bits))


@pytest.mark.parametrize("probs", PROBS)
def test_integer_descent_equals_the_float64_uniforms(probs):
    """12,288 edge ids, two thirds of them at or above 2^32, at 26
    levels: the integer descent is the float64 one, bit for bit."""
    log_n, key = 26, _key(2**31 + 5)
    eids = jnp.concatenate([
        jnp.arange(4096, dtype=jnp.int64),
        (1 << 32) - 2048 + jnp.arange(4096, dtype=jnp.int64),
        jnp.asarray(np.random.default_rng(1).integers(
            1 << 33, 1 << 40, 4096), jnp.int64)])
    thr = rmat.thresholds(probs)
    got = jax.jit(jax.vmap(lambda e: rmat.descend(key, e, thr, log_n)))(eids)
    want = jax.jit(jax.vmap(lambda e: _float64_descent(
        key, e, *probs[:3], log_n)))(eids)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert np.asarray(got[0]).max() < 1 << log_n


def test_probabilities_key_the_program():
    """A plan's thresholds are its probabilities'; other probabilities
    give another wave program and another serve program."""
    from repro.serve.scheduler import program_of

    plans = [RMAT(n=1 << 8, m=500, probs=p, seed=3, chunks=4).plan(2)
             for p in PROBS[:2]]
    assert [pl.rmat_thresholds for pl in plans] == [
        rmat.thresholds(p) for p in PROBS[:2]]
    assert plans[0].signature() != plans[1].signature()
    assert program_of(plans[0]) != program_of(plans[1])
    assert program_of(plans[0]).rmat_thr == plans[0].rmat_thresholds
    assert plans[0].reseed(9).signature() == plans[0].signature()


def test_mixed_probabilities_in_one_plan_raise():
    plan = RMAT(n=1 << 8, m=500, seed=3, chunks=4).plan(2)
    fp = plan.fparams.copy()
    fp[0, 0, 0] = 0.5
    with pytest.raises(ValueError, match="share their probabilities"):
        dataclasses.replace(plan, fparams=fp).rmat_thresholds
