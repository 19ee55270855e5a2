"""Paper Fig. 6 (sequential ER comparison) + Fig. 7/8 (ER scaling).

Fig. 6 analog: our G(n,m) per-edge cost vs a Boost-style sequential
baseline (Vitter Algorithm-D-like skip sampling in numpy).
Fig. 7/8 analog: simulated weak scaling — max per-PE generation time as
P grows with fixed m/P (single machine executes PEs sequentially; the
communication-free property means per-PE times ARE the parallel time).
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import er
from .common import row, timeit, traced_phases, update_bench_json


def boost_style_baseline(seed: int, n: int, m: int) -> np.ndarray:
    """Sequential sorted-sample baseline (Algorithm D analog)."""
    rng = np.random.default_rng(seed)
    U = n * (n - 1)
    # sorted sample via exponential spacings + dedup-retry (expected O(m))
    k = m
    out = np.unique(rng.integers(0, U, size=int(k * 1.05) + 16))
    while len(out) < m:
        out = np.unique(np.concatenate([out, rng.integers(0, U, size=m)]))
    idx = out[:m]
    u = idx // (n - 1)
    c = idx % (n - 1)
    v = c + (c >= u)
    return np.stack([u, v], axis=1)


def bench_fig6():
    n = 1 << 20
    for m in (1 << 18, 1 << 20):
        t_ours = timeit(lambda: er.gnm_directed(0, n, m, P=1))  # repro: allow(no-deprecated-shim) legacy-path A/B baseline
        t_base = timeit(lambda: boost_style_baseline(0, n, m))
        row(f"er_seq_directed_n2^20_m2^{m.bit_length()-1}",
            t_ours / m * 1e6,
            f"ours_s={t_ours:.3f};baseline_s={t_base:.3f};speedup={t_base/t_ours:.2f}x")
        t_u = timeit(lambda: er.gnm_undirected(0, n, m // 2, P=1))  # repro: allow(no-deprecated-shim) legacy-path A/B baseline
        row(f"er_seq_undirected_n2^20_m2^{m.bit_length()-2}",
            t_u / (m // 2) * 1e6, f"ours_s={t_u:.3f}")


def bench_fig7_weak_scaling():
    m_per_pe = 1 << 18
    for P in (1, 2, 4, 8):
        m = m_per_pe * P
        n = m // 16
        per_pe = [
            timeit(lambda pe=pe: er.gnm_directed_pe(1, n, m, P, pe), warmup=1, iters=1)
            for pe in range(P)
        ]
        row(f"er_weak_directed_P{P}", max(per_pe) / m_per_pe * 1e6,
            f"max_pe_s={max(per_pe):.3f};imbalance={max(per_pe)/ (sum(per_pe)/P):.2f}")
        per_pe_u = [
            timeit(lambda pe=pe: er.gnm_undirected_pe(1, n, m // 2, P, pe), warmup=1, iters=1)
            for pe in range(P)
        ]
        row(f"er_weak_undirected_P{P}", max(per_pe_u) / m_per_pe * 1e6,
            f"max_pe_s={max(per_pe_u):.3f};recompute_bound=2x")


def bench_fig8_strong_scaling():
    m, n = 1 << 21, 1 << 17
    base = None
    for P in (1, 2, 4, 8):
        per_pe = [
            timeit(lambda pe=pe: er.gnm_directed_pe(2, n, m, P, pe), warmup=0, iters=1)
            for pe in range(P)
        ]
        t = max(per_pe)
        base = base or t
        row(f"er_strong_directed_P{P}", t / (m / P) * 1e6,
            f"speedup={base/t:.2f}x_of_{P}x")


def bench_engine_phases():
    """The engine path end-to-end (plan emit -> SPMD run -> extract),
    with the plan/exec/sink phase breakdown when tracing is on.  No span
    waits for the device, so ``exec_s`` is the run's dispatch alone; the
    device time falls in ``sink_s``, where extraction waits for it."""
    from repro.api import GNM, generate

    n, m, P = 1 << 16, 1 << 18, 8
    spec = GNM(n=n, m=m, seed=7, chunks=P)
    generate(spec, P)  # compile warmup
    t0 = time.perf_counter()
    g, phases = traced_phases(lambda: generate(spec, P))
    wall = time.perf_counter() - t0
    rec = {"n": n, "m": m, "P": P, "edges": int(g.edges.shape[0]),
           "wall_s": round(wall, 4)}
    if phases is not None:
        rec["phases"] = phases
    update_bench_json(f"er_engine_n2^16_P{P}", rec, name="er")
    row(f"er_engine_n2^16_P{P}", wall / m * 1e6,
        f"wall_s={wall:.3f}" + (
            f";plan_s={phases['plan_s']:.3f};dispatch_s={phases['exec_s']:.3f};"
            f"sink_s={phases['sink_s']:.3f}" if phases else ""))


def main():
    bench_fig6()
    bench_fig7_weak_scaling()
    bench_fig8_strong_scaling()
    bench_engine_phases()


if __name__ == "__main__":
    main()
