"""Quickstart: one GraphSpec -> plan -> run API for every network model.

Each spec is a frozen dataclass; `generate(spec, P)` plans the instance
on the host (O(P)-ish divide-and-conquer), executes it as one
zero-collective SPMD program on P virtual PEs, and returns a Graph.
The edge set is identical for any P — P only decides which PE executes
which chunk/cell/pair.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.api import BA, GNM, GNP, RDG, RGG, RHG, RMAT, SBM, generate
from repro.core import graph


def stats(name, g):
    deg = g.degrees()
    e = g.edges
    print(f"{name:22s} n={g.n:7d} m={g.m:8d} "
          f"avg_deg={deg.mean():6.2f} max_deg={deg.max():5.0f} "
          f"dups={graph.has_duplicates(e)} loops={graph.has_self_loops(e)}")


def main():
    seed, n, P = 42, 5000, 4

    specs = [
        ("G(n,m) directed", GNM(n=n, m=8 * n, directed=True, seed=seed)),
        ("G(n,m) undirected", GNM(n=n, m=4 * n, seed=seed)),
        ("G(n,p)", GNP(n=n, p=8.0 / n, seed=seed)),
        ("RGG 2d", RGG(n=n, radius=0.55 * float(np.sqrt(np.log(n) / n)), seed=seed)),
        ("RGG 3d", RGG(n=n, radius=0.55 * float((np.log(n) / n) ** (1 / 3)),
                       dim=3, seed=seed)),
        ("RHG (gamma=2.6)", RHG(n=1500, avg_deg=8, gamma=2.6, seed=seed)),
        ("RDG 2d (torus)", RDG(n=2000, seed=seed)),
        ("BA (d=4)", BA(n=n, d=4, seed=seed)),
        ("R-MAT", RMAT(n=1 << 13, m=8 * n, seed=seed)),
    ]
    for name, spec in specs:
        stats(name, generate(spec, P))
    stats("SBM (8 blocks)", generate(SBM(n=n, blocks=8, p_in=0.01, p_out=0.0005, seed=seed), P))

    print("\nEvery family above ran through the same GraphSpec -> plan -> run "
          "engine: the host emits per-PE chunk/cell/pair tables, devices "
          "execute them independently, and the lowered HLO is asserted to "
          "contain zero collective operations — no messages exchanged.")


if __name__ == "__main__":
    main()
