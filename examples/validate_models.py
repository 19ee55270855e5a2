"""The paper's §7 verification loop, streamed: generate each family and
gate it against its closed-form law without ever holding the edge list.

Every report is P-invariant (try changing P) because the streamed edge
multiset and the canonical vertex-ownership split both are.

    PYTHONPATH=src python examples/validate_models.py
"""
from repro.api import BA, GNM, GNP, RHG, RMAT, SBM
from repro.stats import collect, validate


def main():
    n, P = 1 << 13, 8
    specs = [
        GNP(n=n, p=16.0 / n, seed=1),
        GNM(n=n, m=8 * n, seed=2),
        SBM(n=n, blocks=8, p_in=0.02, p_out=0.001, seed=3),
        BA(n=n, d=8, seed=4),
        RMAT(n=n, m=8 * n, seed=5),
        RHG(n=n, avg_deg=8, gamma=2.7, seed=6),
    ]
    for spec in specs:
        print(validate(spec, P), end="\n\n")

    # sampled clustering: exact wedge/triangle counters over a hashed
    # deterministic vertex sample (so this, too, is P-invariant)
    r = collect(GNP(n=2048, p=0.01, seed=7), P,
                metrics=("degree", "clustering"), cluster_samples=128)
    print(f"GNP sampled clustering: global_cc={r.clustering.global_cc:.5f} "
          f"(ER expectation ~ p = 0.01), mean_local={r.clustering.mean_local_cc:.5f}")


if __name__ == "__main__":
    main()
