"""Pairwise priors (paper §IV): encode confidence about single edges in the
interface matrix R and watch precision/recall move (paper Figs 9/10).

  PYTHONPATH=src python examples/priors_demo.py
"""
import numpy as np

from repro.core import random_cpts, random_dag, roc_point
from repro.core.priors import make_prior_matrix, ppf
from repro.data.bn_sampler import ancestral_sample
from repro.learner import LearnConfig, learn_structure


def main():
    rng = np.random.default_rng(3)
    n, q, m = 16, 2, 800                      # deliberately data-starved
    truth = random_dag(rng, n, max_parents=3)
    data = ancestral_sample(rng, truth, random_cpts(rng, truth, q), m, q)
    cfg = LearnConfig(q=q, s=3, iters=3000, seed=1)

    print("PPF(R): R=0.9 ->", f"{float(ppf(np.float32(0.9))):+.2f}",
          " R=0.5 -> +0.00   R=0.1 ->",
          f"{float(ppf(np.float32(0.1))):+.2f}", "(log10 units, Eq. 10)")

    base = learn_structure(data, cfg)
    fp0, tp0 = roc_point(base["adjacency"], truth)
    print(f"no prior:    TP {tp0:.3f}  FP {fp0:.4f}")

    # user knows 30% of the true edges exist (R=0.85)
    known = [(m_, i_) for (m_, i_) in zip(*np.nonzero(truth))
             if rng.random() < 0.3]
    R = make_prior_matrix(n, known_edges=known, confidence=0.85)
    out = learn_structure(data, cfg, prior_matrix=np.asarray(R))
    fp1, tp1 = roc_point(out["adjacency"], truth)
    print(f"edge priors on {len(known)} known edges: TP {tp1:.3f}  FP {fp1:.4f}")

    # user additionally forbids some non-edges (R=0.15)
    nonedges = [(a, b) for a in range(n) for b in range(n)
                if a != b and truth[a, b] == 0 and rng.random() < 0.1]
    R2 = make_prior_matrix(n, known_edges=known, forbidden_edges=nonedges,
                           confidence=0.85)
    out2 = learn_structure(data, cfg, prior_matrix=np.asarray(R2))
    fp2, tp2 = roc_point(out2["adjacency"], truth)
    print(f"+ forbidden priors on {len(nonedges)} non-edges: "
          f"TP {tp2:.3f}  FP {fp2:.4f}")


if __name__ == "__main__":
    main()
