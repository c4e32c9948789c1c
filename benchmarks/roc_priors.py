"""Paper Figures 9 & 10: ROC points for a 20-node graph (1,000 samples) with
progressively stronger pairwise priors, at 1,000 and 10,000 MCMC iterations.

Point construction follows §VI exactly: learn once with no prior; identify
mistakenly-removed / mistakenly-added edges; assign interface value hi/lo to
a random fraction of those mistakes; relearn.
"""
from __future__ import annotations

import numpy as np

from repro.core import random_cpts, random_dag, roc_point
from repro.data.bn_sampler import ancestral_sample
from repro.learner import LearnConfig, learn_structure

from .common import emit

# (interface hi for missing edges, lo for spurious edges, fraction of mistakes)
POINTS = [
    ("no-prior", None, None, 0.0),
    ("R=0.7/0.2 @20%", 0.7, 0.2, 0.2),
    ("R=0.7/0.2 @40%", 0.7, 0.2, 0.4),
    ("R=0.8/0.1 @20%", 0.8, 0.1, 0.2),
    ("R=0.8/0.1 @40%", 0.8, 0.1, 0.4),
]


def _prior_from_mistakes(rng, learned, truth, hi, lo, frac):
    n = truth.shape[0]
    R = np.full((n, n), 0.5, np.float32)
    missing = (truth == 1) & (learned == 0)       # mistakenly removed
    spurious = (learned == 1) & (truth == 0)      # mistakenly added
    for (m, i) in zip(*np.nonzero(missing)):
        if rng.random() < frac:
            R[i, m] = hi                          # R[i,m]: edge m -> i
    for (m, i) in zip(*np.nonzero(spurious)):
        if rng.random() < frac:
            R[i, m] = lo
    return R


def run(iters_list=(1000, 10000), n: int = 20, m: int = 1000,
        q: int = 2, chains: int = 2) -> list[dict]:
    rng = np.random.default_rng(3)
    truth = random_dag(rng, n, max_parents=4)
    data = ancestral_sample(rng, truth, random_cpts(rng, truth, q), m, q)
    rows = []
    for iters in iters_list:
        cfg = LearnConfig(q=q, s=4, iters=iters, seed=1, chains=chains)
        base = learn_structure(data, cfg)
        base_adj = base["adjacency"]
        for label, hi, lo, frac in POINTS:
            if hi is None:
                adj = base_adj
            else:
                R = _prior_from_mistakes(np.random.default_rng(5), base_adj,
                                         truth, hi, lo, frac)
                adj = learn_structure(data, cfg, prior_matrix=R)["adjacency"]
            fp, tp = roc_point(adj, truth)
            rows.append({"iters": iters, "prior": label,
                         "tp_rate": tp, "fp_rate": fp})
    emit("roc_priors", rows)
    return rows


if __name__ == "__main__":
    run()
