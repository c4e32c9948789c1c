"""Quickstart: learn a small Bayesian network from synthetic data in ~30 s.

  PYTHONPATH=src python examples/quickstart.py

Covers the paper's full loop: ancestral-sample data from a known ground
truth -> precompute the local-score table (the 'hash table', Eq. 4) ->
order-space MCMC with the max-based order score (Eq. 6) -> recover the best
graph (no postprocessing) -> compare against the ground truth.
"""
import numpy as np

from repro.core import random_cpts, random_dag, roc_point
from repro.data.bn_sampler import ancestral_sample
from repro.learner import LearnConfig, learn_structure


def main():
    rng = np.random.default_rng(0)
    n, q, m = 12, 2, 2000
    truth = random_dag(rng, n, max_parents=3)
    data = ancestral_sample(rng, truth, random_cpts(rng, truth, q), m, q)

    out = learn_structure(data, LearnConfig(q=q, s=3, iters=2000, chains=2))

    fp, tp = roc_point(out["adjacency"], truth)
    print(f"nodes={n}  parent-set table S={out['S']}")
    print(f"best log-score  {out['score']:.2f}")
    print(f"preprocess      {out['preprocess_s']:.2f}s"
          f"   sampling {out['iteration_s']:.2f}s"
          f" ({out['per_iteration_s']*1e3:.2f} ms/iter)")
    print(f"accept rate     {out['accept_rate']:.2f}")
    print(f"TP rate {tp:.3f}   FP rate {fp:.4f}")
    print("\nlearned adjacency (rows=child's parents):")
    print(out["adjacency"])


if __name__ == "__main__":
    main()
