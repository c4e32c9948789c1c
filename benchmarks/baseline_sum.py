"""Paper §III-B: the max-based order score (Eq. 6, ours) vs the SUM-based
order score of Linderman et al. [5] — the baseline the paper improves on.

The paper's three claims, measured here on the same data/seeds:
  1. max needs only compare/assign ops (no exp/log): per-iteration time;
  2. sum can prefer an order whose best graph is NOT the global best:
     best-graph score achieved;
  3. max needs no postprocessing (the best graph falls out of scoring).

Both scorers now run their INCREMENTAL per-iteration path (ISSUE 3: the sum
scorer gained a per-node running-logsumexp cache spliced through the same
splice_window as the max deltas), so the per-iteration comparison is
like-for-like — what remains is the intrinsic exp/log cost, not an
implementation handicap. ``--full`` reverts both to full rescores.
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.core import random_cpts, random_dag, roc_point
from repro.data.bn_sampler import ancestral_sample
from repro.learner import LearnConfig, learn_structure

from .common import emit


def run(n: int = 20, m: int = 1000, q: int = 2, iters: int = 2000,
        chains: int = 2, window: int = 8) -> list[dict]:
    rng = np.random.default_rng(3)
    truth = random_dag(rng, n, max_parents=4)
    data = ancestral_sample(rng, truth, random_cpts(rng, truth, q), m, q)
    rows = []
    for scorer in ("max", "sum"):
        out = learn_structure(data, LearnConfig(
            q=q, s=4, iters=iters, chains=chains, seed=1, scorer=scorer,
            window=window))
        fp, tp = roc_point(out["adjacency"], truth)
        rows.append({
            "scorer": scorer,
            "path": (f"delta(w={out['delta_window']})" if out["delta_window"]
                     else "full") + ("+bitmask" if out["mask_cache"] else ""),
            "graph_score": "n/a (sum-score space)" if scorer == "sum" else
                           round(out["score"], 2),
            "per_iter_ms": out["per_iteration_s"] * 1e3,
            "tp_rate": tp, "fp_rate": fp,
            "postprocessing": "none (paper Eq. 6)" if scorer == "max"
                              else "argmax pass per sampled order",
        })
    emit("baseline_sum", rows)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full rescore every iteration for both scorers")
    ap.add_argument("--iters", type=int, default=2000)
    args = ap.parse_args()
    run(iters=args.iters, window=0 if args.full else 8)
