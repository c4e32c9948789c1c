"""Paper Figure 11: noise tolerance — each data entry flips state with
probability p; ROC of the learned 20-node graph (10,000-iteration sampling in
the paper; iteration count configurable for CPU budgets).

Rows land in BENCH_faults.json through benchmarks.common.save, keyed by
their config (n, m, q, s, iters, chains, flip_p — flip_p is a CONFIG_KEY),
so the trajectory merges like every other bench: a re-run at the same
config replaces its old row, the ``--smoke`` CI row (tiny iteration budget)
lands BESIDE the full-budget rows instead of clobbering them.
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.core import random_cpts, random_dag, roc_point
from repro.data.bn_sampler import ancestral_sample, inject_noise
from repro.learner import LearnConfig, learn_structure

try:
    from .common import emit
except ImportError:                       # run as a script, not a module
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks.common import emit


def run(ps=(0.0, 0.01, 0.05, 0.07, 0.1, 0.15), n: int = 20, m: int = 1000,
        q: int = 2, s: int = 4, iters: int = 2000,
        chains: int = 2) -> list[dict]:
    rng = np.random.default_rng(3)
    truth = random_dag(rng, n, max_parents=4)
    clean = ancestral_sample(rng, truth, random_cpts(rng, truth, q), m, q)
    rows = []
    for p in ps:
        data = clean if p == 0 else inject_noise(
            np.random.default_rng(11), clean, p, q)
        out = learn_structure(data, LearnConfig(q=q, s=s, iters=iters, seed=1,
                                                chains=chains))
        fp, tp = roc_point(out["adjacency"], truth)
        rows.append({"n": n, "m": m, "q": q, "s": s, "iters": iters,
                     "chains": chains, "flip_p": p,
                     "tp_rate": tp, "fp_rate": fp, "final_score": out["score"]})
    emit("BENCH_faults", rows)
    return rows


def main(argv=None) -> list[dict]:
    ap = argparse.ArgumentParser(description="Fig. 11 noise-tolerance sweep")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI budget: 2 noise levels, short walk (its "
                         "rows merge beside the full sweep, not over it)")
    ap.add_argument("--iters", type=int, default=0,
                    help="override the iteration budget (0 = default)")
    args = ap.parse_args(argv)
    if args.smoke:
        return run(ps=(0.0, 0.1), n=12, m=300,
                   iters=args.iters or 200, chains=2)
    return run(iters=args.iters or 2000)


if __name__ == "__main__":
    main()
