"""Paper Tables IV & V: end-to-end learning on the 11-node STN and the
37-node ALARM network — preprocessing vs iteration runtime split (Table IV),
and all-parent-sets vs size-limited preprocessing+scoring (Table V).

All-parent-sets is only feasible for the 11-node graph (s = n−1 = 10); for
20 nodes the paper itself needed 1123 s on a GPP, and the contingency dim
q^s explodes — we run the limited variant and report the skip explicitly.
"""
from __future__ import annotations

import numpy as np

from repro.core import random_cpts, roc_point
from repro.data.bn_sampler import ancestral_sample
from repro.data.networks import alarm_adjacency, stn_adjacency
from repro.learner import LearnConfig, learn_structure

from .common import emit


def _data(adj: np.ndarray, m: int, q: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return ancestral_sample(rng, adj, random_cpts(rng, adj, q), m, q)


def run(iters: int = 1000, m: int = 1000, q: int = 2) -> list[dict]:
    rows = []
    # ---- Table IV: STN (11 nodes) and ALARM (37 nodes), s=4
    for name, adj_fn in (("stn-11", stn_adjacency), ("alarm-37", alarm_adjacency)):
        adj = adj_fn()
        data = _data(adj, m, q, seed=0)
        out = learn_structure(data, LearnConfig(q=q, s=4, iters=iters))
        fp, tp = roc_point(out["adjacency"], adj)
        rows.append({
            "network": name, "parent_sets": "limited(s=4)", "S": out["S"],
            "preprocess_s": out["preprocess_s"],
            "iteration_s": out["iteration_s"],
            "total_s": out["preprocess_s"] + out["iteration_s"],
            "per_iter_ms": out["per_iteration_s"] * 1e3,
            "tp_rate": tp, "fp_rate": fp,
        })
    # ---- Table V: all parent sets vs limited, 11-node graph
    adj = stn_adjacency()
    data = _data(adj, m, q, seed=0)
    out = learn_structure(data, LearnConfig(q=q, s=10, iters=iters))
    fp, tp = roc_point(out["adjacency"], adj)
    rows.append({
        "network": "stn-11", "parent_sets": "all(s=10)", "S": out["S"],
        "preprocess_s": out["preprocess_s"],
        "iteration_s": out["iteration_s"],
        "total_s": out["preprocess_s"] + out["iteration_s"],
        "per_iter_ms": out["per_iteration_s"] * 1e3,
        "tp_rate": tp, "fp_rate": fp,
    })
    rows.append({
        "network": "random-20", "parent_sets": "all(s=19)", "S": "2^19",
        "preprocess_s": "skipped: q^s contingency dim infeasible "
                        "(the memory-saving strategy IS the point)",
        "iteration_s": "-", "total_s": "-", "per_iter_ms": "-",
        "tp_rate": "-", "fp_rate": "-",
    })
    # limited 20-node for the Table V comparison row
    rng = np.random.default_rng(7)
    from repro.core import random_dag
    adj20 = random_dag(rng, 20, max_parents=4)
    data20 = _data(adj20, m, q, seed=7)
    out = learn_structure(data20, LearnConfig(q=q, s=4, iters=iters))
    fp, tp = roc_point(out["adjacency"], adj20)
    rows.append({
        "network": "random-20", "parent_sets": "limited(s=4)", "S": out["S"],
        "preprocess_s": out["preprocess_s"],
        "iteration_s": out["iteration_s"],
        "total_s": out["preprocess_s"] + out["iteration_s"],
        "per_iter_ms": out["per_iteration_s"] * 1e3,
        "tp_rate": tp, "fp_rate": fp,
    })
    emit("table45_end2end", rows)
    return rows


if __name__ == "__main__":
    run()
